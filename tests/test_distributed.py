"""Distributed correctness via subprocesses (8 host devices per process, so
the XLA device-count flag never leaks into this pytest process — smoke
tests here see 1 device, per the dry-run contract)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, os.path.join(ROOT, script)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


@pytest.mark.slow
def test_exchange_shard_map_equivalences():
    """shard_map PRISM/Voltage/decode == single-host oracles (8 devices)."""
    r = _run("scripts/sanity_exchange.py")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL SANITY PASSED" in r.stdout


@pytest.mark.slow
def test_e2e_distributed_train_and_decode():
    """PRISM/Voltage train steps + sharded decode on a (4×2) mesh."""
    r = _run("scripts/sanity_e2e_distributed.py")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "E2E DISTRIBUTED SANITY PASSED" in r.stdout


def test_exchange_sharding_helpers_raise_on_a_mesh_they_cannot_use():
    """The only quiet case is no mesh at all; a mesh without the named
    axes is an error.  A batch that does not divide over the batch axes is
    not: the exchange keeps it whole on every device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.core import exchange as xchg
    from repro.launch.mesh import make_auto_mesh

    t = jnp.zeros((3, 4, 2, 8))
    cfg = xchg.ExchangeConfig(xchg.ExchangeMode.PRISM, "seq", 1, L=2,
                              batch_axes=("data",))
    assert xchg._pin_seq_sharding(t, "seq") is t
    assert xchg._manual_batch_axes(3, cfg) == ()
    with jax.sharding.set_mesh(make_auto_mesh((1,), ("model",))):
        with pytest.raises(ValueError, match="not in the mesh"):
            xchg._pin_seq_sharding(t, "seq")
        with pytest.raises(ValueError, match="lack"):
            xchg._manual_batch_axes(3, cfg)
    with jax.sharding.set_mesh(make_auto_mesh((1, 1), ("data", "seq"))):
        assert xchg._manual_batch_axes(3, cfg) == ("data",)
        jax.jit(lambda x: xchg._pin_seq_sharding(x, "seq"))(t)
    two = jax.sharding.AbstractMesh((2, 1), ("data", "seq"),
                                    axis_types=(AxisType.Auto,) * 2)
    with jax.sharding.use_abstract_mesh(two):
        assert xchg._manual_batch_axes(3, cfg) == ()
        assert xchg._manual_batch_axes(4, cfg) == ("data",)
