"""Sanity check: shard_map exchange vs single-host oracles (8 host devices)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.api import ExecutionPlan
from repro.core.exchange import exchange_attention, decode_attention_sharded
from repro.core.partition import (simulate_prism_attention,
                                  simulate_voltage_attention)
from repro.core.prism_attention import reference_attention
from repro.launch.mesh import make_auto_mesh
from repro.transport import CodecSpec, codec_sim_attention

mesh = make_auto_mesh((4, 2), ("seq", "model"))
B, N, H, Hk, dh = 2, 64, 8, 4, 16
L = 4
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, N, H, dh), jnp.float32)
k = jnp.asarray(rng.randn(B, N, Hk, dh), jnp.float32)
v = jnp.asarray(rng.randn(B, N, Hk, dh), jnp.float32)

with jax.sharding.set_mesh(mesh):
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))

    for causal in (False, True):
        cfg = ExecutionPlan.voltage(seq_shards=4).to_exchange_config()
        out = jax.jit(lambda a, b, c: exchange_attention(a, b, c, cfg, causal=causal))(qs, ks, vs)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        print(f"voltage causal={causal} OK")

        cfg = ExecutionPlan.prism(L=L, seq_shards=4).to_exchange_config()
        out = jax.jit(lambda a, b, c: exchange_attention(a, b, c, cfg, causal=causal))(qs, ks, vs)
        ref = simulate_prism_attention(q, k, v, 4, L, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        print(f"prism causal={causal} OK")

    # PRISM == VOLTAGE when segment size == 1 (L = Np)
    cfg = ExecutionPlan.prism(L=N // 4, seq_shards=4).to_exchange_config()
    out = jax.jit(lambda a, b, c: exchange_attention(a, b, c, cfg, causal=False))(qs, ks, vs)
    # bidirectional, seg=1: means == tokens, but own-partition means masked and
    # local full used instead -> equals full attention
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    print("prism seg=1 == full OK")

    # chunked ring executor (compute/comm overlap) == full attention
    for causal in (False, True):
        for nch in (1, 2):
            cfgr = ExecutionPlan("voltage", seq_axis="seq", seq_shards=4,
                                 overlap_chunks=nch).to_exchange_config()
            out = jax.jit(lambda a, b, c: exchange_attention(
                a, b, c, cfgr, causal=causal))(qs, ks, vs)
            ref = reference_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=3e-5)
            print(f"ring chunks={nch} causal={causal} OK")

    # sharded codec exchange == single-host codec oracle
    for codec, param in (("int8", 0), ("int4", 0), ("topk", 8)):
        cfgc = ExecutionPlan("prism", seq_axis="seq", seq_shards=4,
                             codec=codec,
                             codec_param=param).to_exchange_config()
        out = jax.jit(lambda a, b, c: exchange_attention(
            a, b, c, cfgc, causal=True))(qs, ks, vs)
        ref = codec_sim_attention(q, k, v, 4, codec, CodecSpec(param=param),
                                  causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)
        print(f"codec {codec} sharded == sim oracle OK")

    # decode
    S = 64
    kc = jnp.asarray(rng.randn(B, S, Hk, dh), jnp.float32)
    vc = jnp.asarray(rng.randn(B, S, Hk, dh), jnp.float32)
    q1 = jnp.asarray(rng.randn(B, 1, H, dh), jnp.float32)
    clen = jnp.array([40, 64], jnp.int32)
    cspec = NamedSharding(mesh, P(None, "seq", None, None))
    kcs, vcs = jax.device_put(kc, cspec), jax.device_put(vc, cspec)
    cfg = ExecutionPlan.voltage(seq_shards=4).to_exchange_config()
    out = jax.jit(lambda a, b, c, d: decode_attention_sharded(a, b, c, d, cfg))(q1, kcs, vcs, clen)
    pos = jnp.arange(S)[None, :]
    ref = reference_attention(q1, kc, vc, kv_mask=pos < clen[:, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    print("decode sharded OK")

    # PRISM-decode (beyond-paper): locally cached remote means, zero
    # collectives on the seq axis. With seg size 1 (L = shard length) the
    # means ARE the tokens, so the result must equal exact decode.
    Sp = S // 4
    km = jnp.stack([kc[:, i * Sp:(i + 1) * Sp] for i in range(4)], axis=1)
    vm = jnp.stack([vc[:, i * Sp:(i + 1) * Sp] for i in range(4)], axis=1)
    cfgp = ExecutionPlan.prism(L=Sp, seq_shards=4).to_exchange_config()
    out = jax.jit(lambda a, b, c, d, e, f: decode_attention_sharded(
        a, b, c, d, cfgp, k_means=e, v_means=f))(
        q1, kcs, vcs, jnp.asarray(S), km, vm)
    ref = reference_attention(q1, kc, vc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    print("prism-decode seg=1 == exact OK")

print("ALL SANITY PASSED")
