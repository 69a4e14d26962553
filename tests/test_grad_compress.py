"""Segment-Means gradient compression over the pod (DCN) axis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.train.grad_compress import (compress, compress_with_feedback,
                                       compression_ratio, decompress)


def test_compress_identity_at_full_L():
    g = jnp.asarray(np.random.RandomState(0).randn(8, 4), jnp.float32)
    np.testing.assert_allclose(np.asarray(compress(g, 8)), np.asarray(g))


def test_decompress_is_transpose_of_compress():
    """<compress(g), z> == <g, decompress(z)>/seg — adjointness up to the
    mean's 1/seg factor (the property that makes the estimator unbiased)."""
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(12, 5), jnp.float32)
    z = jnp.asarray(rng.randn(4, 5), jnp.float32)
    seg = 3
    lhs = jnp.vdot(compress(g, 4), z)
    rhs = jnp.vdot(g, decompress(z, 12)) / seg
    assert float(lhs) == pytest.approx(float(rhs), rel=1e-5)


def test_error_feedback_telescopes():
    """Σ_t decompress(payload_t) == Σ_t g_t exactly once the stream stops —
    no gradient mass is ever lost (residual telescoping)."""
    rng = np.random.RandomState(2)
    gs = [jnp.asarray(rng.randn(16, 3), jnp.float32) for _ in range(5)]
    res = None
    transmitted = jnp.zeros((16, 3), jnp.float32)
    for g in gs:
        z, res = compress_with_feedback(g, res, 4)
        transmitted = transmitted + decompress(z, 16)
    total = sum(gs)
    # transmitted + residual == total gradient mass, exactly
    np.testing.assert_allclose(np.asarray(transmitted + res),
                               np.asarray(total), atol=1e-4, rtol=1e-5)


def test_compression_ratio():
    assert compression_ratio(64, 8) == 8.0
    assert compression_ratio(7, 8) == 1.0        # not compressible


@given(st.integers(1, 4), st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_compress_preserves_mean(lpow, spow):
    """The compressed payload carries the exact column means — the DC
    component of the gradient always crosses the wire."""
    L, seg = 2 ** lpow, 2 ** spow
    rng = np.random.RandomState(L * 10 + seg)
    g = jnp.asarray(rng.randn(L * seg, 3), jnp.float32)
    z = compress(g, L)
    np.testing.assert_allclose(np.asarray(z.mean(0)), np.asarray(g.mean(0)),
                               atol=1e-5)


def test_cross_pod_mean_subprocess():
    """compressed_cross_pod_mean under a real 2-pod shard_map — exercised via
    the distributed e2e script path (single-device fallback here): with
    L == rows the payload is lossless, so the result equals plain pmean."""
    from repro.train.grad_compress import compressed_cross_pod_mean

    g = {"w": jnp.asarray(np.random.RandomState(3).randn(8, 4), jnp.float32)}

    def f(gin):
        out, res = compressed_cross_pod_mean(gin, None, L=8, pod_axis="pod")
        return out

    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((1,), ("pod",))
    from jax.sharding import PartitionSpec as P
    with jax.sharding.set_mesh(mesh):
        out = jax.shard_map(f, in_specs=({"w": P(None, None)},),
                            out_specs={"w": P(None, None)},
                            axis_names={"pod"}, check_vma=False)(g)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=1e-6)
