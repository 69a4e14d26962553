"""Kernel-dispatch layer: backend resolution and pallas(interpret)-vs-
reference parity for every routed op, across dtypes and odd shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch as kdsp

RNG = np.random.RandomState(11)


def _pair(fn, *args, **kw):
    with kdsp.force_backend("pallas"):
        a = fn(*args, **kw)
    with kdsp.force_backend("reference"):
        b = fn(*args, **kw)
    return a, b


def _tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 else \
        dict(atol=3e-6, rtol=1e-6)


# --- backend resolution ----------------------------------------------------

def test_backend_resolution_order(monkeypatch):
    monkeypatch.delenv(kdsp.ENV_VAR, raising=False)
    assert kdsp.resolve_backend() in ("pallas", "reference")
    monkeypatch.setenv(kdsp.ENV_VAR, "pallas")
    assert kdsp.resolve_backend() == "pallas"
    prev = kdsp.set_backend("reference")      # override beats the env
    try:
        assert kdsp.resolve_backend() == "reference"
    finally:
        kdsp.set_backend(prev)
    monkeypatch.setenv(kdsp.ENV_VAR, "warp")
    with pytest.raises(ValueError, match="invalid"):
        kdsp.resolve_backend()
    with pytest.raises(ValueError, match="unknown kernel backend"):
        kdsp.set_backend("warp")


def test_backend_auto_matches_jax_backend(monkeypatch):
    monkeypatch.delenv(kdsp.ENV_VAR, raising=False)
    want = "pallas" if jax.default_backend() == "tpu" else "reference"
    with kdsp.force_backend("auto"):
        assert kdsp.resolve_backend() == want
    info = kdsp.backend_info()
    assert info["resolved"] == want and info["jax_backend"] is not None


# --- segment means ---------------------------------------------------------

@pytest.mark.parametrize("B,N,L,feat", [(1, 16, 4, (128,)), (2, 64, 8, (48,)),
                                        (3, 33, 11, (7,)),
                                        (2, 32, 8, (4, 16))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_means_parity(B, N, L, feat, dtype):
    x = jnp.asarray(RNG.randn(B, N, *feat), dtype)
    a, b = _pair(kdsp.segment_means, x, L, axis=1)
    assert a.shape == b.shape == (B, L, *feat)
    if dtype == jnp.float32:   # f32: kernel and reference are bit-compatible
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,N,L,feat", [(2, 32, 8, (4, 16)), (1, 24, 3, (5,)),
                                        (3, 48, 6, (2, 32))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_means_masked_parity(B, N, L, feat, dtype):
    x = jnp.asarray(RNG.randn(B, N, *feat), dtype)
    mask = jnp.asarray(RNG.rand(B, N) > 0.3)
    (am, ac), (bm, bc) = _pair(kdsp.segment_means_masked, x, L, mask, axis=1)
    np.testing.assert_array_equal(np.asarray(ac), np.asarray(bc))
    np.testing.assert_allclose(np.asarray(am, np.float32),
                               np.asarray(bm, np.float32), **_tol(dtype))


def test_segment_means_masked_empty_segment():
    """A fully-padded segment must produce count 0 (and a finite mean)."""
    x = jnp.asarray(RNG.randn(1, 16, 8), jnp.float32)
    mask = jnp.asarray(np.arange(16) < 8)[None, :]
    (am, ac), (bm, bc) = _pair(kdsp.segment_means_masked, x, 4, mask, axis=1)
    np.testing.assert_array_equal(np.asarray(ac), [[4, 4, 0, 0]])
    assert np.isfinite(np.asarray(am)).all()
    np.testing.assert_allclose(np.asarray(am), np.asarray(bm), atol=3e-6)


def _fallbacks(name):
    return kdsp.fallback_counts().get(name, 0)


def test_segment_means_non_token_axis_falls_back():
    """Axes the kernel can't tile still work (reference route), counted."""
    x = jnp.asarray(RNG.randn(2, 3, 12, 8), jnp.float32)
    before = _fallbacks("segment_means/layout")
    with kdsp.force_backend("pallas"):
        out = kdsp.segment_means(x, 4, axis=2)
    assert _fallbacks("segment_means/layout") == before + 1
    from repro.core.segment_means import segment_means as ref
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, 4, axis=2)),
                               atol=1e-6)


# --- decode attention ------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Hk,dh", [(1, 32, 2, 2, 16), (2, 64, 4, 2, 16),
                                         (3, 48, 6, 3, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_parity(B, S, H, Hk, dh, dtype):
    q = jnp.asarray(RNG.randn(B, 1, H, dh), dtype)
    k = jnp.asarray(RNG.randn(B, S, Hk, dh), dtype)
    v = jnp.asarray(RNG.randn(B, S, Hk, dh), dtype)
    clen = jnp.asarray(RNG.randint(1, S + 1, size=B))
    a, b = _pair(kdsp.decode_attention, q, k, v, clen)
    assert a.shape == b.shape == (B, 1, H, dh)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **_tol(dtype))


def test_decode_attention_window_softcap_parity():
    q = jnp.asarray(RNG.randn(1, 1, 4, 16), jnp.float32)
    k = jnp.asarray(RNG.randn(1, 64, 4, 16), jnp.float32)
    v = jnp.asarray(RNG.randn(1, 64, 4, 16), jnp.float32)
    a, b = _pair(kdsp.decode_attention, q, k, v, 50, window=16,
                 logit_softcap=30.0, scale=0.2)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)


def test_decode_attention_matches_sharded_entrypoint():
    """core.exchange.decode_attention_sharded (degenerate layout) is the
    wired call site — same numbers as calling the dispatch layer direct."""
    from repro.core.exchange import ExchangeConfig, decode_attention_sharded
    q = jnp.asarray(RNG.randn(2, 1, 4, 16), jnp.float32)
    k = jnp.asarray(RNG.randn(2, 32, 2, 16), jnp.float32)
    v = jnp.asarray(RNG.randn(2, 32, 2, 16), jnp.float32)
    clen = jnp.asarray([20, 32])
    for backend in ("pallas", "reference"):
        with kdsp.force_backend(backend):
            got = decode_attention_sharded(q, k, v, clen, ExchangeConfig())
            want = kdsp.decode_attention(q, k, v, clen)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


# --- PRISM prefill attention ----------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("counts", [False, True])
def test_prism_attention_parity(causal, counts):
    B, Nq, H, Hk, dh, P, L = 2, 16, 4, 2, 16, 2, 4
    q = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    kl = jnp.asarray(RNG.randn(B, Nq, Hk, dh), jnp.float32)
    vl = jnp.asarray(RNG.randn(B, Nq, Hk, dh), jnp.float32)
    km = jnp.asarray(RNG.randn(B, P, L, Hk, dh), jnp.float32)
    vm = jnp.asarray(RNG.randn(B, P, L, Hk, dh), jnp.float32)
    mc = (jnp.asarray(RNG.randint(0, 5, (B, P, L)), jnp.float32)
          if counts else None)
    a, b = _pair(kdsp.prism_attention, q, kl, vl, km, vm, 1, 4,
                 causal=causal, mean_counts=mc)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_prism_attention_masked_falls_back():
    """kv_mask has no kernel support — both backends must agree (reference
    route) rather than silently dropping the mask."""
    B, Nq, H, dh, P, L = 1, 8, 2, 8, 2, 2
    q = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    kl = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    vl = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    km = jnp.asarray(RNG.randn(B, P, L, H, dh), jnp.float32)
    vm = jnp.asarray(RNG.randn(B, P, L, H, dh), jnp.float32)
    mask = jnp.asarray([[True] * 6 + [False] * 2])
    before = _fallbacks("prism_attention/kv_mask")
    a, b = _pair(kdsp.prism_attention, q, kl, vl, km, vm, 0, 4,
                 kv_mask=mask)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _fallbacks("prism_attention/kv_mask") == before + 1


@pytest.mark.parametrize("masked", [False, True])
def test_prism_sim_routes_through_dispatch(masked):
    """The single-host PRISM simulation (the ``prism_sim`` plan) takes the
    kernels where they apply and counts a fallback per partition where they
    do not (padded keys), with the reference's numbers either way."""
    from repro.core.partition import simulate_prism_attention
    B, N, H, Hk, dh, P, L = 2, 32, 4, 2, 16, 4, 2
    q = jnp.asarray(RNG.randn(B, N, H, dh), jnp.float32)
    k = jnp.asarray(RNG.randn(B, N, Hk, dh), jnp.float32)
    v = jnp.asarray(RNG.randn(B, N, Hk, dh), jnp.float32)
    mask = (jnp.broadcast_to(jnp.arange(N) < 27, (B, N)) if masked
            else None)
    before = _fallbacks("prism_attention/kv_mask")
    a, b = _pair(simulate_prism_attention, q, k, v, P, L, kv_mask=mask)
    assert _fallbacks("prism_attention/kv_mask") == before + (P if masked
                                                              else 0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


@pytest.mark.parametrize("paged", [False, True])
def test_decode_head_dim_the_chip_cannot_tile_falls_back(monkeypatch, paged):
    """Compiled decode kernels read one KV head's (tokens, dh) slab, so a
    head dim that is not a lane multiple (with Hk > 1) runs the reference
    and is counted; interpret mode keeps taking any head dim."""
    B, S, H, Hk, dh, ps = 2, 32, 4, 2, 16, 8
    q = jnp.asarray(RNG.randn(B, 1, H, dh), jnp.float32)
    clen = jnp.asarray([20, 32])
    if paged:
        op, name = kdsp.decode_attention_paged, "decode_attention_paged"
        k = jnp.asarray(RNG.randn(B * S // ps, ps, Hk, dh), jnp.float32)
        v = jnp.asarray(RNG.randn(B * S // ps, ps, Hk, dh), jnp.float32)
        args = (q, k, v, jnp.arange(B * S // ps).reshape(B, S // ps), clen)
    else:
        op, name = kdsp.decode_attention, "decode_attention"
        k = jnp.asarray(RNG.randn(B, S, Hk, dh), jnp.float32)
        v = jnp.asarray(RNG.randn(B, S, Hk, dh), jnp.float32)
        args = (q, k, v, clen)
    with kdsp.force_backend("reference"):
        want = op(*args)
    before = _fallbacks(f"{name}/head_dim")
    with kdsp.force_backend("pallas"):
        op(*args)                                   # interpret: kernel
        assert _fallbacks(f"{name}/head_dim") == before
        monkeypatch.setattr(kdsp, "_interpret", lambda: False)
        got = op(*args)                             # "compiled": reference
    assert _fallbacks(f"{name}/head_dim") == before + 1
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
