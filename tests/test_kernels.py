"""Per-kernel shape/dtype sweeps vs the pure-jnp ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode import flash_decode_op, flash_decode_ref
from repro.kernels.flash_decode.ops import (merge_partials, validity_bias,
                                           validity_mask)
from repro.kernels.prism_attention import (prism_attention_op,
                                           prism_attention_ref)
from repro.kernels.prism_attention.ops import build_mean_bias
from repro.kernels.segment_means import segment_means_op, segment_means_ref

RNG = np.random.RandomState(7)


def _tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 else \
        dict(atol=3e-5, rtol=1e-5)


@pytest.mark.parametrize("B,N,D,L", [(1, 16, 128, 4), (2, 64, 48, 8),
                                     (3, 33, 7, 11), (1, 256, 512, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_means_sweep(B, N, D, L, dtype):
    if N % L:
        pytest.skip("integer segments only")
    x = jnp.asarray(RNG.randn(B, N, D), dtype)
    out = segment_means_op(x, L)
    ref = segment_means_ref(x, L)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_segment_means_nd_features():
    x = jnp.asarray(RNG.randn(2, 32, 4, 16), jnp.float32)   # [B, N, Hk, dh]
    out = segment_means_op(x, 8)
    ref = segment_means_ref(x.reshape(2, 32, 64), 8).reshape(2, 8, 4, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("B,Nq,H,Hk,dh,P,L",
                         [(1, 16, 2, 2, 8, 2, 2), (2, 32, 4, 2, 16, 4, 4),
                          (1, 128, 8, 8, 64, 2, 8), (1, 24, 6, 2, 32, 3, 2)])
@pytest.mark.parametrize("causal", [False, True])
def test_prism_attention_sweep(B, Nq, H, Hk, dh, P, L, causal):
    q = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    kl = jnp.asarray(RNG.randn(B, Nq, Hk, dh), jnp.float32)
    vl = jnp.asarray(RNG.randn(B, Nq, Hk, dh), jnp.float32)
    km = jnp.asarray(RNG.randn(B, P, L, Hk, dh), jnp.float32)
    vm = jnp.asarray(RNG.randn(B, P, L, Hk, dh), jnp.float32)
    pidx = P // 2
    out = prism_attention_op(q, kl, vl, km, vm, pidx, seg_size=4,
                             causal=causal)
    bias = build_mean_bias(B, P, L, pidx, 4, causal=causal)
    ref = prism_attention_ref(q, kl, vl, km.reshape(B, P * L, Hk, dh),
                              vm.reshape(B, P * L, Hk, dh), bias,
                              causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_prism_attention_bf16_and_softcap():
    B, Nq, H, dh, P, L = 1, 32, 2, 16, 2, 4
    q = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.bfloat16)
    kl = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.bfloat16)
    vl = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.bfloat16)
    km = jnp.asarray(RNG.randn(B, P, L, H, dh), jnp.bfloat16)
    vm = jnp.asarray(RNG.randn(B, P, L, H, dh), jnp.bfloat16)
    out = prism_attention_op(q, kl, vl, km, vm, 1, seg_size=4, causal=True,
                             softcap=50.0)
    bias = build_mean_bias(B, P, L, 1, 4, causal=True)
    ref = prism_attention_ref(q, kl, vl, km.reshape(B, P * L, H, dh),
                              vm.reshape(B, P * L, H, dh), bias, causal=True,
                              logit_softcap=50.0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2,
                               rtol=3e-2)


def test_prism_kernel_matches_core_semantics():
    from repro.core.prism_attention import prism_attention as core
    B, Nq, H, dh, P, L = 2, 32, 4, 16, 4, 4
    q = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    kl = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    vl = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    km = jnp.asarray(RNG.randn(B, P, L, H, dh), jnp.float32)
    vm = jnp.asarray(RNG.randn(B, P, L, H, dh), jnp.float32)
    for pidx in range(P):
        out = prism_attention_op(q, kl, vl, km, vm, pidx, seg_size=2,
                                 causal=True)
        ref = core(q, kl, vl, km, vm, pidx, 2, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)


@pytest.mark.parametrize("B,S,H,Hk,dh", [(1, 32, 2, 2, 16), (2, 64, 4, 2, 16),
                                         (1, 128, 8, 1, 64), (3, 48, 6, 3, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, S, H, Hk, dh, dtype):
    q = jnp.asarray(RNG.randn(B, H, dh), dtype)
    k = jnp.asarray(RNG.randn(B, S, Hk, dh), dtype)
    v = jnp.asarray(RNG.randn(B, S, Hk, dh), dtype)
    clen = jnp.asarray(RNG.randint(1, S + 1, size=B))
    o, m, l = flash_decode_op(q, k, v, clen)
    orf, mrf, lrf = flash_decode_ref(q, k, v, validity_bias(B, S, clen))
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(l), np.asarray(lrf),
                               **_tol(dtype))


def test_flash_decode_window():
    B, S, H, dh = 1, 64, 2, 16
    q = jnp.asarray(RNG.randn(B, H, dh), jnp.float32)
    k = jnp.asarray(RNG.randn(B, S, H, dh), jnp.float32)
    v = jnp.asarray(RNG.randn(B, S, H, dh), jnp.float32)
    o, m, l = flash_decode_op(q, k, v, 50, window=16)
    from repro.core.prism_attention import reference_attention
    pos = jnp.arange(S)[None, :]
    mask = (pos < 50) & (pos >= 50 - 16)
    full = reference_attention(q[:, None], k, v, kv_mask=mask)[:, 0]
    np.testing.assert_allclose(np.asarray(o / l[..., None]),
                               np.asarray(full), atol=3e-5)


@pytest.mark.parametrize("window", [None, 16])
def test_flash_decode_ignores_garbage_outside_valid_slots(window):
    """Cache slots outside the valid range may hold anything (a compiled
    prefill has left NaN past the prompt): the kernel selects them away in
    K and V, so its output equals the one over zeros there."""
    B, S, H, Hk, dh = 2, 64, 4, 2, 16
    q = jnp.asarray(RNG.randn(B, H, dh), jnp.float32)
    k = jnp.asarray(RNG.randn(B, S, Hk, dh), jnp.float32)
    v = jnp.asarray(RNG.randn(B, S, Hk, dh), jnp.float32)
    clen = jnp.asarray([20, 50])
    ok = validity_mask(B, S, clen, window=window)[:, :, None, None]
    clean = flash_decode_op(q, jnp.where(ok, k, 0.0), jnp.where(ok, v, 0.0),
                            clen, window=window)
    dirty = flash_decode_op(q, jnp.where(ok, k, jnp.nan),
                            jnp.where(ok, v, jnp.nan), clen, window=window)
    for a, b in zip(dirty, clean):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_flash_decode_paged_ignores_garbage_outside_valid_slots():
    """NaN past each row's length inside its last page, in pages the
    table names past the walk (one all-NaN page), and so in the VMEM
    block the kernel reuses: none of it counts.  Blocks of 16 pages."""
    from repro.kernels.flash_decode import flash_decode_paged_op
    B, ps, MP, H, Hk, dh = 2, 16, 20, 16, 8, 128
    q = jnp.asarray(RNG.randn(B, H, dh), jnp.float32)
    clen = jnp.asarray([11, 300])
    walked = -(-clen // ps)
    pt = jnp.where(jnp.arange(MP)[None] < walked[:, None],
                   jnp.arange(B * MP).reshape(B, MP), B * MP)
    ok = jnp.concatenate([
        validity_mask(B, MP * ps, clen).reshape(B * MP, ps),
        jnp.zeros((1, ps), bool)])[None, ..., None]
    k = jnp.asarray(RNG.randn(1, B * MP + 1, ps, Hk * dh), jnp.float32)
    v = jnp.asarray(RNG.randn(1, B * MP + 1, ps, Hk * dh), jnp.float32)
    clean = flash_decode_paged_op(q, jnp.where(ok, k, 0.0),
                                  jnp.where(ok, v, 0.0), 0, pt, clen,
                                  interpret=True)
    dirty = flash_decode_paged_op(q, jnp.where(ok, k, jnp.nan),
                                  jnp.where(ok, v, jnp.nan), 0, pt, clen,
                                  interpret=True)
    for a, b in zip(dirty, clean):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_flash_decode_merge_shards():
    B, S, H, dh, P = 2, 64, 4, 16, 4
    q = jnp.asarray(RNG.randn(B, H, dh), jnp.float32)
    k = jnp.asarray(RNG.randn(B, S, H, dh), jnp.float32)
    v = jnp.asarray(RNG.randn(B, S, H, dh), jnp.float32)
    clen = jnp.asarray([40, 64])
    parts = [flash_decode_op(q, k[:, i * 16:(i + 1) * 16],
                             v[:, i * 16:(i + 1) * 16], clen, offset=i * 16)
             for i in range(P)]
    merged = merge_partials(jnp.stack([p[0] for p in parts]),
                            jnp.stack([p[1] for p in parts]),
                            jnp.stack([p[2] for p in parts]))
    from repro.core.prism_attention import reference_attention
    pos = jnp.arange(S)[None, :]
    full = reference_attention(q[:, None], k, v,
                               kv_mask=pos < clen[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               atol=3e-5)


# --- paged flash decode (page table via scalar prefetch) --------------------

def _paged_case(*shape, lens="random"):
    return pytest.param(*shape, lens, id="-".join(map(str, shape))
                        + ("" if lens == "random" else f"-{lens}"))


@pytest.mark.parametrize("B,P,ps,MP,H,Hk,dh,lens", [
    _paged_case(2, 9, 16, 4, 4, 2, 16), _paged_case(3, 13, 8, 3, 6, 3, 32),
    _paged_case(1, 5, 32, 2, 8, 1, 64),
    # internlm2-1.8b widths: blocks of 16 (f32) or 32 (bf16) pages, so a
    # 40-page table holds 3 or 2 blocks, the last one short
    _paged_case(4, 161, 16, 40, 16, 8, 128, lens="edges"),
    _paged_case(1, 81, 16, 80, 16, 8, 128),               # the suffix scan
    _paged_case(3, 121, 16, 40, 16, 8, 128, lens="trash"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_matches_gather_reference(B, P, ps, MP, H, Hk,
                                                     dh, lens, dtype):
    """Pallas paged kernel (page table, lengths and layer through scalar
    prefetch, pages copied a block at a time) vs the jnp.take gather +
    dense reference, on random page tables with repeated pages, reading
    the second layer of a two-layer stacked pool.  Lengths: ragged at
    random; or ``edges``, one row each of 1, one block, one block + 1 and
    the whole table; or ``trash``, a row whose whole table points at one
    page, as an idle row's points at the trash page, beside a row whose
    length runs past its table."""
    from repro.kernels.flash_decode import (flash_decode_paged_op,
                                            flash_decode_paged_ref,
                                            gather_pages)
    from repro.kernels.flash_decode.paged import pages_per_block
    q = jnp.asarray(RNG.randn(B, H, dh), dtype)
    kp = jnp.asarray(RNG.randn(2, P, ps, Hk * dh), dtype)
    vp = jnp.asarray(RNG.randn(2, P, ps, Hk * dh), dtype)
    pt = RNG.randint(0, P, size=(B, MP))
    clen = RNG.randint(1, MP * ps + 1, size=B)
    if lens == "edges":
        bk = ps * pages_per_block(ps, Hk * dh, MP, kp.dtype.itemsize)
        assert MP * ps > bk + 1 and MP % (bk // ps)
        clen = np.asarray([1, bk, bk + 1, MP * ps])
    elif lens == "trash":
        pt[1] = P - 1
        clen[0] = MP * ps + 7
    pt, clen = jnp.asarray(pt, jnp.int32), jnp.asarray(clen)
    bias = validity_bias(B, MP * ps, clen)
    o, m, l = flash_decode_paged_op(q, kp, vp, 1, pt, clen, interpret=True)
    orf, mrf, lrf = flash_decode_paged_ref(q, kp, vp, 1, pt, bias)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(l), np.asarray(lrf),
                               **_tol(dtype))
    # and the gather itself is one layer's pages, row after row
    got = gather_pages(kp, 1, pt)
    assert got.shape == (B, MP * ps, Hk * dh)
    np.testing.assert_array_equal(
        np.asarray(got[0, ps:2 * ps], np.float32),
        np.asarray(kp[1, pt[0, 1]], np.float32))


# a three-layer stacked pool at internlm2-1.8b's K/V widths: rows ending
# exactly on a page boundary, one page past it, and an idle row whose
# table is all trash page (the pool's last)
_STACK = dict(L=3, P=13, ps=16, MP=4, H=16, Hk=8, dh=128)


def _stacked_case(dtype):
    L, P, ps, MP, H, Hk, dh = (_STACK[k] for k in
                               ("L", "P", "ps", "MP", "H", "Hk", "dh"))
    q = jnp.asarray(RNG.randn(4, H, dh), dtype)
    kp = jnp.asarray(RNG.randn(L, P, ps, Hk * dh), dtype)
    vp = jnp.asarray(RNG.randn(L, P, ps, Hk * dh), dtype)
    pt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11],
                      [P - 1] * MP], jnp.int32)
    clen = jnp.asarray([ps, 2 * ps, 2 * ps + 1, 1])
    return q, kp, vp, pt, clen


@pytest.mark.parametrize("layer", [0, _STACK["L"] - 1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_reads_only_its_layer(layer, dtype):
    """Through the stacked pool, the kernel (interpret mode) reads the
    pages of the layer it is given: it equals the gather reference of
    that layer, for the first and the last layer, with rows ending on a
    page boundary and an idle row on the trash page; and NaN in every
    other layer's pages, the same page ids, changes none of its output."""
    from repro.kernels.flash_decode import (flash_decode_paged_op,
                                            flash_decode_paged_ref)
    q, kp, vp, pt, clen = _stacked_case(dtype)
    got = flash_decode_paged_op(q, kp, vp, layer, pt, clen, interpret=True)
    want = flash_decode_paged_ref(
        q, kp, vp, layer, pt, validity_bias(4, _STACK["MP"] * _STACK["ps"],
                                            clen))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **_tol(dtype))
    others = jnp.arange(_STACK["L"])[:, None, None, None] != layer
    poisoned = flash_decode_paged_op(q, jnp.where(others, jnp.nan, kp),
                                     jnp.where(others, jnp.nan, vp), layer,
                                     pt, clen, interpret=True)
    for a, b in zip(poisoned, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_paged_step_writes_one_entry_a_row_in_its_layer(backend):
    """One paged attention step of layer 1 of a three-layer stacked pool:
    each row's new K and V land at (layer, its page, its offset) — the
    next page's first slot for a row ending on a page boundary, the trash
    page for the idle row — and nothing else in the stack changes; the
    output is the one a lone copy of that layer's pages gives."""
    from repro.kernels import dispatch as kdsp
    from repro.models.layers import (AttnSpec, attention_decode_paged,
                                     init_attention)
    L, P, ps, H, Hk, dh = (_STACK[k] for k in
                           ("L", "P", "ps", "H", "Hk", "dh"))
    _, kp, vp, pt, clen = _stacked_case(jnp.float32)
    spec = AttnSpec(n_heads=H, n_kv=Hk, head_dim=dh)
    params = init_attention(jax.random.key(3), 64, H, Hk, dh, jnp.float32)
    x = jnp.asarray(RNG.randn(4, 1, 64), jnp.float32)
    pool = {"k": kp, "v": vp}
    with kdsp.force_backend(backend):
        y, new = attention_decode_paged(params, x, spec, None, pool, 1, pt,
                                        clen)
        y1, _ = attention_decode_paged(
            params, x, spec, None, {n: t[1:2] for n, t in pool.items()}, 0,
            pt, clen)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), atol=1e-6)
    pages = np.asarray(pt)[np.arange(4), np.asarray(clen) // ps]
    assert pages.tolist() == [1, 6, 10, P - 1]
    for name in ("k", "v"):
        changed = np.argwhere(np.any(np.asarray(new[name])
                                     != np.asarray(pool[name]), axis=-1))
        assert sorted(map(tuple, changed.tolist())) == sorted(
            (1, int(p), int(n) % ps) for p, n in zip(pages, clen))


def test_flash_decode_paged_softcap_and_normalized():
    """Softcapped paged partials normalize to the dense op's output on the
    gathered layout — ONE validity definition shared by both paths."""
    from repro.kernels.flash_decode import (flash_decode_paged_op,
                                            gather_pages)
    B, P, ps, MP, H, dh = 2, 7, 16, 3, 4, 16
    q = jnp.asarray(RNG.randn(B, H, dh), jnp.float32)
    kp = jnp.asarray(RNG.randn(1, P, ps, H * dh), jnp.float32)
    vp = jnp.asarray(RNG.randn(1, P, ps, H * dh), jnp.float32)
    pt = jnp.asarray(RNG.randint(0, P, size=(B, MP)), jnp.int32)
    clen = jnp.asarray([17, 40])
    o, m, l = flash_decode_paged_op(q, kp, vp, 0, pt, clen, softcap=30.0,
                                    interpret=True)
    dense = (B, MP * ps, H, dh)
    od, md, ld = flash_decode_op(q, gather_pages(kp, 0, pt).reshape(dense),
                                 gather_pages(vp, 0, pt).reshape(dense),
                                 clen, softcap=30.0)
    np.testing.assert_allclose(np.asarray(o / l[..., None]),
                               np.asarray(od / ld[..., None]), atol=3e-5)


def test_paged_dispatch_backend_parity():
    """dispatch.decode_attention_paged: forced pallas (interpret) and
    forced reference agree on the same paged inputs."""
    from repro.kernels import dispatch as kdsp
    B, P, ps, MP, H, dh = 2, 6, 8, 3, 2, 16
    q = jnp.asarray(RNG.randn(B, 1, H, dh), jnp.float32)
    kp = jnp.asarray(RNG.randn(3, P, ps, H * dh), jnp.float32)
    vp = jnp.asarray(RNG.randn(3, P, ps, H * dh), jnp.float32)
    pt = jnp.asarray(RNG.randint(0, P, size=(B, MP)), jnp.int32)
    clen = jnp.asarray([5, 20])
    with kdsp.force_backend("pallas"):
        a = kdsp.decode_attention_paged(q, kp, vp, 2, pt, clen)
    with kdsp.force_backend("reference"):
        b = kdsp.decode_attention_paged(q, kp, vp, 2, pt, clen)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pick_s_block_cached_and_shared():
    """Satellite: the s_block divisor search is computed once per S (an
    lru_cache), and dense + paged ops share ONE validity definition."""
    from repro.kernels.flash_decode.ops import pick_s_block, validity_mask
    assert pick_s_block(512) == 512
    assert pick_s_block(48) == 16
    assert pick_s_block(7) == 7 or pick_s_block(7) == 1
    info = pick_s_block.cache_info()
    pick_s_block(48)
    assert pick_s_block.cache_info().hits > info.hits
    m = validity_mask(2, 8, jnp.asarray([3, 8]))
    np.testing.assert_array_equal(
        np.asarray(m),
        np.arange(8)[None, :] < np.asarray([3, 8])[:, None])
