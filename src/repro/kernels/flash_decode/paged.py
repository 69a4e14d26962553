"""Paged flash-decode: one-token attention gathered through a page table.

The KV cache lives in a shared pool of fixed-size pages, every layer's
stacked: ``[n_layers, n_pages, page_size, Hk·dh]``, a page one contiguous
``[ps, Hk·dh]`` row block, the layout the kernel copies.  Each request
owns a row of a ``[B, max_pages]`` int32 page table mapping its logical
block ``p`` to a physical page id.  Every entry point takes the stacked
pool and a layer index, and reads that layer's pages where they lie: no
caller slices a layer out.  The reference path materializes the gather
with ``jnp.take``; the Pallas path never materializes it.

Pallas path: grid (B, n_blocks), one step per row and block of
``pages_per_block`` pages, every KV head at once.  K/V stay in HBM; the
page table, each row's valid length and the layer index ride in as
scalar prefetch, and page ``p`` of the layer is the copy of
``pool[layer, p]``.  Each step copies its block's pages into one half of
a double-buffered VMEM block, having started the next block's copies
(this row's next, else the next row's first) into the other half.  A row
walks ``ceil(len / block tokens)`` blocks and copies only its pages below
``ceil(len / ps)``: the blocks past its length issue no copy and skip
their compute, so an idle row that points at the trash page costs one
block.

Validity arrives as each row's valid length from ``ops.valid_span`` — the
ONE definition of cache validity, shared with the dense op.  Positions at
or past it (stale VMEM, trash pages, pages past the span) are *selected*
away in both K and V, never multiplied by zero: they may hold NaN.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode.kernel import (NEG_INF, merge_heads,
                                               out_shapes)
from repro.kernels.flash_decode.ref import flash_decode_ref


def gather_pages(pool: jnp.ndarray,         # [L, P, ps, W]
                 layer,                     # int32 scalar
                 page_table: jnp.ndarray    # [B, MP] int32
                 ) -> jnp.ndarray:          # [B, MP*ps, W]
    """Materialize a per-request contiguous view of layer ``layer``'s
    pages from the stacked page pool."""
    B, MP = page_table.shape
    ps = pool.shape[2]
    return jnp.take(pool[layer], page_table, axis=0).reshape(
        B, MP * ps, *pool.shape[3:])


def flash_decode_paged_ref(q: jnp.ndarray,           # [B, H, dh]
                           k_pool: jnp.ndarray,      # [L, P, ps, Hk·dh]
                           v_pool: jnp.ndarray,
                           layer,                    # int32 scalar
                           page_table: jnp.ndarray,  # [B, MP] int32
                           kv_bias: jnp.ndarray,     # [B, MP*ps] f32
                           *, scale: Optional[float] = None,
                           softcap: Optional[float] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``jnp.take`` gather + the dense reference math → (o·l, m, l)."""
    B, S, dh = page_table.shape[0], kv_bias.shape[1], q.shape[-1]
    k = gather_pages(k_pool, layer, page_table).reshape(B, S, -1, dh)
    v = gather_pages(v_pool, layer, page_table).reshape(B, S, -1, dh)
    return flash_decode_ref(q, k, v, kv_bias, scale=scale, softcap=softcap)


# one K (or V) block buffer in VMEM; four of them are live (K, V x 2).
# On a v5e at internlm2-1.8b widths, blocks of 512 tokens walked 8 rows
# of 300-1,800 tokens in 0.073 ms a call, 256 in 0.11 ms, 1,024 in
# 0.063 ms but one row of 1,100 (a suffix scan) slower than 512.
KV_BLOCK_BYTES = 1 << 20
MAX_BLOCK_TOKENS = 512


def pages_per_block(page_size: int, row_width: int, max_pages: int,
                    itemsize: int) -> int:
    """Pages the paged kernel walks per grid step: as many as one
    ``KV_BLOCK_BYTES`` buffer of [ps, row_width] pages holds, at most
    ``MAX_BLOCK_TOKENS`` tokens and the table's ``max_pages``."""
    per_page = page_size * row_width * itemsize
    return max(1, min(max_pages, KV_BLOCK_BYTES // per_page,
                      MAX_BLOCK_TOKENS // page_size))


def _paged_kernel(len_ref, pt_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                  m_ref, l_ref, k_buf, v_buf, sems, slot_ref, *, scale: float,
                  softcap: Optional[float], max_pages: int):
    b, j = pl.program_id(0), pl.program_id(1)
    n_rows = pl.num_programs(0)
    _, ppb, ps, _ = k_buf.shape
    _, Hk, G, dh = q_ref.shape
    bk = ppb * ps

    def n_walk(r):                       # blocks row r walks (at least one)
        return jnp.maximum((len_ref[r] + bk - 1) // bk, 1)

    def copies(r, blk, slot, method):
        """Start or wait the copies of block ``blk`` of row ``r`` into
        buffer ``slot``: its pages below ceil(len / ps)."""
        first = blk * ppb
        n = jnp.minimum((len_ref[r] + ps - 1) // ps - first, ppb)
        layer = layer_ref[0]

        def one(p, carry):
            page = pt_ref[r * max_pages + first + p]
            for i, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                getattr(pltpu.make_async_copy(src.at[layer, page],
                                              dst.at[slot, p],
                                              sems.at[i, slot]), method)()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((b == 0) & (j == 0))
    def _first():
        slot_ref[0] = 0
        copies(0, 0, 0, "start")

    @pl.when(j < n_walk(b))
    def _walk():
        slot = slot_ref[0]
        last = j + 1 == n_walk(b)

        @pl.when(jnp.logical_not(last))
        def _next_block():
            copies(b, j + 1, 1 - slot, "start")

        @pl.when(last & (b + 1 < n_rows))
        def _next_row():
            copies(b + 1, 0, 1 - slot, "start")

        copies(b, j, slot, "wait")
        slot_ref[0] = 1 - slot
        length = len_ref[b] - j * bk
        key = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        q = q_ref[0].astype(jnp.float32) * scale                 # [Hk, G, dh]
        for h in range(Hk):
            lanes = pl.ds(h * dh, dh)
            k = k_buf[slot, :, :, lanes].astype(jnp.float32).reshape(bk, dh)
            v = v_buf[slot, :, :, lanes].astype(jnp.float32).reshape(bk, dh)
            v = jnp.where(row < length, v, 0.0)
            s = jax.lax.dot_general(q[h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(key < length, s, NEG_INF)              # [G, bk]
            m_prev = m_ref[0, h]                                 # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[0, h] = l_ref[0, h] * alpha + jnp.sum(p, axis=-1,
                                                        keepdims=True)
            o_ref[0, h] = o_ref[0, h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[0, h] = m_new


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def flash_decode_paged_pallas(q: jnp.ndarray,           # [B, H, dh]
                              k_pool: jnp.ndarray,      # [L, P, ps, Hk·dh]
                              v_pool: jnp.ndarray,
                              layer,                    # int32 scalar
                              page_table: jnp.ndarray,  # [B, MP] int32
                              lengths: jnp.ndarray,     # [B] int32
                              *, scale: Optional[float] = None,
                              softcap: Optional[float] = None,
                              interpret: bool = False):
    """Pallas paged flash-decode → (o·l, m, l) partials.

    Row ``b`` attends to its positions below ``lengths[b]``; logical page
    ``p`` of it is physical page ``page_table[b, p]`` of layer ``layer``
    of the stacked pool.
    """
    B, H, dh = q.shape
    ps, W = k_pool.shape[2], k_pool.shape[3]
    MP = page_table.shape[1]
    scale = (dh ** -0.5) if scale is None else scale
    Hk = W // dh
    G = H // Hk
    ppb = pages_per_block(ps, W, MP, k_pool.dtype.itemsize)
    n_blocks = -(-MP // ppb)
    # a length past the table walks the whole table: no walk outruns the
    # grid, so every copy a step starts is waited for
    lengths = jnp.minimum(lengths, MP * ps).astype(jnp.int32)
    row = pl.BlockSpec((1, Hk, G, dh), lambda b, j, *_: (b, 0, 0, 0))
    stat = pl.BlockSpec((1, Hk, G, 1), lambda b, j, *_: (b, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_blocks),
        in_specs=[row, hbm, hbm],
        out_specs=(row, stat, stat),
        scratch_shapes=[pltpu.VMEM((2, ppb, ps, W), k_pool.dtype),
                        pltpu.VMEM((2, ppb, ps, W), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    o, m, l = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, softcap=softcap,
                          max_pages=MP),
        grid_spec=grid_spec,
        out_shape=out_shapes(B, Hk, G, dh),
        # sequential: each step starts the copies of the next row's block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths, page_table.astype(jnp.int32).reshape(-1),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q.reshape(B, Hk, G, dh), k_pool, v_pool)
    return merge_heads(o, m, l, H)


def flash_decode_paged_op(q: jnp.ndarray,           # [B, 1, H, dh] / [B,H,dh]
                          k_pool: jnp.ndarray,      # [L, P, ps, Hk·dh]
                          v_pool: jnp.ndarray,
                          layer,                    # int32 scalar
                          page_table: jnp.ndarray,  # [B, MP] int32
                          cache_len,                # [B] valid prefix length
                          *, scale: Optional[float] = None,
                          softcap: Optional[float] = None,
                          interpret: Optional[bool] = None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Valid lengths + Pallas paged kernel → (o·l, m, l) partials."""
    from repro.kernels.flash_decode.ops import _on_cpu, valid_span
    interpret = _on_cpu() if interpret is None else interpret
    if q.ndim == 4:
        q = q[:, 0]
    lengths = valid_span(q.shape[0], cache_len)[:, 1]
    return flash_decode_paged_pallas(q, k_pool, v_pool, layer, page_table,
                                     lengths, scale=scale, softcap=softcap,
                                     interpret=interpret)
