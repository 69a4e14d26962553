"""Paged latent-attention decode (MLA, absorbed form) through a page table.

The cache is a pool of latent pages, every layer's stacked: ``c_kv``
``[L, P, ps, r]`` and the shared rotary key ``k_pe`` ``[L, P, ps, dp]``
(its ``dr`` columns zero-padded to ``dp``, a lane multiple: a page copy
spans whole lanes); a request owns a row of a ``[B, max_pages]`` page
table, as for K/V pages, and the kernel reads the pages of the layer
index it is given.  Every query head scores against the one latent page,
in the absorbed form:

  s_h = (q_nope_h · W_uk_h) · c_kvᵀ + q_pe_h · k_peᵀ ,   o_h = p_h · c_kv

so the kernel takes ``q_lat`` ``[B, H, r]`` (``q_nope`` already through
``W_uk``) and ``q_pe`` ``[B, H, dr]`` and returns the latent output
``[B, H, r]``; ``W_uv`` and the output projection run after it.

The structure is the K/V kernel's (``paged.py``): grid (B, n_blocks), one
step per row and block of ``pages_per_block`` pages; ``c_kv`` and
``k_pe`` stay in HBM and each page is one copy of each into a
double-buffered VMEM block, the next block's copies (this row's next,
else the next row's first) started before this one waits; page table,
valid lengths and the layer index ride in as scalar prefetch; a row
walks only ``ceil(len / block tokens)`` blocks and copies only its pages
below ``ceil(len / ps)``.  Positions at or past a row's length are *selected*
away, never multiplied by zero: they may hold NaN.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode.kernel import NEG_INF
from repro.kernels.flash_decode.paged import gather_pages, pages_per_block


def flash_decode_latent_ref(q_lat: jnp.ndarray,      # [B, H, r]
                            q_pe: jnp.ndarray,       # [B, H, dr]
                            c_pool: jnp.ndarray,     # [L, P, ps, r]
                            pe_pool: jnp.ndarray,    # [L, P, ps, dp >= dr]
                            layer,                   # int32 scalar
                            page_table: jnp.ndarray,  # [B, MP] int32
                            lengths: jnp.ndarray,    # [B] int32
                            *, scale: float) -> jnp.ndarray:
    """``jnp.take`` gather + masked softmax in float32 → o [B, H, r]."""
    dr = q_pe.shape[-1]
    c = gather_pages(c_pool, layer, page_table).astype(
        jnp.float32)                                          # [B, S, r]
    pe = gather_pages(pe_pool, layer, page_table)[..., :dr].astype(
        jnp.float32)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32), c)
         + jnp.einsum("bhd,bsd->bhs", q_pe.astype(jnp.float32), pe)) * scale
    valid = jnp.arange(c.shape[1])[None, :] < lengths[:, None]    # [B, S]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, jnp.where(valid[..., None], c, 0.0))


def _latent_kernel(len_ref, pt_ref, layer_ref, q_ref, qpe_ref, c_hbm, pe_hbm,
                   o_ref, m_ref, l_ref, c_buf, pe_buf, sems, slot_ref, *,
                   scale: float, max_pages: int):
    b, j = pl.program_id(0), pl.program_id(1)
    n_rows = pl.num_programs(0)
    _, ppb, ps, r = c_buf.shape
    dp = pe_buf.shape[-1]
    bk = ppb * ps

    def n_walk(row):                     # blocks a row walks (at least one)
        return jnp.maximum((len_ref[row] + bk - 1) // bk, 1)

    def copies(row, blk, slot, method):
        """Start or wait the copies of block ``blk`` of ``row`` into buffer
        ``slot``: its pages below ceil(len / ps), c_kv and k_pe."""
        first = blk * ppb
        n = jnp.minimum((len_ref[row] + ps - 1) // ps - first, ppb)
        layer = layer_ref[0]

        def one(p, carry):
            page = pt_ref[row * max_pages + first + p]
            for i, (src, dst) in enumerate(((c_hbm, c_buf),
                                            (pe_hbm, pe_buf))):
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
        c = c_buf[slot].reshape(bk, r)                       # [bk, r]
        pe = pe_buf[slot].reshape(bk, dp)
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q_ref[0], c, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qpe_ref[0], pe, dims,
                                   preferred_element_type=jnp.float32))
        s = jnp.where(key < length, s * scale, NEG_INF)      # [H, bk]
        cv = jnp.where(row < length, c.astype(jnp.float32), 0.0)
        m_prev = m_ref[0]                                    # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_ref[0] = o_ref[0] * alpha + jnp.dot(
            p, cv, preferred_element_type=jnp.float32)
        m_ref[0] = m_new


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_decode_latent_pallas(q_lat: jnp.ndarray,      # [B, H, r]
                               q_pe: jnp.ndarray,       # [B, H, dr]
                               c_pool: jnp.ndarray,     # [L, P, ps, r]
                               pe_pool: jnp.ndarray,    # [L, P, ps, dp]
                               layer,                   # int32 scalar
                               page_table: jnp.ndarray,  # [B, MP] int32
                               lengths: jnp.ndarray,    # [B] int32
                               *, scale: float, interpret: bool = False
                               ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
    """Pallas paged latent decode → (o·l [B, H, r], m [B, H], l [B, H])
    partials, in float32.  Row ``b`` attends to its positions below
    ``lengths[b]``; its logical page ``p`` is page ``page_table[b, p]``
    of layer ``layer`` of the stacked pool.  ``q_lat`` and ``q_pe``
    (zero-padded to the pool's ``k_pe`` width) are read in the pool's
    dtype; the scores accumulate in float32."""
    B, H, r = q_lat.shape
    dp = pe_pool.shape[-1]
    ps = c_pool.shape[2]
    MP = page_table.shape[1]
    # a page's c_kv and padded k_pe rows count together against a block
    ppb = pages_per_block(ps, r + dp, MP, c_pool.dtype.itemsize)
    n_blocks = -(-MP // ppb)
    # a length past the table walks the whole table: no walk outruns the
    # grid, so every copy a step starts is waited for
    lengths = jnp.minimum(lengths, MP * ps).astype(jnp.int32)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_blocks),
        in_specs=[pl.BlockSpec((1, H, r), lambda b, j, *_: (b, 0, 0)),
                  pl.BlockSpec((1, H, dp), lambda b, j, *_: (b, 0, 0)),
                  hbm, hbm],
        out_specs=(pl.BlockSpec((1, H, r), lambda b, j, *_: (b, 0, 0)),
                   pl.BlockSpec((1, H, 1), lambda b, j, *_: (b, 0, 0)),
                   pl.BlockSpec((1, H, 1), lambda b, j, *_: (b, 0, 0))),
        scratch_shapes=[pltpu.VMEM((2, ppb, ps, r), c_pool.dtype),
                        pltpu.VMEM((2, ppb, ps, dp), pe_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    o, m, l = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, max_pages=MP),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, H, r), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32)),
        # sequential: each step starts the copies of the next row's block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths, page_table.astype(jnp.int32).reshape(-1),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q_lat.astype(c_pool.dtype),
      jnp.pad(q_pe, ((0, 0), (0, 0), (0, dp - q_pe.shape[-1]))).astype(
          pe_pool.dtype), c_pool, pe_pool)
    return o, m[..., 0], l[..., 0]
