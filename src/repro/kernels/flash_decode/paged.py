"""Paged flash-decode: one-token attention gathered through a page table.

The KV cache lives in a shared pool of fixed-size pages
(``[n_pages, page_size, Hk, dh]``); each request owns a row of a
``[B, max_pages]`` int32 page table mapping its logical block ``p`` to a
physical page id.  The reference path materializes the gather with
``jnp.take``; the Pallas path never materializes it — the page table rides
in as a scalar-prefetch operand and the K/V block index maps read
``pt[b, p]`` directly, so each (b, kv-head, p) grid step streams exactly
one physical page HBM→VMEM.  Grid (B, Hk, max_pages) with the page axis
minor-most sequential, so the online-softmax state in VMEM scratch is the
*same* ``_kernel`` body the dense flash-decode uses.

Validity arrives as each row's valid span from ``ops.valid_span`` — the
ONE definition of cache validity, shared with the dense op.  Free/
overhanging table entries may point at a trash page; positions outside the
span are selected away, so their values never count.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode.kernel import (_kernel, merge_heads,
                                               out_shapes, state_shapes)
from repro.kernels.flash_decode.ref import flash_decode_ref


def gather_pages(pool: jnp.ndarray,         # [P, ps, Hk, dh]
                 page_table: jnp.ndarray    # [B, MP] int32
                 ) -> jnp.ndarray:          # [B, MP*ps, Hk, dh]
    """Materialize a per-request contiguous KV view from the page pool."""
    B, MP = page_table.shape
    ps = pool.shape[1]
    return jnp.take(pool, page_table, axis=0).reshape(
        B, MP * ps, *pool.shape[2:])


def flash_decode_paged_ref(q: jnp.ndarray,           # [B, H, dh]
                           k_pool: jnp.ndarray,      # [P, ps, Hk, dh]
                           v_pool: jnp.ndarray,
                           page_table: jnp.ndarray,  # [B, MP] int32
                           kv_bias: jnp.ndarray,     # [B, MP*ps] f32
                           *, scale: Optional[float] = None,
                           softcap: Optional[float] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``jnp.take`` gather + the dense reference math → (o·l, m, l)."""
    k = gather_pages(k_pool, page_table)
    v = gather_pages(v_pool, page_table)
    return flash_decode_ref(q, k, v, kv_bias, scale=scale, softcap=softcap)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def flash_decode_paged_pallas(q: jnp.ndarray,           # [B, H, dh]
                              k_pool: jnp.ndarray,      # [P, ps, Hk, dh]
                              v_pool: jnp.ndarray,
                              page_table: jnp.ndarray,  # [B, MP] int32
                              span: jnp.ndarray,        # [B, 2] int32
                              *, scale: Optional[float] = None,
                              softcap: Optional[float] = None,
                              interpret: bool = False):
    """Pallas paged flash-decode → (o·l, m, l) partials.

    The page table is the first operand (scalar prefetch), available to the
    K/V BlockSpec index maps: logical block ``p`` of row ``b`` resolves to
    physical page ``pt[b, p]`` of the pool viewed as [P, ps, Hk·dh], block
    (1, ps, dh) — ps is the full page dim, so any page size tiles.
    """
    B, H, dh = q.shape
    P, ps, Hk = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    MP = page_table.shape[1]
    scale = (dh ** -0.5) if scale is None else scale
    G = H // Hk

    def _paged_kernel(pt_ref, q_ref, k_ref, v_ref, span_ref,
                      o_ref, m_ref, l_ref, acc_ref, mm_ref, ll_ref):
        del pt_ref  # consumed by the index maps
        _kernel(q_ref, k_ref, v_ref, span_ref, o_ref, m_ref, l_ref,
                acc_ref, mm_ref, ll_ref, scale=scale, softcap=softcap,
                n_s_blocks=MP)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hk, MP),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), lambda b, h, p, pt: (b, h, 0, 0)),
            pl.BlockSpec((1, ps, dh), lambda b, h, p, pt: (pt[b, p], 0, h)),
            pl.BlockSpec((1, ps, dh), lambda b, h, p, pt: (pt[b, p], 0, h)),
            pl.BlockSpec((1, 1, 2), lambda b, h, p, pt: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, G, dh), lambda b, h, p, pt: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, p, pt: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, p, pt: (b, h, 0, 0))),
        scratch_shapes=state_shapes(G, dh),
    )
    o, m, l = pl.pallas_call(
        _paged_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes(B, Hk, G, dh),
        interpret=interpret,
    )(page_table.astype(jnp.int32), q.reshape(B, Hk, G, dh),
      k_pool.reshape(P, ps, Hk * dh), v_pool.reshape(P, ps, Hk * dh),
      span.reshape(B, 1, 2))
    return merge_heads(o, m, l, H)


def flash_decode_paged_op(q: jnp.ndarray,           # [B, 1, H, dh] / [B,H,dh]
                          k_pool: jnp.ndarray,      # [P, ps, Hk, dh]
                          v_pool: jnp.ndarray,
                          page_table: jnp.ndarray,  # [B, MP] int32
                          cache_len,                # [B] valid prefix length
                          *, scale: Optional[float] = None,
                          softcap: Optional[float] = None,
                          interpret: Optional[bool] = None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Valid spans + Pallas paged kernel → (o·l, m, l) partials."""
    from repro.kernels.flash_decode.ops import _on_cpu, valid_span
    interpret = _on_cpu() if interpret is None else interpret
    if q.ndim == 4:
        q = q[:, 0]
    span = valid_span(q.shape[0], cache_len)
    return flash_decode_paged_pallas(q, k_pool, v_pool, page_table, span,
                                     scale=scale, softcap=softcap,
                                     interpret=interpret)
