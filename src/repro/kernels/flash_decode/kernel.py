"""Pallas-TPU flash-decode: one-token partial attention over a
sequence-sharded KV-cache shard, emitting (o·l, m, l) for the cross-shard
LSE merge (one tiny psum — ``repro.core.exchange.decode_attention_sharded``).

Tiling: grid (B, Hk, S/TS). One program serves all ``G = H/Hk`` query
heads of one KV head, so each K/V tile streams HBM→VMEM once per group.
The S axis is the *minor-most sequential* grid dim, so the (m, l, acc)
online-softmax state lives in VMEM scratch across S-blocks of the same
(b, kv-head). Validity/window masking arrives as each row's span of valid
local slots [lo, hi) (``ops.valid_span``, from cache_len, offset and
window), compared in the kernel against a key iota as a row (scores) and
as a column (V rows) — branch-free. Masked positions are *selected* away
in both K and V, never added to or multiplied by zero: cache slots past
the valid length (and trash pages) may hold anything, NaN included, and
0·NaN is NaN.

TPU block rule: the last two dims of every block are multiples of (8, 128)
or the full array dims. The wrapper views operands so that they are:

  q       [B, Hk, G, dh]        block (1, 1, G, dh)      full (G, dh)
  k, v    [B, S, Hk·dh]         block (1, TS, dh)        TS % 8, dh % 128
  span    [B, 1, 2] int32       block (1, 1, 2)          full (1, 2)
  o       [B, Hk, G, dh]        block (1, 1, G, dh)
  m, l    [B, Hk, G, 1]         block (1, 1, G, 1)

The span is a VMEM operand rather than a scalar prefetch: the serving
runtime vmaps decode over slots, and Pallas batches a vmapped scalar
prefetch as a loop of kernel launches.

so compiled kernels need ``dh % 128 == 0`` unless ``Hk == 1`` (the
dispatch layer routes other head dims to the reference and counts it).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, span_ref, o_ref, m_ref, l_ref,
            acc_ref, mm_ref, ll_ref, *, scale: float,
            softcap: Optional[float], n_s_blocks: int):
    si = pl.program_id(2)
    ts = k_ref.shape[1]

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mm_ref[...] = jnp.full_like(mm_ref, NEG_INF)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale             # [G, dh]
    k = k_ref[0].astype(jnp.float32)                        # [TS, dh]
    v = v_ref[0].astype(jnp.float32)
    lo = span_ref[0, :, 0:1] - si * ts                      # [1, 1], this
    hi = span_ref[0, :, 1:2] - si * ts                      # tile's slots
    key = jax.lax.broadcasted_iota(jnp.int32, (1, ts), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
    ok = (key >= lo) & (key < hi)                           # [1, TS]
    v = jnp.where((row >= lo) & (row < hi), v, 0.0)         # [TS, dh]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, TS]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(ok, s, NEG_INF)
    m_prev = mm_ref[...]                                    # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                  # [G, TS]
    ll_ref[...] = ll_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    mm_ref[...] = m_new

    @pl.when(si == n_s_blocks - 1)
    def _flush():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
        m_ref[0, 0] = mm_ref[...]
        l_ref[0, 0] = ll_ref[...]


def state_shapes(G: int, dh: int):
    """VMEM scratch of the online softmax: acc [G, dh], m [G, 1], l [G, 1]."""
    return [pltpu.VMEM((G, dh), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32)]


def out_shapes(B: int, Hk: int, G: int, dh: int):
    return (jax.ShapeDtypeStruct((B, Hk, G, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Hk, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hk, G, 1), jnp.float32))


def merge_heads(o, m, l, H: int):
    """[B, Hk, G, ·] kernel outputs → (o [B, H, dh], m [B, H], l [B, H])."""
    B = o.shape[0]
    return (o.reshape(B, H, o.shape[-1]), m.reshape(B, H), l.reshape(B, H))


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "s_block",
                                             "interpret"))
def flash_decode_pallas(q: jnp.ndarray,       # [B, H, dh]
                        k: jnp.ndarray,       # [B, S, Hk, dh]
                        v: jnp.ndarray,
                        span: jnp.ndarray,     # [B, 2] int32 [lo, hi)
                        *, scale: Optional[float] = None,
                        softcap: Optional[float] = None,
                        s_block: int = 512,
                        interpret: bool = False):
    B, H, dh = q.shape
    S, Hk = k.shape[1], k.shape[2]
    scale = (dh ** -0.5) if scale is None else scale
    G = H // Hk
    ts = min(s_block, S)
    assert S % ts == 0, (S, ts)
    n_s = S // ts

    o, m, l = pl.pallas_call(
        functools.partial(_kernel, scale=scale, softcap=softcap,
                          n_s_blocks=n_s),
        grid=(B, Hk, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, ts, dh), lambda b, h, s: (b, s, h)),
            pl.BlockSpec((1, ts, dh), lambda b, h, s: (b, s, h)),
            pl.BlockSpec((1, 1, 2), lambda b, h, s: (b, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, 1, G, dh), lambda b, h, s: (b, h, 0, 0)),
                   pl.BlockSpec((1, 1, G, 1), lambda b, h, s: (b, h, 0, 0)),
                   pl.BlockSpec((1, 1, G, 1), lambda b, h, s: (b, h, 0, 0))),
        out_shape=out_shapes(B, Hk, G, dh),
        scratch_shapes=state_shapes(G, dh),
        interpret=interpret,
    )(q.reshape(B, Hk, G, dh), k.reshape(B, S, Hk * dh),
      v.reshape(B, S, Hk * dh), span.reshape(B, 1, 2))
    return merge_heads(o, m, l, H)
