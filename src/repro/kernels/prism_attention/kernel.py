"""Pallas-TPU PRISM attention: flash-style softmax over [local K/V ‖
segment-mean K/V with additive log-count bias].

TPU adaptation of the paper's scaling-aware softmax (DESIGN.md §2): the
GPU prototype materializes the concatenated score matrix; here the two key
groups are processed as separate MXU tiles with one running (m, l, acc)
online-softmax state, so the augmented representation never exists in HBM
— the means ride along as one extra K-block.

Tiling: grid (B, H, Nq/TQ). Per program:
  q tile      [TQ, dh]           VMEM
  local K/V   [Nk, dh]           VMEM (per-partition Nk = N/P is small by
                                 construction — PRISM's partitioning is what
                                 makes full-KV residency viable; a streamed
                                 variant would kick in above ~8k tokens)
  mean K/V    [M, dh] + bias [1, M] VMEM (M = P·L)
MXU work: [TQ, dh]·[dh, Nk] and [TQ, dh]·[dh, M].

TPU block rule (last two block dims multiples of (8, 128) or full): the
wrapper moves heads ahead of tokens — q/out [B, H, Nq, dh], K/V
[B, Hk, Nk|M, dh] — and views the bias as [B, 1, M], so blocks are
(TQ, dh), (Nk|M, dh) and (1, M) with dh whole: any head dim tiles (ViT's
64 as well as 128), and TQ is a multiple of 8 or all of Nq.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, km_ref, vm_ref, bias_ref, o_ref, *,
            scale: float, causal: bool, q_block: int,
            softcap: Optional[float]):
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale             # [TQ, dh]
    k = k_ref[...].astype(jnp.float32)                     # [Nk, dh]
    v = v_ref[...].astype(jnp.float32)
    km = km_ref[...].astype(jnp.float32)                   # [M, dh]
    vm = vm_ref[...].astype(jnp.float32)
    bias = bias_ref[...].astype(jnp.float32)               # [1, M]

    def cap(x):
        return x if softcap is None else softcap * jnp.tanh(x / softcap)

    def qkt(a, b):                                         # a · bᵀ
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    s_loc = cap(qkt(q, k))                                 # [TQ, Nk]
    if causal:
        qpos = qi * q_block + jax.lax.broadcasted_iota(
            jnp.int32, s_loc.shape, 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, s_loc.shape, 1)
        s_loc = jnp.where(qpos >= kpos, s_loc, NEG_INF)

    s_mean = cap(qkt(q, km)) + bias                        # [TQ, M]

    # one online-softmax state across both key groups
    m1 = jnp.max(s_loc, axis=-1, keepdims=True)
    m2 = jnp.max(s_mean, axis=-1, keepdims=True)
    m = jnp.maximum(jnp.maximum(m1, m2), -1e29)            # [TQ, 1]
    p_loc = jnp.exp(s_loc - m)
    p_mean = jnp.exp(s_mean - m)
    l = (jnp.sum(p_loc, axis=-1, keepdims=True)
         + jnp.sum(p_mean, axis=-1, keepdims=True))
    acc = (jnp.dot(p_loc, v, preferred_element_type=jnp.float32)
           + jnp.dot(p_mean, vm, preferred_element_type=jnp.float32))
    o_ref[...] = (acc / l).astype(o_ref.dtype)             # [TQ, dh]


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "softcap", "q_block",
                              "interpret"))
def prism_attention_pallas(
    q: jnp.ndarray,        # [B, Nq, H, dh]
    k_loc: jnp.ndarray,    # [B, Nk, Hk, dh]
    v_loc: jnp.ndarray,
    k_means: jnp.ndarray,  # [B, M, Hk, dh]
    v_means: jnp.ndarray,
    mean_bias: jnp.ndarray,   # [B, M] f32
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    q_block: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Nq, H, dh = q.shape
    Hk = k_loc.shape[2]
    Nk, M = k_loc.shape[1], k_means.shape[1]
    scale = (dh ** -0.5) if scale is None else scale
    tq = min(q_block, Nq)
    assert Nq % tq == 0, (Nq, tq)
    G = H // Hk

    def kv_spec(n):
        return pl.BlockSpec((None, None, n, dh),
                            lambda b, h, i: (b, h // G, 0, 0))

    def heads_first(t):                     # [B, N, h, dh] ↔ [B, h, N, dh]
        return t.transpose(0, 2, 1, 3)

    q_spec = pl.BlockSpec((None, None, tq, dh), lambda b, h, i: (b, h, i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, q_block=tq,
                          softcap=softcap),
        grid=(B, H, Nq // tq),
        in_specs=[q_spec, kv_spec(Nk), kv_spec(Nk), kv_spec(M), kv_spec(M),
                  pl.BlockSpec((None, 1, M), lambda b, h, i: (b, 0, 0))],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Nq, dh), q.dtype),
        interpret=interpret,
    )(heads_first(q), heads_first(k_loc), heads_first(v_loc),
      heads_first(k_means), heads_first(v_means), mean_bias.reshape(B, 1, M))
    return heads_first(out)
