"""Position-wise partitioning (master–worker view) and single-host oracles.

The paper's terminal device splits ``X ∈ R^{N×D}`` into ``P`` equal parts
along the sequence dimension.  These helpers provide (a) the partitioning /
reassembly math and (b) a *single-host simulation* of the P-device
computation — the oracle the distributed (shard_map) implementation and the
Pallas kernels are validated against (off the TPU, where the dispatch layer
runs the reference), and the engine the edge latency simulator drives.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp

from repro.core.prism_attention import reference_attention
from repro.kernels import dispatch as kdsp


def partition_sequence(x: jnp.ndarray, P: int, axis: int = 1) -> jnp.ndarray:
    """Split [..., N, ...] into [P, ..., N/P, ...] along ``axis``."""
    axis = axis % x.ndim
    N = x.shape[axis]
    if N % P != 0:
        raise ValueError(f"sequence length {N} not divisible by P={P}")
    parts = jnp.split(x, P, axis=axis)
    return jnp.stack(parts, axis=0)


def unpartition_sequence(parts: jnp.ndarray, axis: int = 1) -> jnp.ndarray:
    """Inverse of :func:`partition_sequence`: [P, ..., N/P, ...] → [..., N, ...]."""
    P = parts.shape[0]
    return jnp.concatenate([parts[p] for p in range(P)], axis=axis)


def simulate_prism_attention(
    q: jnp.ndarray,   # [B, N, H, dh]  full-sequence projected queries
    k: jnp.ndarray,   # [B, N, Hk, dh] full-sequence projected keys
    v: jnp.ndarray,   # [B, N, Hk, dh]
    P: int,
    L: int,
    *,
    causal: bool = False,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    kv_mask: Optional[jnp.ndarray] = None,   # [B, N] bool; False → padding
) -> jnp.ndarray:
    """Single-host oracle of the P-device PRISM attention.

    Computes what every device p would produce (local full K/V + remote
    segment means, scaling-aware softmax) and concatenates the outputs back
    into the full sequence.  Matches the shard_map implementation exactly,
    padding included: with ``kv_mask`` the means are mask-aware (counts in
    the scaling bias) and padded local keys are masked out.  Segment means
    and attention go through the kernel-dispatch layer, as in the shard_map
    path: the reference off the TPU, the Pallas kernels (and their counted
    fallbacks) on it.
    """
    B, N, H, dh = q.shape
    Np = N // P
    seg = Np // L
    qp = partition_sequence(q, P)     # [P, B, Np, H, dh]
    kp = partition_sequence(k, P)
    vp = partition_sequence(v, P)

    def parts_as_batch(t):            # [P, B, ...] ↔ [P·B, ...]
        return t.reshape(P * B, *t.shape[2:])

    # means of *projected* K/V (linearity; no re-projection)
    if kv_mask is None:
        mp = cnt_all = None
        km = kdsp.segment_means(parts_as_batch(kp), L, axis=1)
        vm = kdsp.segment_means(parts_as_batch(vp), L, axis=1)
    else:
        mp = partition_sequence(kv_mask, P)                  # [P, B, Np]
        km, cnt = kdsp.segment_means_masked(parts_as_batch(kp), L,
                                            parts_as_batch(mp), axis=1)
        vm, _ = kdsp.segment_means_masked(parts_as_batch(vp), L,
                                          parts_as_batch(mp), axis=1)
        cnt_all = cnt.reshape(P, B, L).transpose(1, 0, 2)    # [B, P, L]
    km_all = km.reshape(P, B, *km.shape[1:]).transpose(1, 0, 2, 3, 4)
    vm_all = vm.reshape(P, B, *vm.shape[1:]).transpose(1, 0, 2, 3, 4)
    # km_all, vm_all: [B, P, L, Hk, dh]

    outs = []
    for p in range(P):
        outs.append(
            kdsp.prism_attention(
                qp[p], kp[p], vp[p], km_all, vm_all, p, seg,
                causal=causal, logit_softcap=logit_softcap, scale=scale,
                kv_mask=None if mp is None else mp[p], mean_counts=cnt_all,
            )
        )
    return jnp.concatenate(outs, axis=1)


def simulate_voltage_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, P: int, *,
    causal: bool = False, logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-host oracle of Voltage (full-tensor exchange).

    Voltage's AllGather reconstructs the complete K/V on every device, so the
    math is *exactly* full attention — partitioning only changes where the
    FLOPs run. We still walk the partitions to mirror the distributed code.
    """
    B, N, H, dh = q.shape
    Np = N // P
    qp = partition_sequence(q, P)
    outs = []
    for p in range(P):
        outs.append(
            reference_attention(
                qp[p], k, v, causal=causal, q_offset=p * Np,
                logit_softcap=logit_softcap, scale=scale,
            )
        )
    return jnp.concatenate(outs, axis=1)
