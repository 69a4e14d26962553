"""jit'd wrapper for the PRISM attention kernel.

Builds the mean-bias vector from (part_idx, counts, visibility) — the same
semantics as ``repro.core.prism_attention.prism_attention`` — pads Nq to the
q-block, and interprets on CPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.prism_attention.kernel import (NEG_INF,
                                                  prism_attention_pallas)


#: The TPU compiler's default scoped-VMEM limit for one kernel on v5e.
VMEM_LIMIT = 16 * 2**20


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def pick_q_block(Nq: int) -> int:
    """Largest power-of-two q tile (8..128) dividing ``Nq``, else ``Nq``
    whole: a TPU block's sublane dim is a multiple of 8 or the full dim."""
    return next((t for t in (128, 64, 32, 16, 8) if Nq % t == 0), Nq)


def fits_vmem(Nq: int, Nk: int, M: int, dh: int, itemsize: int) -> bool:
    """Whether one program's VMEM stays under :data:`VMEM_LIMIT`: the
    double-buffered q/out tiles and the whole local K/V and mean K/V, plus
    one f32 score tile [TQ, Nk + M].  A little above the compiler's own
    count: for a v5e at TQ=128, dh=128, bf16, Nk=12288 it reported
    18.00 MiB where this gives 18.15 MiB."""
    tq = pick_q_block(Nq)
    operands = 2 * itemsize * dh * (2 * tq + 2 * (Nk + M))
    return operands + 4 * tq * (Nk + M) <= VMEM_LIMIT


def build_mean_bias(B: int, P: int, L: int, part_idx, seg_size: int,
                    *, causal: bool,
                    mean_counts: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """[B, P·L] additive bias: log(count) for visible means, -inf else."""
    part_of_mean = jnp.arange(P * L) // L                  # [P*L]
    if causal:
        visible = part_of_mean < part_idx
    else:
        visible = part_of_mean != part_idx
    if mean_counts is None:
        counts = jnp.full((B, P * L), float(seg_size), jnp.float32)
    else:
        counts = mean_counts.reshape(B, P * L).astype(jnp.float32)
        visible = visible[None, :] & (counts > 0)
    bias = jnp.log(jnp.maximum(counts, 1.0))
    vis = visible if visible.ndim == 2 else visible[None, :]
    return jnp.where(vis, bias, NEG_INF)


def prism_attention_op(
    q: jnp.ndarray,            # [B, Nq, H, dh]
    k_loc: jnp.ndarray,
    v_loc: jnp.ndarray,
    k_means: jnp.ndarray,      # [B, P, L, Hk, dh]
    v_means: jnp.ndarray,
    part_idx,
    seg_size: int,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    mean_counts: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    interpret = _on_cpu() if interpret is None else interpret
    B, Nq, H, dh = q.shape
    P, L = k_means.shape[1], k_means.shape[2]
    km = k_means.reshape(B, P * L, *k_means.shape[3:])
    vm = v_means.reshape(B, P * L, *v_means.shape[3:])
    bias = build_mean_bias(B, P, L, part_idx, seg_size, causal=causal,
                           mean_counts=mean_counts)
    return prism_attention_pallas(
        q, k_loc, v_loc, km, vm, bias, causal=causal, scale=scale,
        softcap=softcap, q_block=pick_q_block(Nq), interpret=interpret)
