"""Kernel-dispatch layer: route hot ops to the Pallas kernels or the jnp
reference, per backend.

The compression and decode hot paths (``repro.core.exchange``,
``repro.models.layers``) call these wrappers instead of binding either
implementation directly.  Resolution order, first match wins:

1. ``set_backend("pallas" | "reference" | "auto")`` — process-global
   override (returns the previous value; also usable as a context manager
   via ``force_backend``).
2. ``REPRO_KERNEL_BACKEND`` environment variable (same values).
3. ``"auto"`` — Pallas on TPU, reference elsewhere.  On CPU the kernels
   only run under ``interpret=True`` (correct but slow), so auto never
   selects them there; parity tests opt in explicitly.

Shapes/arguments a kernel does not take (non-token segment axes, masked
local keys in PRISM attention, head dims the compiled decode kernels cannot
tile, PRISM partitions too long to hold in VMEM) run the reference instead, and every such fallback increments the
counter ``kernels.fallback{op=...,reason=...}`` in :data:`METRICS`.  The
routing runs while a function is traced, so the counter counts traces that
fell back, not executions.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import prism_attention as ref_attn
from repro.core import segment_means as ref_sm
from repro.obs import MetricsRegistry

_VALID = ("auto", "pallas", "reference")
_OVERRIDE: Optional[str] = None
ENV_VAR = "REPRO_KERNEL_BACKEND"

#: Process-wide registry of the kernel-routing counters.
METRICS = MetricsRegistry()


def set_backend(name: Optional[str]) -> Optional[str]:
    """Set the process-global backend override; returns the previous one.
    ``None`` clears the override (environment / auto resolution applies)."""
    global _OVERRIDE
    if name is not None and name not in _VALID:
        raise ValueError(f"unknown kernel backend {name!r}; one of {_VALID}")
    prev, _OVERRIDE = _OVERRIDE, name
    return prev


@contextlib.contextmanager
def force_backend(name: str):
    """Temporarily force a backend (parity tests, benchmarks)."""
    prev = set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def resolve_backend() -> str:
    """The backend that would execute right now: "pallas" or "reference"."""
    choice = _OVERRIDE or os.environ.get(ENV_VAR, "auto")
    if choice not in _VALID:
        raise ValueError(f"{ENV_VAR}={choice!r} invalid; one of {_VALID}")
    if choice == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    return choice


def _use_pallas() -> bool:
    return resolve_backend() == "pallas"


def _interpret() -> bool:
    """Pallas kernels interpret everywhere but real TPU backends."""
    return jax.default_backend() != "tpu"


def _fallback(op: str, reason: str) -> None:
    """Record that ``op`` was routed to the Pallas backend but ran the
    reference because of ``reason``."""
    METRICS.counter("kernels.fallback", {"op": op, "reason": reason}).inc()


def fallback_counts() -> dict:
    """``{"op/reason": count}`` of every reference fallback so far."""
    return {f"{dict(m.labels)['op']}/{dict(m.labels)['reason']}":
            int(m.value) for m in METRICS.find("kernels.fallback")}


def _decode_tiles(k: jnp.ndarray) -> bool:
    """Whether the compiled decode kernels can tile a [.., Hk, dh] cache:
    each program reads one KV head's (tokens, dh) slab of the
    [.., tokens, Hk·dh] view, so dh must be a lane multiple (128) unless
    the slab is the whole row (Hk == 1).  Interpret mode takes any dh."""
    Hk, dh = k.shape[-2:]
    return _interpret() or dh % 128 == 0 or Hk == 1


# ---------------------------------------------------------------------------
# Segment Means (PRISM Eq. 1) — compression hot path
# ---------------------------------------------------------------------------

def segment_means(x: jnp.ndarray, L: int, axis: int = -2) -> jnp.ndarray:
    """Column-wise means of L equal segments along ``axis``.

    Kernel path: token axis 1 of a [B, N, ...feature] tensor (the layout of
    every exchange call site); anything else falls back to the reference.
    """
    axis = axis % x.ndim
    if _use_pallas():
        if axis == 1 and x.ndim >= 3 and L > 0 and x.shape[1] % L == 0:
            from repro.kernels.segment_means.ops import segment_means_op
            return segment_means_op(x, L, interpret=_interpret())
        _fallback("segment_means", "layout")
    return ref_sm.segment_means(x, L, axis=axis)


def segment_means_masked(x: jnp.ndarray, L: int, mask: jnp.ndarray,
                         axis: int = -2
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mask-aware segment means → (means, counts); see the reference for
    semantics.  The kernel has no mask input, but masked means factor into
    an unmasked segment-sum (the kernel) and a cheap [B, N] count
    reduction:  mean = (seg · kernel_mean(x·mask)) / max(count, 1).
    """
    axis = axis % x.ndim
    if _use_pallas():
        if (axis == 1 and x.ndim >= 3 and L > 0 and x.shape[1] % L == 0
                and mask.ndim == 2):
            from repro.kernels.segment_means.ops import segment_means_op
            B, N = x.shape[:2]
            seg = N // L
            mf = mask.astype(jnp.float32)
            counts = mf.reshape(B, L, seg).sum(axis=-1)           # [B, L]
            mx = x.astype(jnp.float32) * mf.reshape(
                (B, N) + (1,) * (x.ndim - 2))
            sums = (segment_means_op(mx, L, interpret=_interpret())
                    * float(seg))
            denom = jnp.maximum(counts, 1.0).reshape(
                (B, L) + (1,) * (x.ndim - 2))
            return (sums / denom).astype(x.dtype), counts
        _fallback("segment_means_masked", "layout")
    return ref_sm.segment_means_masked(x, L, mask, axis=axis)


# ---------------------------------------------------------------------------
# One-token decode attention — the generation hot path
# ---------------------------------------------------------------------------

def decode_attention(q: jnp.ndarray,        # [B, 1, H, dh]
                     k_cache: jnp.ndarray,  # [B, S, Hk, dh]
                     v_cache: jnp.ndarray,
                     cache_len,             # [B] or scalar — valid prefix
                     *,
                     offset: int = 0,
                     window: Optional[int] = None,
                     logit_softcap: Optional[float] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token attention against a (device-local) KV cache, masked to
    the valid ``cache_len`` prefix (optionally sliding-``window``-limited).

    Pallas path: the flash-decode kernel's (o·l, m, l) partials, normalized
    locally (the single-shard degenerate of the cross-shard LSE merge).
    """
    if _use_pallas():
        if _decode_tiles(k_cache):
            from repro.kernels.flash_decode.ops import flash_decode_op
            o, m, l = flash_decode_op(q, k_cache, v_cache, cache_len,
                                      offset=offset, window=window,
                                      scale=scale, softcap=logit_softcap,
                                      interpret=_interpret())
            out = o / jnp.maximum(l, 1e-38)[..., None]            # [B, H, dh]
            return out[:, None].astype(q.dtype)                   # [B,1,H,dh]
        _fallback("decode_attention", "head_dim")
    from repro.kernels.flash_decode.ops import validity_mask
    valid = validity_mask(q.shape[0], k_cache.shape[1], cache_len,
                          offset=offset, window=window)
    return ref_attn.reference_attention(
        q, k_cache, v_cache, kv_mask=valid,
        logit_softcap=logit_softcap, scale=scale)


def decode_attention_paged(q: jnp.ndarray,           # [B, 1, H, dh]
                           k_pool: jnp.ndarray,      # [P, ps, Hk, dh]
                           v_pool: jnp.ndarray,
                           page_table: jnp.ndarray,  # [B, max_pages] int32
                           cache_len,                # [B] — valid prefix
                           *,
                           logit_softcap: Optional[float] = None,
                           scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token attention against a *paged* KV pool: each request's
    cache is the concatenation of the pool pages named by its page-table
    row, masked to the valid ``cache_len`` prefix.

    Reference path: materialize the gather with ``jnp.take`` and run the
    exact dense reference (CPU/interpret parity oracle).  Pallas path: the
    paged flash-decode kernel indexes pool pages through the scalar-
    prefetched table — no gather is ever materialized.
    """
    if _use_pallas():
        if _decode_tiles(k_pool):
            from repro.kernels.flash_decode.paged import flash_decode_paged_op
            o, m, l = flash_decode_paged_op(q, k_pool, v_pool, page_table,
                                            cache_len, scale=scale,
                                            softcap=logit_softcap,
                                            interpret=_interpret())
            out = o / jnp.maximum(l, 1e-38)[..., None]            # [B, H, dh]
            return out[:, None].astype(q.dtype)                   # [B,1,H,dh]
        _fallback("decode_attention_paged", "head_dim")
    from repro.kernels.flash_decode.ops import validity_mask
    from repro.kernels.flash_decode.paged import gather_pages
    k = gather_pages(k_pool, page_table)
    v = gather_pages(v_pool, page_table)
    valid = validity_mask(q.shape[0], k.shape[1], cache_len)
    return ref_attn.reference_attention(
        q, k, v, kv_mask=valid, logit_softcap=logit_softcap, scale=scale)


# ---------------------------------------------------------------------------
# PRISM prefill attention (scaling-aware softmax over local ‖ remote means)
# ---------------------------------------------------------------------------

def prism_attention(q, k_local, v_local, k_means, v_means, part_idx,
                    seg_size: int, *, causal: bool = False,
                    logit_softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    kv_mask: Optional[jnp.ndarray] = None,
                    mean_counts: Optional[jnp.ndarray] = None,
                    q_offset=0) -> jnp.ndarray:
    """Scaling-aware softmax attention (see ``repro.core.prism_attention``).

    The kernel supports unpadded local keys, a static q-offset of 0, and
    partitions whose K/V fit in VMEM whole; the padded / chunk-recursed /
    longer cases use the reference.
    """
    if _use_pallas():
        from repro.kernels.prism_attention.ops import (fits_vmem,
                                                       prism_attention_op)
        M = k_means.shape[1] * k_means.shape[2]
        if kv_mask is not None:
            _fallback("prism_attention", "kv_mask")
        elif not (isinstance(q_offset, int) and q_offset == 0):
            _fallback("prism_attention", "q_offset")
        elif not (_interpret() or fits_vmem(
                q.shape[1], k_local.shape[1], M, q.shape[-1],
                max(q.dtype.itemsize, k_local.dtype.itemsize))):
            _fallback("prism_attention", "vmem")
        else:
            return prism_attention_op(
                q, k_local, v_local, k_means, v_means, part_idx, seg_size,
                causal=causal, scale=scale, softcap=logit_softcap,
                mean_counts=mean_counts, interpret=_interpret())
    return ref_attn.prism_attention(
        q, k_local, v_local, k_means, v_means, part_idx, seg_size,
        causal=causal, logit_softcap=logit_softcap, scale=scale,
        kv_mask=kv_mask, mean_counts=mean_counts, q_offset=q_offset)


def backend_info() -> dict:
    """What would run right now (benchmarks / docs / bug reports)."""
    return {"resolved": resolve_backend(),
            "override": _OVERRIDE,
            "env": os.environ.get(ENV_VAR),
            "jax_backend": jax.default_backend(),
            "interpret": _interpret()}
