"""jit'd wrapper: builds each row's valid span from (cache_len, offset,
window) and merges shard partials (the exact LSE combine used across
devices)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.kernel import NEG_INF, flash_decode_pallas


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.lru_cache(maxsize=None)
def pick_s_block(S: int) -> int:
    """Largest power-of-two tile (8..512) dividing ``S``, else ``S`` whole.

    A TPU block's sublane dim must be a multiple of 8 or the full dim, so
    the search never goes below 8.  Cached per S — the divisor search used
    to rerun on every trace of ``flash_decode_op``."""
    for t in (512, 256, 128, 64, 32, 16, 8):
        if S % t == 0:
            return t
    return S


def valid_span(B: int, cache_len, offset=0,
               window: Optional[int] = None) -> jnp.ndarray:
    """[B, 2] int32: each row's valid cache slots of this shard, as local
    positions [lo, hi) — below the (global) ``cache_len`` and inside the
    sliding window.  The ONE definition of cache validity: the kernels
    compare against it, and ``validity_mask`` (the reference) expands it."""
    hi = jnp.broadcast_to(jnp.reshape(jnp.asarray(cache_len, jnp.int32),
                                      (-1,)), (B,)) - offset
    lo = jnp.zeros_like(hi) if window is None else hi - window
    return jnp.stack([lo, hi], axis=-1).astype(jnp.int32)


def validity_mask(B: int, S: int, cache_len, offset=0,
                  window: Optional[int] = None) -> jnp.ndarray:
    """[B, S] bool: True where the slot is in the row's ``valid_span``."""
    span = valid_span(B, cache_len, offset=offset, window=window)
    pos = jnp.arange(S)[None, :]
    return (pos >= span[:, :1]) & (pos < span[:, 1:])


def validity_bias(B: int, S: int, cache_len, offset=0,
                  window: Optional[int] = None) -> jnp.ndarray:
    """[B, S] additive bias: 0 where valid, -inf where empty / outside the
    sliding window (the input of the ``flash_decode_ref`` oracle)."""
    ok = validity_mask(B, S, cache_len, offset=offset, window=window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def flash_decode_op(q: jnp.ndarray,      # [B, 1, H, dh] or [B, H, dh]
                    k: jnp.ndarray,      # [B, S, Hk, dh]
                    v: jnp.ndarray,
                    cache_len,
                    *, offset=0, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    softcap: Optional[float] = None,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Partial attention over the local shard → (o_unnorm, m, l)."""
    interpret = _on_cpu() if interpret is None else interpret
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    B = q.shape[0]
    S = k.shape[1]
    span = valid_span(B, cache_len, offset=offset, window=window)
    return flash_decode_pallas(q, k, v, span, scale=scale, softcap=softcap,
                               s_block=pick_s_block(S), interpret=interpret)


def merge_partials(o, m, l) -> jnp.ndarray:
    """Combine [n_shards, B, H, dh] partials exactly (flash-decoding)."""
    m_star = jnp.max(m, axis=0)                              # [B, H]
    w = jnp.exp(m - m_star[None])
    l_tot = jnp.sum(w * l, axis=0)
    o_tot = jnp.sum(w[..., None] * o, axis=0)
    return o_tot / l_tot[..., None]
