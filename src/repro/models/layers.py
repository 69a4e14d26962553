"""Shared model building blocks (pure-JAX, module-free).

Conventions
-----------
* Parameters are nested dicts of ``jnp.ndarray`` (or ``ShapeDtypeStruct`` in
  abstract/dry-run mode); every layer ships an ``init_*`` and an ``apply``
  function.  No framework dependency beyond jax.
* Weights are stored in ``cfg.dtype`` (bf16 by default); math that needs it
  (norms, softmax, RoPE) runs in f32 and casts back.
* Attention is the paper's integration point: ``ExchangeConfig`` decides how
  K/V cross sequence partitions (LOCAL / VOLTAGE full-tensor / PRISM segment
  means) — see ``repro.core.exchange``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.exchange import (ExchangeConfig, ExchangeMode,
                                 decode_attention_sharded, exchange_attention)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = (d_in ** -0.5) if scale is None else scale
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.zeros((d,), dtype)}        # stored zero-centered


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm with (1 + scale) weighting (llama/gemma convention)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


def init_layernorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(kind: str, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return layernorm(params, x) if kind == "layernorm" else rmsnorm(params, x)


def init_norm(kind: str, d: int, dtype=jnp.float32) -> Params:
    return init_layernorm(d, dtype) if kind == "layernorm" else init_rmsnorm(d, dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 · mscale · ln(factor) + 1 (1 unscaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, y) -> Tuple[np.ndarray,
                                                             float]:
    """(inverse frequencies [hd/2], cos/sin factor) of YaRN rope ``y``
    (a ``YaRNCfg``), in DeepSeek-V2's form: each frequency blends the
    original one and the ``factor``-interpolated one by a linear ramp
    between the correction dims of ``beta_fast`` and ``beta_slow``."""
    half = head_dim // 2
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2) / head_dim)
    inter = extra / y.factor

    def corr_dim(rotations):
        return (head_dim * math.log(y.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp                       # 1: the original frequency
    inv = inter * (1 - keep) + extra * keep
    factor = (yarn_mscale(y.factor, y.mscale)
              / yarn_mscale(y.factor, y.mscale_all_dim))
    return inv.astype(np.float32), float(factor)


def rope_tables(positions: jnp.ndarray, head_dim: int, theta: float,
                scaling=None):
    """cos/sin tables for given (possibly sharded) integer positions.

    positions: [..., N] int32 global positions → ([..., N, hd/2], ...) f32.
    ``scaling``: a ``YaRNCfg`` (YaRN frequencies and cos/sin factor), or
    None for plain rope.
    """
    half = head_dim // 2
    if scaling is None:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
        factor = 1.0
    else:
        freqs, factor = yarn_frequencies(head_dim, theta, scaling)
        freqs = jnp.asarray(freqs)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if factor == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate [..., N, H, hd] by per-position tables [..., N, hd/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]        # broadcast over heads
    s = sin[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, dtype, gated: bool = True) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d, d_ff, dtype)
    return p


def apply_mlp(params: Params, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    up = x @ params["w_up"]
    if "w_gate" in params:
        gate = x @ params["w_gate"]
        h = _act(gate, act) * up
    else:
        h = _act(up, act)
    return h @ params["w_down"]


def _act(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {kind}")


# ---------------------------------------------------------------------------
# GQA attention with PRISM/Voltage/local exchange
# ---------------------------------------------------------------------------

def init_attention(key, d: int, n_heads: int, n_kv: int, head_dim: int, dtype,
                   qkv_bias: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d, n_kv * head_dim, dtype),
        "wv": dense_init(ks[2], d, n_kv * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static attention behaviour for one layer."""
    n_heads: int
    n_kv: int
    head_dim: int
    causal: bool = True
    window: Optional[int] = None          # sliding window (gemma2 local layers)
    logit_softcap: Optional[float] = None
    rope_theta: float = 10000.0
    use_rope: bool = True
    scale: Optional[float] = None         # override 1/sqrt(hd) (gemma2 uses
                                          # query_pre_attn_scalar)


def project_qkv(params: Params, x: jnp.ndarray, spec: AttnSpec,
                positions: Optional[jnp.ndarray]):
    """Linear projections + RoPE. x: [B, N, D] → q [B,N,H,hd], k/v [B,N,Hk,hd]."""
    B, N, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, N, spec.n_heads, spec.head_dim)
    k = k.reshape(B, N, spec.n_kv, spec.head_dim)
    v = v.reshape(B, N, spec.n_kv, spec.head_dim)
    if spec.use_rope:
        if positions is None:
            positions = jnp.arange(N, dtype=jnp.int32)[None, :]
        cos, sin = rope_tables(positions, spec.head_dim, spec.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def attention_block(
    params: Params,
    x: jnp.ndarray,                       # [B, N, D] (N possibly seq-sharded)
    spec: AttnSpec,
    xcfg: ExchangeConfig,
    *,
    positions: Optional[jnp.ndarray] = None,   # [B, N] global positions
) -> jnp.ndarray:
    """Full-sequence (train/prefill) attention with the configured exchange."""
    q, k, v = project_qkv(params, x, spec, positions)
    out = exchange_attention(
        q, k, v, xcfg, causal=spec.causal, window=spec.window,
        logit_softcap=spec.logit_softcap, scale=spec.scale)
    B, N = x.shape[:2]
    return out.reshape(B, N, spec.n_heads * spec.head_dim) @ params["wo"]


def _quantize_kv(t: jnp.ndarray):
    """Symmetric per-(token, head) int8 quantization: [B,1,Hk,dh] →
    (int8 values, f32 scale [B,1,Hk])."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def attention_decode(
    params: Params,
    x: jnp.ndarray,                       # [B, 1, D] new token features
    spec: AttnSpec,
    xcfg: ExchangeConfig,
    cache: Dict[str, jnp.ndarray],        # {"k": [B,S,Hk,hd], "v": ..., }
    cache_index,                          # scalar int32 — write position
    *,
    k_means: Optional[jnp.ndarray] = None,
    v_means: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One autoregressive step against a (possibly sequence-sharded) cache.

    Caches created with ``quant=True`` hold int8 values + per-(token, head)
    f32 scales; dequantization happens per layer on the device-local shard
    (transient bf16, the resident cache stays int8 — 2× HBM saving)."""
    B = x.shape[0]
    pos = jnp.full((B, 1), cache_index, jnp.int32)
    q, k_new, v_new = project_qkv(params, x, spec, pos)
    quant = "k_scale" in cache
    if quant:
        k_q, k_s = _quantize_kv(k_new)
        v_q, v_s = _quantize_kv(v_new)
        new_cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_q, cache_index, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_q, cache_index, axis=1),
            "k_scale": jax.lax.dynamic_update_slice_in_dim(
                cache["k_scale"], k_s, cache_index, axis=1),
            "v_scale": jax.lax.dynamic_update_slice_in_dim(
                cache["v_scale"], v_s, cache_index, axis=1),
        }
        k_cache = _dequantize_kv(new_cache["k"], new_cache["k_scale"],
                                 x.dtype)
        v_cache = _dequantize_kv(new_cache["v"], new_cache["v_scale"],
                                 x.dtype)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), cache_index, axis=1)
    cache_len = cache_index + 1
    if spec.window is not None:
        # sliding-window cache: only the last `window` positions are valid
        # (device-local — no sharded merge); kernel-dispatched.
        from repro.kernels import dispatch as kdsp
        out = kdsp.decode_attention(q, k_cache, v_cache, cache_len,
                                    window=spec.window,
                                    logit_softcap=spec.logit_softcap,
                                    scale=spec.scale)
    else:
        out = decode_attention_sharded(
            q, k_cache, v_cache, cache_len, xcfg,
            logit_softcap=spec.logit_softcap, scale=spec.scale,
            k_means=k_means, v_means=v_means)
    y = out.reshape(B, 1, spec.n_heads * spec.head_dim) @ params["wo"]
    if quant:
        return y, new_cache
    return y, {"k": k_cache, "v": v_cache}


def attention_decode_paged(
    params: Params,
    x: jnp.ndarray,                       # [S, 1, D] new token features
    spec: AttnSpec,
    xcfg: ExchangeConfig,
    pool: Dict[str, jnp.ndarray],         # {"k": [L,P,ps,Hk·hd], "v": ...}
    layer,                                # int32 scalar — this layer
    page_table: jnp.ndarray,              # [S, max_pages] int32
    lengths: jnp.ndarray,                 # [S] int32 — per-row write position
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One autoregressive step of layer ``layer`` against a shared *paged*
    KV pool, every layer's pages stacked, read and written where it lies.

    Unlike ``attention_decode`` (scalar ``cache_index``, dense per-request
    cache, vmapped per row by the serving chunk), all rows step together
    here — the pool is shared state, so per-row vmap would fork it.  Row
    ``b`` writes its new K/V at logical position ``lengths[b]``, which the
    page table resolves to ``(layer, page_table[b, len//ps], len % ps)`` of
    the stack, in one scatter; attention then reads the layer's pages
    through ``kdsp.decode_attention_paged``.  Rows never write into shared
    (refcount > 1) pages: the allocator COW-copies any partially-filled
    shared page at admit, so a row's write frontier always lands in a page
    it exclusively owns (or the trash page, for idle rows).
    """
    if spec.window is not None:
        raise NotImplementedError("paged decode has no sliding-window path")
    B = x.shape[0]
    pos = lengths[:, None].astype(jnp.int32)                  # [S, 1]
    q, k_new, v_new = project_qkv(params, x, spec, pos)
    ps = pool["k"].shape[2]
    blk = (lengths // ps).astype(jnp.int32)
    wp = jnp.take_along_axis(page_table, blk[:, None], axis=1)[:, 0]  # [S]
    off = lengths % ps
    k_pool = pool["k"].at[layer, wp, off].set(
        k_new[:, 0].reshape(B, -1).astype(pool["k"].dtype))
    v_pool = pool["v"].at[layer, wp, off].set(
        v_new[:, 0].reshape(B, -1).astype(pool["v"].dtype))
    from repro.kernels import dispatch as kdsp
    out = kdsp.decode_attention_paged(
        q, k_pool, v_pool, layer, page_table, lengths + 1,
        logit_softcap=spec.logit_softcap, scale=spec.scale)
    y = out.reshape(B, 1, spec.n_heads * spec.head_dim) @ params["wo"]
    return y, {"k": k_pool, "v": v_pool}


def prefill_kv_cache(cache: Dict[str, jnp.ndarray], k_new: jnp.ndarray,
                     v_new: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Bulk-write projected prompt K/V [B, T0, Hk, hd] into positions
    [0, T0) of a decode cache (single-pass prefill).  Quantized caches get
    the same per-(token, head) int8 quantization the per-step path applies,
    so prefill-then-decode and decode-only caches are bit-identical."""
    if "k_scale" in cache:
        k_q, k_s = _quantize_kv(k_new)
        v_q, v_s = _quantize_kv(v_new)
        upd = {"k": k_q, "v": v_q, "k_scale": k_s, "v_scale": v_s}
        return {name: jax.lax.dynamic_update_slice_in_dim(
                    cache[name], val, 0, axis=1)
                for name, val in upd.items()}
    return {"k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), 0, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), 0, axis=1)}


def init_kv_cache(batch: int, seq: int, n_kv: int, head_dim: int, dtype,
                  quant: bool = False):
    shape = (batch, seq, n_kv, head_dim)
    if quant:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# embeddings / unembed
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype) -> Params:
    return {"table": embed_init(key, vocab, d, dtype)}


def embed(params: Params, tokens: jnp.ndarray, scale_by_sqrt_d: bool = False):
    x = jnp.take(params["table"], tokens, axis=0)
    if scale_by_sqrt_d:
        x = x * jnp.asarray(x.shape[-1] ** 0.5, x.dtype)
    return x


def unembed(params: Params, x: jnp.ndarray,
            final_softcap: Optional[float] = None) -> jnp.ndarray:
    logits = (x @ params["table"].T).astype(jnp.float32)
    if final_softcap is not None:
        logits = final_softcap * jnp.tanh(logits / final_softcap)
    return logits
