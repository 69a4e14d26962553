"""Multi-head Latent Attention (DeepSeek-V2) with PRISM latent exchange.

The KV path is compressed to a rank-``r`` latent ``c_kv`` plus a shared
rotary key ``k_pe``; only ``r + d_rope`` floats/token are cached or
communicated. PRISM's segment means are taken **in latent space** (the two
compressions compound — see ``repro.core.exchange.exchange_attention_mla``),
and decode uses the absorbed formulation (W_uk folded into the query,
W_uv applied after attention) so the cache is never expanded.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLACfg
from repro.core.exchange import ExchangeConfig, ExchangeMode, exchange_attention_mla
from repro.models.layers import (apply_rope, dense_init, init_rmsnorm,
                                 rmsnorm, rope_tables, yarn_mscale)

Params = Dict[str, Any]


def init_mla(key, d: int, n_heads: int, cfg: MLACfg, dtype) -> Params:
    ks = jax.random.split(key, 8)
    H = n_heads
    dq = H * (cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.q_lora_rank is None:            # DeepSeek-V2-Lite: one projection
        q = {"w_q": dense_init(ks[0], d, dq, dtype)}
    else:
        q = {"w_dq": dense_init(ks[0], d, cfg.q_lora_rank, dtype),
             "q_norm": init_rmsnorm(cfg.q_lora_rank),
             "w_uq": dense_init(ks[1], cfg.q_lora_rank, dq, dtype)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_dim, dtype),
        "kv_norm": init_rmsnorm(cfg.kv_lora_rank),
        # stored [r, H, dim] so mean-then-expand is a single einsum
        "w_uk": (jax.random.normal(ks[3], (cfg.kv_lora_rank, H, cfg.qk_nope_dim),
                                   jnp.float32) * cfg.kv_lora_rank ** -0.5
                 ).astype(dtype),
        "w_uv": (jax.random.normal(ks[4], (cfg.kv_lora_rank, H, cfg.v_head_dim),
                                   jnp.float32) * cfg.kv_lora_rank ** -0.5
                 ).astype(dtype),
        "wo": dense_init(ks[5], H * cfg.v_head_dim, d, dtype),
    }


def softmax_scale(cfg: MLACfg, rope_scaling=None) -> float:
    """1/sqrt(nope + rope dims), times YaRN's mscale(mscale_all_dim)^2."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if rope_scaling is not None and rope_scaling.mscale_all_dim:
        scale *= yarn_mscale(rope_scaling.factor,
                             rope_scaling.mscale_all_dim) ** 2
    return scale


def _project_q(params: Params, x: jnp.ndarray, H: int, cfg: MLACfg,
               positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    B, N, _ = x.shape
    if "w_q" in params:
        q = x @ params["w_q"]
    else:
        q = rmsnorm(params["q_norm"], x @ params["w_dq"]) @ params["w_uq"]
    q = q.reshape(B, N, H, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_pe = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    cos, sin = rope_tables(positions, cfg.qk_rope_dim, theta, scaling)
    q_pe = apply_rope(q_pe, cos, sin)
    return jnp.concatenate([q_nope, q_pe], axis=-1)


def _project_kv_latent(params: Params, x: jnp.ndarray, cfg: MLACfg,
                       positions: jnp.ndarray, theta: float, scaling=None):
    ckv = x @ params["w_dkv"]
    c_kv, k_pe = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = rmsnorm(params["kv_norm"], c_kv)
    cos, sin = rope_tables(positions, cfg.qk_rope_dim, theta, scaling)
    k_pe = apply_rope(k_pe[..., None, :], cos, sin)[..., 0, :]
    return c_kv, k_pe


def mla_block(params: Params, x: jnp.ndarray, n_heads: int, cfg: MLACfg,
              xcfg: ExchangeConfig, *, positions: Optional[jnp.ndarray] = None,
              rope_theta: float = 10000.0, rope_scaling=None) -> jnp.ndarray:
    """Full-sequence MLA attention (train / prefill)."""
    B, N, _ = x.shape
    if positions is None:
        positions = jnp.arange(N, dtype=jnp.int32)[None, :]
    q = _project_q(params, x, n_heads, cfg, positions, rope_theta,
                   rope_scaling)
    c_kv, k_pe = _project_kv_latent(params, x, cfg, positions, rope_theta,
                                    rope_scaling)
    scale = softmax_scale(cfg, rope_scaling)
    out = exchange_attention_mla(q, c_kv, k_pe, params["w_uk"], params["w_uv"],
                                 xcfg, causal=True, scale=scale)
    return out.reshape(B, N, n_heads * cfg.v_head_dim) @ params["wo"]


def mla_prefill(params: Params, x: jnp.ndarray, n_heads: int, cfg: MLACfg,
                xcfg: ExchangeConfig, cache: Dict[str, jnp.ndarray],
                *, positions: Optional[jnp.ndarray] = None,
                rope_theta: float = 10000.0, rope_scaling=None
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence MLA attention that also bulk-writes the latent cache
    for positions [0, N) — the single-pass prefill analogue of
    ``mla_block`` (same math) + ``mla_decode``'s cache updates."""
    B, N, _ = x.shape
    if positions is None:
        positions = jnp.arange(N, dtype=jnp.int32)[None, :]
    q = _project_q(params, x, n_heads, cfg, positions, rope_theta,
                   rope_scaling)
    c_kv, k_pe = _project_kv_latent(params, x, cfg, positions, rope_theta,
                                    rope_scaling)
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1)
    pe_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k_pe"], k_pe.astype(cache["k_pe"].dtype), 0, axis=1)
    scale = softmax_scale(cfg, rope_scaling)
    out = exchange_attention_mla(q, c_kv, k_pe, params["w_uk"], params["w_uv"],
                                 xcfg, causal=True, scale=scale)
    y = out.reshape(B, N, n_heads * cfg.v_head_dim) @ params["wo"]
    return y, {"c_kv": c_cache, "k_pe": pe_cache}


def mla_decode(params: Params, x: jnp.ndarray, n_heads: int, cfg: MLACfg,
               xcfg: ExchangeConfig, cache: Dict[str, jnp.ndarray],
               cache_index, *, rope_theta: float = 10000.0,
               rope_scaling=None
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Absorbed-form decode over the latent cache.

    logits_h = q_nope_h·W_uk_h·c_kv^T + q_pe·k_pe^T ;  out_h = (p·c_kv)·W_uv_h
    — the per-token work in the cache dimension is O(r + d_rope), and the
    latent cache shards over the sequence axis exactly like a K/V cache
    (flash-decoding LSE merge, see below).
    """
    B = x.shape[0]
    H = n_heads
    pos = jnp.full((B, 1), cache_index, jnp.int32)
    q = _project_q(params, x, H, cfg, pos, rope_theta,
                   rope_scaling)                                 # [B,1,H,dq]
    q_nope, q_pe = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    c_new, pe_new = _project_kv_latent(params, x, cfg, pos, rope_theta,
                                       rope_scaling)
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), cache_index, axis=1)
    pe_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k_pe"], pe_new.astype(cache["k_pe"].dtype), cache_index, axis=1)

    # absorb: q_lat[b,1,h,r] = q_nope · W_uk^T
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, params["w_uk"])
    scale = softmax_scale(cfg, rope_scaling)
    cache_len = cache_index + 1

    from repro.core.exchange import mla_decode_attention_sharded
    o_lat = mla_decode_attention_sharded(
        q_lat, q_pe, c_cache, pe_cache, cache_len, xcfg, scale=scale)
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat, params["w_uv"])
    y = out.reshape(B, 1, H * cfg.v_head_dim) @ params["wo"]
    return y, {"c_kv": c_cache, "k_pe": pe_cache}


def mla_decode_paged(params: Params, x: jnp.ndarray, n_heads: int,
                     cfg: MLACfg, pool: Dict[str, jnp.ndarray], layer,
                     page_table: jnp.ndarray, lengths: jnp.ndarray, *,
                     rope_theta: float = 10000.0, rope_scaling=None
                     ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Absorbed-form decode of every row against layer ``layer`` of the
    shared *paged* latent pool (``{"c_kv": [L, P, ps, r], "k_pe": [L, P,
    ps, dr padded]}``): row ``b`` writes its new ``c_kv``/``k_pe`` at
    logical position ``lengths[b]`` through its page-table row, then its
    absorbed query scores every head against the row's latent pages
    (``kdsp.decode_attention_latent_paged``); ``W_uv`` and ``wo`` run
    after the kernel, as in ``mla_decode``."""
    from repro.kernels import dispatch as kdsp
    B = x.shape[0]
    H = n_heads
    pos = lengths[:, None].astype(jnp.int32)                      # [S, 1]
    q = _project_q(params, x, H, cfg, pos, rope_theta, rope_scaling)
    q_nope, q_pe = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    c_new, pe_new = _project_kv_latent(params, x, cfg, pos, rope_theta,
                                       rope_scaling)
    ps = pool["c_kv"].shape[2]
    wp = jnp.take_along_axis(page_table, (lengths // ps)[:, None].astype(
        jnp.int32), axis=1)[:, 0]                                 # [S]
    off = lengths % ps
    c_pool = pool["c_kv"].at[layer, wp, off].set(
        c_new[:, 0].astype(pool["c_kv"].dtype))
    pe_pool = pool["k_pe"].at[layer, wp, off].set(
        pad_lanes(pe_new[:, 0], pool["k_pe"].shape[-1]).astype(
            pool["k_pe"].dtype))
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, params["w_uk"])
    o_lat = kdsp.decode_attention_latent_paged(
        q_lat, q_pe, c_pool, pe_pool, layer, page_table, lengths + 1,
        scale=softmax_scale(cfg, rope_scaling))
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat, params["w_uv"])
    y = out.reshape(B, 1, H * cfg.v_head_dim) @ params["wo"]
    return y, {"c_kv": c_pool, "k_pe": pe_pool}


# A latent page's k_pe rows are held padded to the TPU's lane width: a
# 64-wide minor dim is laid out padded to 128 lanes anyway, and the paged
# kernel's page copies must span whole lanes.
PE_LANES = 128


def init_mla_cache(batch: int, seq: int, cfg: MLACfg, dtype,
                   paged: bool = False):
    """Latent cache ``c_kv`` [batch, seq, r] and ``k_pe`` [batch, seq, dr];
    ``paged`` (the page pool, batch = pages and seq = page size) holds
    ``k_pe`` zero-padded to a multiple of ``PE_LANES``."""
    dr = cfg.qk_rope_dim
    if paged:
        dr = -(-dr // PE_LANES) * PE_LANES
    return {"c_kv": jnp.zeros((batch, seq, cfg.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, seq, dr), dtype)}


def pad_lanes(t: jnp.ndarray, width: int) -> jnp.ndarray:
    """``t`` zero-padded on its last dim to ``width`` (a paged ``k_pe``)."""
    pad = width - t.shape[-1]
    if pad == 0:
        return t
    return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, pad)])
