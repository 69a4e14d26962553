"""Unified Transformer stacks for every assigned architecture family.

One scan-based implementation covers: dense GQA decoders (llama / qwen /
internlm), gemma2 (local–global alternation, softcaps, post-norms), MoE
decoders (deepseek-moe / deepseek-v2 with MLA), encoder–decoder (whisper),
VLM with interleaved cross-attention (llama-3.2-vision), hybrid
attention+SSM (hymba) and pure-recurrent (xLSTM).

Layer parameters are **stacked** along a leading group axis and consumed by
``jax.lax.scan`` (with per-layer ``jax.checkpoint``), so HLO size — and
dry-run compile time — is independent of depth. Heterogeneous stacks (gemma
local/global pairs, VLM 1-in-k cross layers, xLSTM 1-in-k sLSTM) scan over
*groups* holding one stack per member role.

Entry points:
  init_lm(key, cfg)                     → params pytree
  forward_lm(params, batch, cfg, xcfg)  → (logits, aux)   train / full fwd
  init_decode_cache(cfg, B, S)          → cache pytree
  prefill(params, batch, cache, cfg, xcfg) → (last logits, primed cache)
  decode_step(params, batch, cache, i, cfg, xcfg) → (logits, cache)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.exchange import (ExchangeConfig, ExchangeMode,
                                 exchange_cross_attention, pin_activations)
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (AttnSpec, apply_mlp, apply_norm,
                                 attention_block, attention_decode,
                                 attention_decode_paged, embed,
                                 init_attention, init_embedding, init_kv_cache,
                                 init_mlp, init_norm, prefill_kv_cache,
                                 project_qkv, unembed)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# attention specs per layer kind
# ---------------------------------------------------------------------------

def _attn_spec(cfg: ModelConfig, *, window: Optional[int] = None,
               causal: Optional[bool] = None, use_rope: bool = True) -> AttnSpec:
    return AttnSpec(
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
        causal=cfg.causal if causal is None else causal,
        window=window, logit_softcap=cfg.attn_softcap,
        rope_theta=cfg.rope_theta, use_rope=use_rope and cfg.rope_theta > 0,
        scale=cfg.query_scale)


def _stack(init_fn, key, n: int):
    """Stack ``n`` independent inits along a leading axis (scan layout)."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def pad_len(n: int, shards: int, L: int) -> int:
    """Pad a memory length so each of ``shards`` partitions splits into L
    integer segments (mask-aware means handle the remainder exactly)."""
    q = shards * max(L, 1)
    return ((n + q - 1) // q) * q


# ---------------------------------------------------------------------------
# per-family layer init / apply
# ---------------------------------------------------------------------------

def _init_dense_layer(cfg: ModelConfig):
    d, dtype = cfg.d_model, cfg.jdtype

    def init(key):
        ks = jax.random.split(key, 2)
        p = {"ln1": init_norm(cfg.norm_type, d),
             "attn": init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.hd, dtype, qkv_bias=cfg.qkv_bias),
             "ln2": init_norm(cfg.norm_type, d),
             "mlp": init_mlp(ks[1], d, cfg.d_ff, dtype,
                             gated=cfg.act != "gelu")}
        if cfg.post_norms:
            p["post_attn"] = init_norm(cfg.norm_type, d)
            p["post_mlp"] = init_norm(cfg.norm_type, d)
        return p
    return init


def _apply_attn_mlp(p: Params, x, cfg: ModelConfig, xcfg, spec: AttnSpec,
                    positions, mlp_fn=None):
    """Standard pre-norm block: x + attn(ln(x)); x + mlp(ln(x))."""
    x = pin_activations(x, xcfg)
    h = attention_block(p["attn"], apply_norm(cfg.norm_type, p["ln1"], x),
                        spec, xcfg, positions=positions)
    if cfg.post_norms:
        h = apply_norm(cfg.norm_type, p["post_attn"], h)
    x = x + h
    hin = apply_norm(cfg.norm_type, p["ln2"], x)
    h2 = mlp_fn(hin) if mlp_fn else apply_mlp(p["mlp"], hin, cfg.act)
    aux = 0.0
    if isinstance(h2, tuple):
        h2, aux = h2
    if cfg.post_norms:
        h2 = apply_norm(cfg.norm_type, p["post_mlp"], h2)
    return x + h2, aux


def _apply_attn_mlp_prefill(p: Params, x, cfg: ModelConfig, xcfg,
                            spec: AttnSpec, positions, cache,
                            mlp_fn=None):
    """Full-sequence block that also bulk-writes the prompt K/V into the
    decode cache — the single-pass prefill analogue of ``_apply_attn_mlp``
    (same math) + ``_apply_attn_mlp_decode``'s cache updates."""
    x = pin_activations(x, xcfg)
    xin = apply_norm(cfg.norm_type, p["ln1"], x)
    q, k, v = project_qkv(p["attn"], xin, spec, positions)
    new_cache = prefill_kv_cache(cache, k, v)
    from repro.core.exchange import exchange_attention
    attn = exchange_attention(q, k, v, xcfg, causal=spec.causal,
                              window=spec.window,
                              logit_softcap=spec.logit_softcap,
                              scale=spec.scale)
    B, N = x.shape[:2]
    h = attn.reshape(B, N, spec.n_heads * spec.head_dim) @ p["attn"]["wo"]
    if cfg.post_norms:
        h = apply_norm(cfg.norm_type, p["post_attn"], h)
    x = x + h
    hin = apply_norm(cfg.norm_type, p["ln2"], x)
    h2 = mlp_fn(hin) if mlp_fn else apply_mlp(p["mlp"], hin, cfg.act)
    if isinstance(h2, tuple):
        h2 = h2[0]
    if cfg.post_norms:
        h2 = apply_norm(cfg.norm_type, p["post_mlp"], h2)
    return x + h2, new_cache


def _apply_attn_mlp_decode(p: Params, x, cfg: ModelConfig, xcfg,
                           spec: AttnSpec, cache, index, mlp_fn=None):
    h, new_cache = attention_decode(
        p["attn"], apply_norm(cfg.norm_type, p["ln1"], x), spec, xcfg,
        cache, index)
    if cfg.post_norms:
        h = apply_norm(cfg.norm_type, p["post_attn"], h)
    x = x + h
    hin = apply_norm(cfg.norm_type, p["ln2"], x)
    h2 = mlp_fn(hin) if mlp_fn else apply_mlp(p["mlp"], hin, cfg.act)
    if isinstance(h2, tuple):
        h2 = h2[0]
    if cfg.post_norms:
        h2 = apply_norm(cfg.norm_type, p["post_mlp"], h2)
    return x + h2, new_cache


# --- MoE -------------------------------------------------------------------

def _init_moe_layer(cfg: ModelConfig, dense_mlp: bool):
    d, dtype = cfg.d_model, cfg.jdtype
    m = cfg.moe

    def init(key):
        ks = jax.random.split(key, 3)
        p = {"ln1": init_norm(cfg.norm_type, d), "ln2": init_norm(cfg.norm_type, d)}
        if cfg.mla is not None:
            p["attn"] = mla_mod.init_mla(ks[0], d, cfg.n_heads, cfg.mla, dtype)
        else:
            p["attn"] = init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                       cfg.hd, dtype, qkv_bias=cfg.qkv_bias)
        if dense_mlp:
            p["mlp"] = init_mlp(ks[1], d, m.d_ff_dense, dtype)
        else:
            p["moe"] = moe_mod.init_moe(ks[1], d, m, dtype)
        return p
    return init


def _apply_moe_layer(p: Params, x, cfg: ModelConfig, xcfg, positions,
                     dense_mlp: bool):
    x = pin_activations(x, xcfg)
    if cfg.mla is not None:
        h = mla_mod.mla_block(p["attn"],
                              apply_norm(cfg.norm_type, p["ln1"], x),
                              cfg.n_heads, cfg.mla, xcfg,
                              positions=positions, rope_theta=cfg.rope_theta)
    else:
        h = attention_block(p["attn"],
                            apply_norm(cfg.norm_type, p["ln1"], x),
                            _attn_spec(cfg), xcfg, positions=positions)
    x = x + h
    hin = apply_norm(cfg.norm_type, p["ln2"], x)
    if dense_mlp:
        return x + apply_mlp(p["mlp"], hin, cfg.act), 0.0
    y, aux = moe_mod.apply_moe(p["moe"], hin, cfg.moe, cfg.act)
    return x + y, aux


# --- hymba (parallel attention ‖ mamba heads) ------------------------------

def _init_hymba_layer(cfg: ModelConfig):
    d, dtype = cfg.d_model, cfg.jdtype

    def init(key):
        ks = jax.random.split(key, 3)
        return {"ln1": init_norm(cfg.norm_type, d),
                "attn": init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                       cfg.hd, dtype),
                "mamba": ssm_mod.init_mamba(ks[1], d, cfg.ssm, dtype),
                "attn_norm": init_norm(cfg.norm_type, cfg.n_heads * cfg.hd),
                "ssm_norm": init_norm(cfg.norm_type, d),
                "fuse": (jnp.zeros((cfg.n_heads * cfg.hd, d), dtype)
                         if cfg.n_heads * cfg.hd != d else None),
                "ln2": init_norm(cfg.norm_type, d),
                "mlp": init_mlp(ks[2], d, cfg.d_ff, dtype)}
    return init


def _hymba_mix(p, attn_out, ssm_out, cfg):
    """Hymba's fusion: per-path normalization then mean (arXiv:2411.13676)."""
    a = apply_norm(cfg.norm_type, p["attn_norm"], attn_out)
    if p.get("fuse") is not None:
        a = a @ p["fuse"]
    s = apply_norm(cfg.norm_type, p["ssm_norm"], ssm_out)
    return 0.5 * (a + s)


def _apply_hymba_layer(p, x, cfg: ModelConfig, xcfg, positions):
    x = pin_activations(x, xcfg)
    xin = apply_norm(cfg.norm_type, p["ln1"], x)
    spec = _attn_spec(cfg)
    from repro.models.layers import project_qkv  # local import for clarity
    from repro.core.exchange import exchange_attention
    q, k, v = project_qkv(p["attn"], xin, spec, positions)
    attn_out = exchange_attention(q, k, v, xcfg, causal=True)
    B, N = x.shape[:2]
    attn_out = attn_out.reshape(B, N, cfg.n_heads * cfg.hd) @ p["attn"]["wo"]
    ssm_out, _ = ssm_mod.mamba_scan(p["mamba"], xin, cfg.ssm)
    x = x + _hymba_mix(p, attn_out, ssm_out, cfg)
    h2 = apply_mlp(p["mlp"], apply_norm(cfg.norm_type, p["ln2"], x), cfg.act)
    return x + h2, 0.0


def _apply_hymba_decode(p, x, cfg, xcfg, cache, index):
    xin = apply_norm(cfg.norm_type, p["ln1"], x)
    spec = _attn_spec(cfg)
    attn_out, kv_cache = attention_decode(p["attn"], xin, spec, xcfg,
                                          cache["kv"], index)
    ssm_out, sstate = ssm_mod.mamba_step(p["mamba"], xin, cfg.ssm,
                                         cache["ssm"])
    x = x + _hymba_mix(p, attn_out, ssm_out, cfg)
    h2 = apply_mlp(p["mlp"], apply_norm(cfg.norm_type, p["ln2"], x), cfg.act)
    return x + h2, {"kv": kv_cache, "ssm": sstate}


# --- xLSTM ------------------------------------------------------------------

def _init_xlstm_group(cfg: ModelConfig):
    """One group = (slstm_every - 1) mLSTM blocks + 1 sLSTM block."""
    d, dtype = cfg.d_model, cfg.jdtype
    n_m = cfg.ssm.slstm_every - 1

    def init(key):
        ks = jax.random.split(key, n_m + 1)
        m_ln = jax.tree_util.tree_map(lambda l: jnp.stack([l] * n_m),
                                      init_norm(cfg.norm_type, d))
        return {"m_ln": m_ln if n_m else None,
                "mlstm": _stack(lambda k: ssm_mod.init_mlstm(k, d, cfg.ssm,
                                                             dtype),
                                ks[0], n_m) if n_m else None,
                "s_ln": init_norm(cfg.norm_type, d),
                "slstm": ssm_mod.init_slstm(ks[-1], d, cfg.ssm, dtype)}
    return init


def _apply_xlstm_group(p, x, cfg: ModelConfig, states=None, decode=False):
    """states: {"m": stacked mLSTM states [n_m, ...], "s": sLSTM state}."""
    n_m = cfg.ssm.slstm_every - 1
    new_m, new_s = None, None
    if n_m:
        def body(carry, inp):
            xc = carry
            lp, ln_p, st = inp
            xin = apply_norm(cfg.norm_type, ln_p, xc)
            if decode:
                y, ns = ssm_mod.mlstm_step(lp, xin, cfg.ssm, st)
            else:
                y, ns = ssm_mod.mlstm_scan(lp, xin, cfg.ssm, state0=st)
            return xc + y, ns
        m_states = (states["m"] if states is not None else
                    jax.tree_util.tree_map(
                        lambda l: jnp.stack([l] * n_m),
                        ssm_mod.init_mlstm_state(x.shape[0], cfg.d_model,
                                                 cfg.ssm)))
        x, new_m = jax.lax.scan(body, x, (p["mlstm"], p["m_ln"], m_states))
    xin = apply_norm(cfg.norm_type, p["s_ln"], x)
    s_state = states["s"] if states is not None else None
    if decode:
        y, new_s = ssm_mod.slstm_step(p["slstm"], xin, cfg.ssm, s_state)
    else:
        y, new_s = ssm_mod.slstm_scan(p["slstm"], xin, cfg.ssm, state0=s_state)
    return x + y, {"m": new_m, "s": new_s}


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 8)
    d, dtype = cfg.d_model, cfg.jdtype
    params: Params = {
        "embed": init_embedding(ks[0], cfg.vocab_size, d, dtype),
        "final_norm": init_norm(cfg.norm_type, d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(ks[1], cfg.vocab_size, d, dtype)

    fam = cfg.family
    if fam in ("dense",):
        if cfg.local_global:
            n_pairs = cfg.n_layers // 2
            params["local_layers"] = _stack(_init_dense_layer(cfg), ks[2], n_pairs)
            params["global_layers"] = _stack(_init_dense_layer(cfg), ks[3], n_pairs)
        else:
            params["layers"] = _stack(_init_dense_layer(cfg), ks[2], cfg.n_layers)
    elif fam == "moe":
        fd = cfg.moe.first_dense_layers
        params["first_layers"] = _stack(_init_moe_layer(cfg, dense_mlp=True),
                                        ks[2], fd)
        params["layers"] = _stack(_init_moe_layer(cfg, dense_mlp=False),
                                  ks[3], cfg.n_layers - fd)
    elif fam == "audio":
        params["enc_layers"] = _stack(
            _init_dense_layer(dataclasses.replace(cfg, causal=False)),
            ks[2], cfg.encoder_layers)
        params["enc_norm"] = init_norm(cfg.norm_type, d)
        params["dec_layers"] = _stack(_init_encdec_layer(cfg), ks[3],
                                      cfg.n_layers)
    elif fam == "vlm":
        k_every = cfg.cross_attn_every
        n_groups = cfg.n_layers // k_every
        params["self_layers"] = _stack(
            lambda k: _stack(_init_dense_layer(cfg), k, k_every - 1),
            ks[2], n_groups)
        params["cross_layers"] = _stack(_init_cross_layer(cfg), ks[3], n_groups)
    elif fam == "hybrid":
        params["layers"] = _stack(_init_hymba_layer(cfg), ks[2], cfg.n_layers)
    elif fam == "ssm":
        n_groups = cfg.n_layers // cfg.ssm.slstm_every
        params["groups"] = _stack(_init_xlstm_group(cfg), ks[2], n_groups)
    else:
        raise ValueError(f"unknown family {fam}")
    return params


def _init_encdec_layer(cfg: ModelConfig):
    """Whisper decoder layer: causal self-attn + cross-attn + MLP."""
    d, dtype = cfg.d_model, cfg.jdtype

    def init(key):
        ks = jax.random.split(key, 3)
        return {"ln1": init_norm(cfg.norm_type, d),
                "attn": init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                       cfg.hd, dtype),
                "ln_x": init_norm(cfg.norm_type, d),
                "xattn": init_attention(ks[1], d, cfg.n_heads, cfg.n_kv_heads,
                                        cfg.hd, dtype),
                "ln2": init_norm(cfg.norm_type, d),
                "mlp": init_mlp(ks[2], d, cfg.d_ff, dtype,
                                gated=cfg.act != "gelu")}
    return init


def _init_cross_layer(cfg: ModelConfig):
    """VLM cross-attention layer (attends to image tokens) + MLP."""
    d, dtype = cfg.d_model, cfg.jdtype

    def init(key):
        ks = jax.random.split(key, 2)
        return {"ln1": init_norm(cfg.norm_type, d),
                "xattn": init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                        cfg.hd, dtype),
                "gate": jnp.zeros((), jnp.float32),
                "ln2": init_norm(cfg.norm_type, d),
                "mlp": init_mlp(ks[1], d, cfg.d_ff, dtype)}
    return init


def _cross_attend(p, x, mem_kv, mem_mask, cfg: ModelConfig, xcfg):
    """Cross-attention of x onto a precomputed (k, v) memory.

    Full-sequence queries use the partitioned-memory exchange (PRISM/Voltage
    over the memory); single-token decode queries use the exact sharded-merge
    (the per-step collective is already output-sized, so compressing it
    further buys nothing — DESIGN.md §4).
    """
    B, N, _ = x.shape
    xin = apply_norm(cfg.norm_type, p["ln1"], x)
    q = (xin @ p["xattn"]["wq"]).reshape(B, N, cfg.n_heads, cfg.hd)
    if N == 1:
        from repro.core.exchange import decode_attention_sharded
        dcfg = (xcfg if xcfg.mode == ExchangeMode.LOCAL
                else xcfg.with_mode(ExchangeMode.VOLTAGE))
        valid = mem_mask.sum(axis=-1).astype(jnp.int32)      # pads are a suffix
        out = decode_attention_sharded(q, mem_kv["k"], mem_kv["v"], valid,
                                       dcfg, logit_softcap=cfg.attn_softcap,
                                       scale=cfg.query_scale)
    else:
        out = exchange_cross_attention(q, mem_kv["k"], mem_kv["v"], mem_mask,
                                       xcfg, logit_softcap=cfg.attn_softcap,
                                       scale=cfg.query_scale)
    out = out.reshape(B, N, cfg.n_heads * cfg.hd) @ p["xattn"]["wo"]
    if "gate" in p:
        out = jnp.tanh(p["gate"]).astype(out.dtype) * out
    return x + out


def _memory_kv(p_attn, mem, cfg: ModelConfig):
    """Project a memory [B, M, D] to (k, v) once (shared by all queries)."""
    B, M, _ = mem.shape
    k = (mem @ p_attn["wk"]).reshape(B, M, cfg.n_kv_heads, cfg.hd)
    v = (mem @ p_attn["wv"]).reshape(B, M, cfg.n_kv_heads, cfg.hd)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def forward_lm(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
               xcfg: ExchangeConfig, last_only: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. batch: {"tokens": [B, N], +family extras}.

    Returns (logits [B, N, V] f32, aux scalar). ``last_only`` unembeds just
    the final position (prefill: a [B, N, V] logits tensor is N× wasted
    HBM — only the next-token distribution is needed).
    """
    tokens = batch["tokens"]
    B, N = tokens.shape
    x = embed(params["embed"], tokens, scale_by_sqrt_d=cfg.embed_scale)
    x = pin_activations(x, xcfg)
    positions = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[None], (B, N))
    aux_total = jnp.zeros((), jnp.float32)
    fam = cfg.family

    if fam == "dense":
        if cfg.local_global:
            def pair(xc, lp):
                x1, _ = _apply_attn_mlp(lp[0], xc, cfg, xcfg,
                                        _attn_spec(cfg, window=cfg.window),
                                        positions)
                x2, _ = _apply_attn_mlp(lp[1], x1, cfg, xcfg, _attn_spec(cfg),
                                        positions)
                return x2, None
            x, _ = jax.lax.scan(jax.checkpoint(pair), x,
                                (params["local_layers"], params["global_layers"]))
        else:
            def body(xc, lp):
                y, _ = _apply_attn_mlp(lp, xc, cfg, xcfg, _attn_spec(cfg),
                                       positions)
                return y, None
            x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])

    elif fam == "moe":
        def first(xc, lp):
            y, a = _apply_moe_layer(lp, xc, cfg, xcfg, positions, True)
            return y, a
        x, _ = jax.lax.scan(jax.checkpoint(first), x, params["first_layers"])

        def body(xc, lp):
            y, a = _apply_moe_layer(lp, xc, cfg, xcfg, positions, False)
            return y, a
        x, auxs = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
        aux_total = aux_total + jnp.sum(auxs)

    elif fam == "audio":
        mem, mem_mask = _encode_audio(params, batch, cfg, xcfg)

        def body(xc, lp):
            h = attention_block(lp["attn"],
                                apply_norm(cfg.norm_type, lp["ln1"], xc),
                                _attn_spec(cfg), xcfg, positions=positions)
            xc = xc + h
            mem_kv = _memory_kv(lp["xattn"], mem, cfg)
            xc = _cross_attend({"ln1": lp["ln_x"], "xattn": lp["xattn"]},
                               xc, mem_kv, mem_mask, cfg, xcfg)
            h2 = apply_mlp(lp["mlp"], apply_norm(cfg.norm_type, lp["ln2"], xc),
                           cfg.act)
            return xc + h2, None
        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["dec_layers"])

    elif fam == "vlm":
        mem, mem_mask = _image_memory(batch, cfg, xcfg)

        def group(xc, lp):
            selfs, crossp = lp

            def inner(xi, sp):
                y, _ = _apply_attn_mlp(sp, xi, cfg, xcfg, _attn_spec(cfg),
                                       positions)
                return y, None
            xc, _ = jax.lax.scan(inner, xc, selfs)
            mem_kv = _memory_kv(crossp["xattn"], mem, cfg)
            xc = _cross_attend(crossp, xc, mem_kv, mem_mask, cfg, xcfg)
            h2 = apply_mlp(crossp["mlp"],
                           apply_norm(cfg.norm_type, crossp["ln2"], xc),
                           cfg.act)
            return xc + h2, None
        x, _ = jax.lax.scan(jax.checkpoint(group), x,
                            (params["self_layers"], params["cross_layers"]))

    elif fam == "hybrid":
        def body(xc, lp):
            y, a = _apply_hymba_layer(lp, xc, cfg, xcfg, positions)
            return y, a
        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])

    elif fam == "ssm":
        def body(xc, gp):
            y, _ = _apply_xlstm_group(gp, xc, cfg)
            return y, None
        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["groups"])

    else:
        raise ValueError(fam)

    if last_only:
        x = x[:, -1:]
    x = pin_activations(apply_norm(cfg.norm_type, params["final_norm"], x),
                        xcfg)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(head, x, final_softcap=cfg.final_softcap)
    return logits, aux_total


def _encode_audio(params, batch, cfg: ModelConfig, xcfg):
    """Whisper encoder over stub frame embeddings [B, M0, D] (padded)."""
    frames = batch["frames"]
    B, M0, _ = frames.shape
    M = pad_len(M0, xcfg.seq_shards, xcfg.L)
    mem = jnp.pad(frames, ((0, 0), (0, M - M0), (0, 0)))
    mem_mask = jnp.broadcast_to(jnp.arange(M)[None] < M0, (B, M))
    pos = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32)[None], (B, M))
    ecfg = dataclasses.replace(cfg, causal=False)

    def body(xc, lp):
        y, _ = _apply_attn_mlp(lp, xc, ecfg, xcfg,
                               _attn_spec(cfg, causal=False), pos)
        return y, None
    mem, _ = jax.lax.scan(jax.checkpoint(body), mem, params["enc_layers"])
    mem = apply_norm(cfg.norm_type, params["enc_norm"], mem)
    return mem, mem_mask


def _image_memory(batch, cfg: ModelConfig, xcfg):
    """Pad stub image-patch embeddings [B, T0, D] for partitioning."""
    img = batch["image_embeds"]
    B, T0, _ = img.shape
    T = pad_len(T0, xcfg.seq_shards, xcfg.L)
    mem = jnp.pad(img, ((0, 0), (0, T - T0), (0, 0)))
    mask = jnp.broadcast_to(jnp.arange(T)[None] < T0, (B, T))
    return mem, mask


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _scan_decode_layers(body_fn, x, params_stack, cache_stack):
    """Layer scan for decode with the stacked cache in the CARRY.

    Scanning the cache as xs with updated ys duplicates every cache buffer
    (input stack + output stack + staging ≈ 3× cache HBM). Carrying it lets
    XLA update the single stacked buffer in place inside the while loop;
    per layer we dynamic-slice one layer's cache out and write it back.

    body_fn(x, layer_params, layer_cache) → (x, new_layer_cache).
    """
    import jax.tree_util as jtu

    def body(carry, lp):
        xc, cache, i = carry
        c = jtu.tree_map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            cache)
        y, nc = body_fn(xc, lp, c)
        cache = jtu.tree_map(
            lambda t, u: jax.lax.dynamic_update_index_in_dim(
                t, u.astype(t.dtype), i, 0), cache, nc)
        return (y, cache, i + 1), None

    (x, cache_stack, _), _ = jax.lax.scan(
        body, (x, cache_stack, jnp.asarray(0, jnp.int32)), params_stack)
    return x, cache_stack


def _zeros_in_memory(tree):
    """Keep a zero-filled cache's zeros.  Made inside a program and filled
    by the prefill's layer scan only up to the prompt, the cache is
    otherwise allocated uninitialized by the TPU compiler (libtpu 0.0.34
    sinks the zero broadcast into the scan), and the positions past the
    prompt come back holding garbage, NaN included."""
    return jax.lax.optimization_barrier(tree)


def init_decode_cache(cfg: ModelConfig, batch: int, seq: int) -> Params:
    """Cache pytree with stacked leading layer/group dims (scan layout)."""
    dtype = cfg.jdtype
    fam = cfg.family

    def kv(n, s):
        c = init_kv_cache(batch, s, cfg.n_kv_heads, cfg.hd, dtype,
                          quant=cfg.kv_quant)
        return _zeros_in_memory(
            jax.tree_util.tree_map(lambda l: jnp.stack([l] * n), c))

    if fam == "dense":
        if cfg.local_global:
            n_pairs = cfg.n_layers // 2
            return {"local": kv(n_pairs, seq), "global": kv(n_pairs, seq)}
        return {"kv": kv(cfg.n_layers, seq)}
    if fam == "moe":
        fd = cfg.moe.first_dense_layers
        if cfg.mla is not None:
            def mlac(n):
                c = mla_mod.init_mla_cache(batch, seq, cfg.mla, dtype)
                return _zeros_in_memory(jax.tree_util.tree_map(
                    lambda l: jnp.stack([l] * n), c))
            return {"first": mlac(fd), "kv": mlac(cfg.n_layers - fd)}
        return {"first": kv(fd, seq), "kv": kv(cfg.n_layers - fd, seq)}
    if fam == "audio":
        return {"kv": kv(cfg.n_layers, seq), "mem_kv": None, "mem_mask": None}
    if fam == "vlm":
        k_every = cfg.cross_attn_every
        n_groups = cfg.n_layers // k_every
        selfs = kv(n_groups, seq)
        selfs = jax.tree_util.tree_map(
            lambda l: l.reshape(n_groups, 1, *l.shape[1:]).repeat(
                k_every - 1, axis=1), selfs)
        return {"self": selfs, "mem_kv": None, "mem_mask": None}
    if fam == "hybrid":
        kvs = kv(cfg.n_layers, seq)
        sst = ssm_mod.init_mamba_state(batch, cfg.d_model, cfg.ssm, dtype)
        sst = jax.tree_util.tree_map(lambda l: jnp.stack([l] * cfg.n_layers), sst)
        return {"kv": kvs, "ssm": sst}
    if fam == "ssm":
        n_groups = cfg.n_layers // cfg.ssm.slstm_every
        n_m = cfg.ssm.slstm_every - 1
        m = ssm_mod.init_mlstm_state(batch, cfg.d_model, cfg.ssm)
        m = jax.tree_util.tree_map(
            lambda l: jnp.stack([jnp.stack([l] * n_m)] * n_groups), m)
        s = ssm_mod.init_slstm_state(batch, cfg.d_model)
        s = jax.tree_util.tree_map(lambda l: jnp.stack([l] * n_groups), s)
        return {"m": m, "s": s}
    raise ValueError(fam)


def decode_step(params: Params, batch: Dict[str, jnp.ndarray], cache: Params,
                cache_index, cfg: ModelConfig, xcfg: ExchangeConfig
                ) -> Tuple[jnp.ndarray, Params]:
    """One-token step. batch: {"tokens": [B, 1], +extras on first call}.

    Returns (logits [B, 1, V], updated cache). ``cache_index`` is the global
    write position (current sequence length).
    """
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x = embed(params["embed"], tokens, scale_by_sqrt_d=cfg.embed_scale)
    fam = cfg.family

    if fam == "dense":
        if cfg.local_global:
            def pair(xc, lps, c):
                lp_l, lp_g = lps
                c_l, c_g = c
                x1, nc_l = _apply_attn_mlp_decode(
                    lp_l, xc, cfg, xcfg, _attn_spec(cfg, window=cfg.window),
                    c_l, cache_index)
                x2, nc_g = _apply_attn_mlp_decode(
                    lp_g, x1, cfg, xcfg, _attn_spec(cfg), c_g, cache_index)
                return x2, (nc_l, nc_g)
            x, (ncl, ncg) = _scan_decode_layers(
                pair, x, (params["local_layers"], params["global_layers"]),
                (cache["local"], cache["global"]))
            new_cache = {"local": ncl, "global": ncg}
        else:
            def body(xc, lp, c):
                return _apply_attn_mlp_decode(lp, xc, cfg, xcfg,
                                              _attn_spec(cfg), c, cache_index)
            x, nkv = _scan_decode_layers(body, x, params["layers"],
                                         cache["kv"])
            new_cache = {"kv": nkv}

    elif fam == "moe":
        def make_body(dense_mlp):
            def body(xc, lp, c):
                if cfg.mla is not None:
                    h, nc = mla_mod.mla_decode(
                        lp["attn"], apply_norm(cfg.norm_type, lp["ln1"], xc),
                        cfg.n_heads, cfg.mla, xcfg, c, cache_index,
                        rope_theta=cfg.rope_theta)
                    xc = xc + h
                    hin = apply_norm(cfg.norm_type, lp["ln2"], xc)
                    if dense_mlp:
                        y = apply_mlp(lp["mlp"], hin, cfg.act)
                    else:
                        y, _ = moe_mod.apply_moe(lp["moe"], hin, cfg.moe, cfg.act)
                    return xc + y, nc
                mlp_fn = ((lambda h: apply_mlp(lp["mlp"], h, cfg.act))
                          if dense_mlp else
                          (lambda h: moe_mod.apply_moe(lp["moe"], h, cfg.moe,
                                                       cfg.act)))
                return _apply_attn_mlp_decode(lp, xc, cfg, xcfg,
                                              _attn_spec(cfg), c, cache_index,
                                              mlp_fn=mlp_fn)
            return body
        x, nfirst = _scan_decode_layers(make_body(True), x,
                                        params["first_layers"],
                                        cache["first"])
        x, nkv = _scan_decode_layers(make_body(False), x, params["layers"],
                                     cache["kv"])
        new_cache = {"first": nfirst, "kv": nkv}

    elif fam == "audio":
        mem_kv, mem_mask = cache["mem_kv"], cache["mem_mask"]

        # mem K/V differ per layer: stacked along the layer axis (read-only
        # xs); the self-attention cache rides the carry (in-place update)
        def body2(xc, lps, c):
            lp, mkv = lps
            h, nc = attention_decode(
                lp["attn"], apply_norm(cfg.norm_type, lp["ln1"], xc),
                _attn_spec(cfg), xcfg, c, cache_index)
            xc = xc + h
            xc = _cross_attend({"ln1": lp["ln_x"], "xattn": lp["xattn"]},
                               xc, mkv, mem_mask, cfg, xcfg)
            h2 = apply_mlp(lp["mlp"],
                           apply_norm(cfg.norm_type, lp["ln2"], xc), cfg.act)
            return xc + h2, nc
        x, nkv = _scan_decode_layers(body2, x,
                                     (params["dec_layers"], mem_kv),
                                     cache["kv"])
        new_cache = {"kv": nkv, "mem_kv": mem_kv, "mem_mask": mem_mask}

    elif fam == "vlm":
        mem_kv, mem_mask = cache["mem_kv"], cache["mem_mask"]

        def group(xc, lps, c):
            selfs, crossp, mkv = lps

            def inner(xi, sp, cc):
                return _apply_attn_mlp_decode(sp, xi, cfg, xcfg,
                                              _attn_spec(cfg), cc, cache_index)
            xc, ncs = _scan_decode_layers(inner, xc, selfs, c)
            xc = _cross_attend(crossp, xc, mkv, mem_mask, cfg, xcfg)
            h2 = apply_mlp(crossp["mlp"],
                           apply_norm(cfg.norm_type, crossp["ln2"], xc),
                           cfg.act)
            return xc + h2, ncs
        x, nself = _scan_decode_layers(
            group, x, (params["self_layers"], params["cross_layers"], mem_kv),
            cache["self"])
        new_cache = {"self": nself, "mem_kv": mem_kv, "mem_mask": mem_mask}

    elif fam == "hybrid":
        def body(xc, lp, c):
            return _apply_hymba_decode(lp, xc, cfg, xcfg, c, cache_index)
        x, new_cache = _scan_decode_layers(body, x, params["layers"], cache)

    elif fam == "ssm":
        def body(xc, gp, st):
            return _apply_xlstm_group(gp, xc, cfg, states=st, decode=True)
        x, new_cache = _scan_decode_layers(body, x, params["groups"], cache)

    else:
        raise ValueError(fam)

    x = apply_norm(cfg.norm_type, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(head, x, final_softcap=cfg.final_softcap)
    return logits, new_cache


def _apply_attn_mlp_decode_paged(p: Params, x, cfg: ModelConfig, xcfg,
                                 spec: AttnSpec, cache, page_table, lengths):
    """Pre-norm block around ``attention_decode_paged`` — the paged twin of
    ``_apply_attn_mlp_decode`` (identical residual/norm/MLP math)."""
    h, new_cache = attention_decode_paged(
        p["attn"], apply_norm(cfg.norm_type, p["ln1"], x), spec, xcfg,
        cache, page_table, lengths)
    if cfg.post_norms:
        h = apply_norm(cfg.norm_type, p["post_attn"], h)
    x = x + h
    hin = apply_norm(cfg.norm_type, p["ln2"], x)
    h2 = apply_mlp(p["mlp"], hin, cfg.act)
    if cfg.post_norms:
        h2 = apply_norm(cfg.norm_type, p["post_mlp"], h2)
    return x + h2, new_cache


def supports_page_pool(cfg: ModelConfig) -> bool:
    """Paged decode covers the plain dense stack: one homogeneous KV cache
    per layer, no sliding-window alternation (gemma local/global needs
    per-page window masks) and no per-slot int8 cache (cold pages quantize
    through the transport codecs instead, in ``repro.serving.pages``)."""
    return (cfg.family == "dense" and not cfg.local_global
            and not cfg.kv_quant)


def init_page_pool(cfg: ModelConfig, n_pages: int, page_size: int) -> Params:
    """Shared paged KV pool: same pytree as ``init_decode_cache`` but the
    (batch, seq) axes become (page, in-page position) — leaves are
    ``[n_layers, n_pages, page_size, Hk, dh]``.  Requests address it through
    per-row page tables; physical rows are interchangeable."""
    if not supports_page_pool(cfg):
        raise ValueError(f"family {cfg.family!r} (local_global="
                         f"{cfg.local_global}, kv_quant={cfg.kv_quant}) "
                         f"has no paged decode path")
    return init_decode_cache(cfg, n_pages, page_size)


def decode_step_paged(params: Params, batch: Dict[str, jnp.ndarray],
                      pool: Params, page_table: jnp.ndarray,
                      lengths: jnp.ndarray, cfg: ModelConfig,
                      xcfg: ExchangeConfig) -> Tuple[jnp.ndarray, Params]:
    """One-token step for every row against the shared paged pool.

    batch: {"tokens": [S, 1]}; ``page_table`` [S, max_pages] int32 maps each
    row's logical blocks to pool pages; ``lengths`` [S] int32 is each row's
    current sequence length (= this step's write position).  Returns
    (logits [S, 1, V], updated pool).
    """
    if not supports_page_pool(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged decode path")
    tokens = batch["tokens"]
    x = embed(params["embed"], tokens, scale_by_sqrt_d=cfg.embed_scale)

    def body(xc, lp, c):
        return _apply_attn_mlp_decode_paged(lp, xc, cfg, xcfg,
                                            _attn_spec(cfg), c,
                                            page_table, lengths)
    x, nkv = _scan_decode_layers(body, x, params["layers"], pool["kv"])
    x = apply_norm(cfg.norm_type, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(head, x, final_softcap=cfg.final_softcap)
    return logits, {"kv": nkv}


# single-pass prefill is defined for the attention-cached families; the
# recurrent families (hybrid mamba conv state, xLSTM) prefill via the
# compiled teacher-forced scan in repro.api.generation instead.
PREFILL_FAMILIES = ("dense", "moe", "audio", "vlm")


def supports_prefill(cfg: ModelConfig) -> bool:
    return cfg.family in PREFILL_FAMILIES


def prefill(params: Params, batch: Dict[str, jnp.ndarray], cache: Params,
            cfg: ModelConfig, xcfg: ExchangeConfig
            ) -> Tuple[jnp.ndarray, Params]:
    """True single-pass prefill: run the whole prompt [B, T0] through
    ``exchange_attention`` ONCE and bulk-write the KV cache for positions
    [0, T0) — replacing T0 sequential one-token decode steps.

    Returns (last-position logits [B, 1, V] f32, primed cache).  For
    audio/vlm the memory slots must be populated first
    (``prefill_memory``).  Distributed exchanges apply their *prefill*
    semantics here: under PRISM the prompt attends through compressed
    segment means (the paper's scheme), which is intentionally not
    identical to T0 exact decode steps.
    """
    if not supports_prefill(cfg):
        raise ValueError(f"family {cfg.family!r} has no single-pass "
                         f"prefill; use the scanned decode fallback "
                         f"(repro.api.generation.prefill_by_decode)")
    tokens = batch["tokens"]
    B, T0 = tokens.shape
    x = embed(params["embed"], tokens, scale_by_sqrt_d=cfg.embed_scale)
    x = pin_activations(x, xcfg)
    positions = jnp.broadcast_to(jnp.arange(T0, dtype=jnp.int32)[None],
                                 (B, T0))
    fam = cfg.family

    if fam == "dense":
        if cfg.local_global:
            def pair(xc, lps, c):
                lp_l, lp_g = lps
                c_l, c_g = c
                x1, nc_l = _apply_attn_mlp_prefill(
                    lp_l, xc, cfg, xcfg, _attn_spec(cfg, window=cfg.window),
                    positions, c_l)
                x2, nc_g = _apply_attn_mlp_prefill(
                    lp_g, x1, cfg, xcfg, _attn_spec(cfg), positions, c_g)
                return x2, (nc_l, nc_g)
            x, (ncl, ncg) = _scan_decode_layers(
                pair, x, (params["local_layers"], params["global_layers"]),
                (cache["local"], cache["global"]))
            new_cache = {"local": ncl, "global": ncg}
        else:
            def body(xc, lp, c):
                return _apply_attn_mlp_prefill(lp, xc, cfg, xcfg,
                                               _attn_spec(cfg), positions, c)
            x, nkv = _scan_decode_layers(body, x, params["layers"],
                                         cache["kv"])
            new_cache = {"kv": nkv}

    elif fam == "moe":
        def make_body(dense_mlp):
            def body(xc, lp, c):
                if cfg.mla is not None:
                    xc = pin_activations(xc, xcfg)
                    h, nc = mla_mod.mla_prefill(
                        lp["attn"], apply_norm(cfg.norm_type, lp["ln1"], xc),
                        cfg.n_heads, cfg.mla, xcfg, c, positions=positions,
                        rope_theta=cfg.rope_theta)
                    xc = xc + h
                    hin = apply_norm(cfg.norm_type, lp["ln2"], xc)
                    if dense_mlp:
                        y = apply_mlp(lp["mlp"], hin, cfg.act)
                    else:
                        y, _ = moe_mod.apply_moe(lp["moe"], hin, cfg.moe,
                                                 cfg.act)
                    return xc + y, nc
                mlp_fn = ((lambda h: apply_mlp(lp["mlp"], h, cfg.act))
                          if dense_mlp else
                          (lambda h: moe_mod.apply_moe(lp["moe"], h, cfg.moe,
                                                       cfg.act)))
                return _apply_attn_mlp_prefill(lp, xc, cfg, xcfg,
                                               _attn_spec(cfg), positions, c,
                                               mlp_fn=mlp_fn)
            return body
        x, nfirst = _scan_decode_layers(make_body(True), x,
                                        params["first_layers"],
                                        cache["first"])
        x, nkv = _scan_decode_layers(make_body(False), x, params["layers"],
                                     cache["kv"])
        new_cache = {"first": nfirst, "kv": nkv}

    elif fam == "audio":
        mem_kv, mem_mask = cache["mem_kv"], cache["mem_mask"]

        def body2(xc, lps, c):
            lp, mkv = lps
            xin = apply_norm(cfg.norm_type, lp["ln1"], xc)
            spec = _attn_spec(cfg)
            q, k, v = project_qkv(lp["attn"], xin, spec, positions)
            nc = prefill_kv_cache(c, k, v)
            from repro.core.exchange import exchange_attention
            h = exchange_attention(q, k, v, xcfg, causal=spec.causal,
                                   logit_softcap=spec.logit_softcap,
                                   scale=spec.scale)
            h = h.reshape(B, T0, spec.n_heads * spec.head_dim) \
                @ lp["attn"]["wo"]
            xc = xc + h
            xc = _cross_attend({"ln1": lp["ln_x"], "xattn": lp["xattn"]},
                               xc, mkv, mem_mask, cfg, xcfg)
            h2 = apply_mlp(lp["mlp"],
                           apply_norm(cfg.norm_type, lp["ln2"], xc), cfg.act)
            return xc + h2, nc
        x, nkv = _scan_decode_layers(body2, x,
                                     (params["dec_layers"], mem_kv),
                                     cache["kv"])
        new_cache = {"kv": nkv, "mem_kv": mem_kv, "mem_mask": mem_mask}

    elif fam == "vlm":
        mem_kv, mem_mask = cache["mem_kv"], cache["mem_mask"]

        def group(xc, lps, c):
            selfs, crossp, mkv = lps

            def inner(xi, sp, cc):
                return _apply_attn_mlp_prefill(sp, xi, cfg, xcfg,
                                               _attn_spec(cfg), positions,
                                               cc)
            xc, ncs = _scan_decode_layers(inner, xc, selfs, c)
            xc = _cross_attend(crossp, xc, mkv, mem_mask, cfg, xcfg)
            h2 = apply_mlp(crossp["mlp"],
                           apply_norm(cfg.norm_type, crossp["ln2"], xc),
                           cfg.act)
            return xc + h2, ncs
        x, nself = _scan_decode_layers(
            group, x, (params["self_layers"], params["cross_layers"], mem_kv),
            cache["self"])
        new_cache = {"self": nself, "mem_kv": mem_kv, "mem_mask": mem_mask}

    else:                                  # pragma: no cover — guarded above
        raise ValueError(fam)

    x = pin_activations(apply_norm(cfg.norm_type, params["final_norm"],
                                   x[:, -1:]), xcfg)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(head, x, final_softcap=cfg.final_softcap)
    return logits, new_cache


def prefill_memory(params: Params, batch: Dict[str, jnp.ndarray],
                   cfg: ModelConfig, xcfg: ExchangeConfig, cache: Params
                   ) -> Params:
    """Populate decode-cache memory slots for enc-dec / VLM families."""
    if cfg.family == "audio":
        mem, mem_mask = _encode_audio(params, batch, cfg, xcfg)
        mem_kv = jax.vmap(lambda lp: _memory_kv(lp["xattn"], mem, cfg),
                          in_axes=0)(params["dec_layers"])
        return {**cache, "mem_kv": mem_kv, "mem_mask": mem_mask}
    if cfg.family == "vlm":
        mem, mem_mask = _image_memory(batch, cfg, xcfg)
        mem_kv = jax.vmap(lambda lp: _memory_kv(lp["xattn"], mem, cfg),
                          in_axes=0)(params["cross_layers"])
        return {**cache, "mem_kv": mem_kv, "mem_mask": mem_mask}
    return cache
