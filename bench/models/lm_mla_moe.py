"""Latent attention with routed experts (DeepSeek-V2-Lite), one chip's
share of an expert-parallel deployment: seeded weights and the plain
float32 reference.

The reference imports nothing of the program.  The benchmark draws the
weights here, on the device, in one jitted call; the serving loop hands
the same arrays to the program in the program's layout
(``program_params``), at the sizes ``program_config`` maps onto the
program's own configuration (the one place that reads the program's
table), and the reference reads them here in its own.

Reference, per DeepSeek-V2 (arXiv:2405.04434) and its published config:
pre-norm decoder blocks with RMSNorm (gain ``1 + g``); multi-head latent
attention in its plain, non-absorbed form: the query projected directly
(no query low-rank), ``[c_kv, k_pe] = h W_dkv``, ``c_kv`` normed, keys
``[c_kv W_uk, k_pe]`` and values ``c_kv W_uv`` per head, full causal
softmax; YaRN rope on the 64 rope columns as published (frequencies
blended between the correction dims of beta_fast and beta_slow, cos/sin
scaled by mscale / mscale_all_dim, the softmax scale by
mscale(mscale_all_dim)^2); layer 0 a SiLU-gated MLP; every other layer a
softmax router over all the router's experts, top-k (renormalised only if
``norm_topk_prob``, times ``routed_scaling_factor``), the part of the
result that the experts held here give (each expert a SiLU-gated MLP;
what experts held elsewhere would add is left out, as in the program),
plus the shared experts; a final RMSNorm and an untied head.  All in
float32 at ``highest`` matmul precision, one sequence and one layer at a
time (each layer's bf16 weights cast inside the layer scan), so it fits
beside the served weights.

``precision="fp8"`` is the control: every matrix product takes both
operands rounded to float8 e4m3 (each scaled to the format's range along
its contracted axes) and accumulates in float32.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.lm_dense import einsum

F32 = jnp.float32


def dims(m: Dict):
    """(layers, hidden, heads, nope, rope, v, latent rank, dense FFN,
    expert FFN, experts held, router outputs, top-k, shared, vocab)."""
    return (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"],
            m["intermediate_size"], m["moe_intermediate_size"],
            m["n_routed_experts"], m["router_outputs"],
            m["num_experts_per_tok"], m["n_shared_experts"], m["vocab_size"])


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` at the sizes the file states, holding
    the file's share of the router's experts."""
    import dataclasses as dc
    from repro.configs import get_config
    m = config["model"]
    (L, D, H, dn, dr, dv, r, F, f, E, R, k, S, V) = dims(m)
    lo = m["experts_held_first"]
    cfg = get_config(config["program_arch"])
    ys = m["rope_scaling"]
    cfg = dc.replace(
        cfg, n_layers=L, d_model=D, n_heads=H, n_kv_heads=H,
        head_dim=dn + dr, d_ff=f, vocab_size=V, rope_theta=m["rope_theta"],
        rope_scaling=dc.replace(
            cfg.rope_scaling, factor=float(ys["factor"]),
            original_max_position_embeddings=int(
                ys["original_max_position_embeddings"]),
            beta_fast=float(ys["beta_fast"]), beta_slow=float(ys["beta_slow"]),
            mscale=float(ys["mscale"]),
            mscale_all_dim=float(ys["mscale_all_dim"])),
        mla=dc.replace(cfg.mla, kv_lora_rank=r, q_lora_rank=m["q_lora_rank"],
                       qk_nope_dim=dn, qk_rope_dim=dr, v_head_dim=dv),
        moe=dc.replace(cfg.moe, n_experts=R, top_k=k, n_shared=S,
                       d_ff_expert=f,
                       first_dense_layers=m["first_k_dense_replace"],
                       d_ff_dense=F, norm_topk_prob=m["norm_topk_prob"],
                       routed_scaling_factor=float(
                           m["routed_scaling_factor"]),
                       held=(lo, lo + E)))
    expect = {"family": "moe", "norm_type": "rmsnorm", "act": "silu",
              "tie_embeddings": m["tie_word_embeddings"], "qkv_bias": False,
              "dtype": m["torch_dtype"], "window": None,
              "attn_softcap": None, "final_softcap": None,
              "embed_scale": False, "post_norms": False}
    wrong = {k: getattr(cfg, k) for k, v in expect.items()
             if getattr(cfg, k) != v}
    if (m["scoring_func"], m["topk_method"], m["moe_layer_freq"],
            m["rope_scaling"]["type"]) != ("softmax", "greedy", 1, "yarn"):
        wrong["routing"] = (m["scoring_func"], m["topk_method"],
                            m["moe_layer_freq"], m["rope_scaling"]["type"])
    if wrong:
        raise SystemExit(f"{config['name']}: the program's config differs "
                         f"from the reference's semantics: {wrong}")
    return cfg


def make_weights(m: Dict, key_int: int):
    """All weights from one seed, on the device, in one jitted call:
    matrices in bf16 (as served), norm gains and the router in float32.
    The expert stacks are drawn a layer at a time, so no float32 copy of
    a whole stack is ever made."""
    (L, D, H, dn, dr, dv, r, F, f, E, R, k, S, V) = dims(m)
    n0 = m["first_k_dense_replace"]
    bf16 = jnp.bfloat16

    def normal(key, shape, std, dtype=bf16):
        return (jax.random.normal(key, shape, F32) * std).astype(dtype)

    def attention(key, n):
        ks = jax.random.split(key, 8)
        return {"attn_norm": normal(ks[0], (n, D), 0.1, F32),
                "w_q": normal(ks[1], (n, D, H * (dn + dr)), D ** -0.5),
                "w_dkv": normal(ks[2], (n, D, r + dr), D ** -0.5),
                "kv_norm": normal(ks[3], (n, r), 0.1, F32),
                "w_uk": normal(ks[4], (n, r, H, dn), r ** -0.5),
                "w_uv": normal(ks[5], (n, r, H, dv), r ** -0.5),
                "wo": normal(ks[6], (n, H * dv, D), (H * dv) ** -0.5),
                "mlp_norm": normal(ks[7], (n, D), 0.1, F32)}

    def per_layer(key, n, shape, std):
        return jax.lax.map(lambda kk: normal(kk, shape, std),
                           jax.random.split(key, n))

    def build(key):
        ks = jax.random.split(key, 16)
        n = L - n0
        return {
            "embed": normal(ks[0], (V, D), 0.02),
            "lm_head": normal(ks[1], (V, D), 0.02),
            "final_norm": normal(ks[2], (D,), 0.1, F32),
            "attn0": attention(ks[3], n0),
            "dense": {"w_gate": normal(ks[4], (n0, D, F), D ** -0.5),
                      "w_up": normal(ks[5], (n0, D, F), D ** -0.5),
                      "w_down": normal(ks[6], (n0, F, D), F ** -0.5)},
            "attn": attention(ks[7], n),
            "moe": {"router": normal(ks[8], (n, D, R), 0.02, F32),
                    "w_gate": per_layer(ks[9], n, (E, D, f), D ** -0.5),
                    "w_up": per_layer(ks[10], n, (E, D, f), D ** -0.5),
                    "w_down": per_layer(ks[11], n, (E, f, D), f ** -0.5),
                    "s_gate": normal(ks[12], (n, D, S * f), D ** -0.5),
                    "s_up": normal(ks[13], (n, D, S * f), D ** -0.5),
                    "s_down": normal(ks[14], (n, S * f, D),
                                     (S * f) ** -0.5)},
        }
    return jax.jit(build)(jax.random.key(key_int))


def program_params(w: Dict) -> Dict:
    """The same arrays in the program's parameter tree (no copies)."""
    def attn(a):
        return {"ln1": {"scale": a["attn_norm"]},
                "attn": {"w_q": a["w_q"], "w_dkv": a["w_dkv"],
                         "kv_norm": {"scale": a["kv_norm"]},
                         "w_uk": a["w_uk"], "w_uv": a["w_uv"], "wo": a["wo"]},
                "ln2": {"scale": a["mlp_norm"]}}
    mo = w["moe"]
    return {
        "embed": {"table": w["embed"]},
        "lm_head": {"table": w["lm_head"]},
        "final_norm": {"scale": w["final_norm"]},
        "first_layers": {**attn(w["attn0"]), "mlp": dict(w["dense"])},
        "layers": {**attn(w["attn"]),
                   "moe": {"router": mo["router"], "w_gate": mo["w_gate"],
                           "w_up": mo["w_up"], "w_down": mo["w_down"],
                           "shared": {"w_gate": mo["s_gate"],
                                      "w_up": mo["s_up"],
                                      "w_down": mo["s_down"]}}},
    }


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(m: Dict):
    """(inverse frequencies [dr/2], cos/sin factor, softmax scale) of the
    published YaRN rope, in DeepSeek-V2's form."""
    ys, dim, base = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]
    factor, orig = ys["factor"], ys["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = inter * (1 - mask) + extra * mask
    cs = (_yarn_mscale(factor, ys["mscale"])
          / _yarn_mscale(factor, ys["mscale_all_dim"]))
    scale = (m["qk_nope_head_dim"] + dim) ** -0.5
    if ys["mscale_all_dim"]:
        scale *= _yarn_mscale(factor, ys["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), cs, scale


def _rope(x, pos, inv, cs):
    """x [T, heads, dr] rotated by half rotation (the departure from
    DeepSeek's interleaved pairs) at integer positions."""
    half = x.shape[-1] // 2
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv)          # [T, half]
    c, s = (jnp.cos(ang) * cs)[:, None], (jnp.sin(ang) * cs)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def hidden_states(w: Dict, m: Dict, tokens, precision: str = "float32"):
    """Final-norm hidden states [T, D] of one causal sequence."""
    (L, D, H, dn, dr, dv, r, F, f, E, R, k, S, V) = dims(m)
    eps = m["rms_norm_eps"]
    lo = m["experts_held_first"]
    inv, cs, scale = yarn(m)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]

    def attention(x, a):
        a = jax.tree_util.tree_map(lambda t: t.astype(F32), a)
        h = _rmsnorm(x, a["attn_norm"], eps)
        q = einsum("td,de->te", h, a["w_q"], precision).reshape(T, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv, cs)],
                            -1)
        ckv = einsum("td,de->te", h, a["w_dkv"], precision)
        c = _rmsnorm(ckv[:, :r], a["kv_norm"], eps)
        k_pe = _rope(ckv[:, None, r:], pos, inv, cs)           # [T, 1, dr]
        k_nope = einsum("tr,rhd->thd", c, a["w_uk"], precision)
        key = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (T, H, dr))],
                              -1)
        v = einsum("tr,rhd->thd", c, a["w_uv"], precision)
        s = einsum("qhd,khd->hqk", q, key, precision) * scale
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = einsum("hqk,khd->qhd", p, v, precision).reshape(T, H * dv)
        x = x + einsum("te,ed->td", o, a["wo"], precision)
        return x, _rmsnorm(x, a["mlp_norm"], eps)

    def mlp(h, g, u, d):
        return einsum("tf,fd->td",
                      jax.nn.silu(einsum("td,df->tf", h, g, precision))
                      * einsum("td,df->tf", h, u, precision), d, precision)

    def dense_layer(x, lw):
        a, fw = lw
        x, h = attention(x, a)
        fw = jax.tree_util.tree_map(lambda t: t.astype(F32), fw)
        return x + mlp(h, fw["w_gate"], fw["w_up"], fw["w_down"]), None

    def moe_layer(x, lw):
        a, mw = lw
        x, h = attention(x, a)
        probs = jax.nn.softmax(
            einsum("td,de->te", h, mw["router"], precision), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        if m["norm_topk_prob"]:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        top_p = top_p * m["routed_scaling_factor"]
        gate = jnp.zeros((T, R), F32).at[pos[:, None], top_i].set(top_p)

        def expert(y, ew):
            g, e = ew
            ew = jax.tree_util.tree_map(lambda t: t.astype(F32), e)
            return y + g[:, None] * mlp(h, ew["w_gate"], ew["w_up"],
                                        ew["w_down"]), None
        held = {n: mw[n] for n in ("w_gate", "w_up", "w_down")}
        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (gate[:, lo:lo + E].T, held))
        sh = jax.tree_util.tree_map(
            lambda t: t.astype(F32),
            {n: mw[n] for n in ("s_gate", "s_up", "s_down")})
        return x + y + mlp(h, sh["s_gate"], sh["s_up"], sh["s_down"]), None

    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(dense_layer, x, (w["attn0"], w["dense"]))
    x, _ = jax.lax.scan(moe_layer, x, (w["attn"], w["moe"]))
    return _rmsnorm(x, w["final_norm"], eps)


def _gaps(w, m, tokens, positions, chosen, precision):
    """At each position: the reference's best logit minus its logit of the
    token chosen there (``chosen``, or under ``precision="fp8"`` the
    control's own first choice)."""
    h = hidden_states(w, m, tokens, "float32")[positions]
    ref = einsum("pd,vd->pv", h, w["lm_head"], "float32")
    if precision != "float32":
        hc = hidden_states(w, m, tokens, precision)[positions]
        chosen = jnp.argmax(einsum("pd,vd->pv", hc, w["lm_head"], precision),
                            axis=-1)
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]


@functools.lru_cache(maxsize=None)
def _gaps_fn(model_json: str, precision: str):
    m = json.loads(model_json)
    return jax.jit(lambda w, t, p, c: _gaps(w, m, t, p, c, precision))


def served_gaps(w: Dict, m: Dict, prompt: np.ndarray, served: np.ndarray,
                bucket: int, n_positions: int,
                precision: str = "float32") -> np.ndarray:
    """Gaps [n] of the served tokens of one request.  The reference runs
    once over prompt + served tokens, padded to ``bucket`` tokens (causal,
    so the padding reaches no position read), and reads ``n_positions``
    positions (fixed, so one program serves every request)."""
    T0, n = len(prompt), len(served)
    seq = np.zeros(bucket, np.int32)
    seq[:T0] = prompt
    seq[T0:T0 + n - 1] = served[:n - 1]
    positions = np.zeros(n_positions, np.int32)
    positions[:n] = np.arange(T0 - 1, T0 - 1 + n)
    chosen = np.zeros(n_positions, np.int32)
    chosen[:n] = served
    fn = _gaps_fn(json.dumps(m, sort_keys=True), precision)
    with jax.default_matmul_precision("highest"):
        out = fn(w, jnp.asarray(seq), jnp.asarray(positions),
                 jnp.asarray(chosen))
    return np.asarray(out)[:n]
