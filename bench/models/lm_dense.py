"""Dense GQA decoder (internlm2-1.8b): seeded weights and the plain
float32 reference.

This module imports nothing of the program.  The benchmark draws the
weights here, on the device, in one jitted call; the serving loop hands
the same arrays to the program in the program's layout
(``program_params``), and the reference reads them here in its own.

Reference: pre-norm decoder blocks, RMSNorm with gain ``1 + g``, rotary
positions by half rotation (theta from the configuration), grouped-query
causal attention (query head ``h`` reads KV head ``h // (H / Hk)``), a
SiLU-gated MLP, a final RMSNorm and an untied output head, all in float32
at ``highest`` matmul precision.  Computed one sequence and one layer at a
time, so it fits beside the served weights.

``precision="fp8"`` is the control: every matrix product takes both
operands rounded to float8 e4m3 (each scaled to the format's range along
its contracted axes) and accumulates in float32.
"""
from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(m: Dict):
    return (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"], m["vocab_size"])


def make_weights(m: Dict, key_int: int):
    """All weights from one seed, on the device, in one jitted call:
    matrices in bf16 (as served), norm gains in float32."""
    L, D, H, Hk, dh, F, V = dims(m)
    bf16 = jnp.bfloat16

    def build(key):
        ks = jax.random.split(key, 12)

        def normal(k, shape, std, dtype=bf16):
            return (jax.random.normal(k, shape, F32) * std).astype(dtype)
        return {
            "embed": normal(ks[0], (V, D), 0.02),
            "lm_head": normal(ks[1], (V, D), 0.02),
            "final_norm": normal(ks[2], (D,), 0.1, F32),
            "attn_norm": normal(ks[3], (L, D), 0.1, F32),
            "mlp_norm": normal(ks[4], (L, D), 0.1, F32),
            "wq": normal(ks[5], (L, D, H * dh), D ** -0.5),
            "wk": normal(ks[6], (L, D, Hk * dh), D ** -0.5),
            "wv": normal(ks[7], (L, D, Hk * dh), D ** -0.5),
            "wo": normal(ks[8], (L, H * dh, D), (H * dh) ** -0.5),
            "w_gate": normal(ks[9], (L, D, F), D ** -0.5),
            "w_up": normal(ks[10], (L, D, F), D ** -0.5),
            "w_down": normal(ks[11], (L, F, D), F ** -0.5),
        }
    return jax.jit(build)(jax.random.key(key_int))


def program_params(w: Dict) -> Dict:
    """The same arrays in the program's parameter tree (no copies)."""
    return {
        "embed": {"table": w["embed"]},
        "lm_head": {"table": w["lm_head"]},
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "ln1": {"scale": w["attn_norm"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "ln2": {"scale": w["mlp_norm"]},
            "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]},
        },
    }


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """``x`` rounded to float8 e4m3, scaled along ``axis`` to its range."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def einsum(spec: str, a, b, precision: str):
    """``jnp.einsum`` in float32, or with both operands rounded to fp8
    (the control)."""
    a, b = a.astype(F32), b.astype(F32)
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        contracted = set(sa) & set(sb) - set(out)
        a = _fp8(a, tuple(i for i, c in enumerate(sa) if c in contracted))
        b = _fp8(b, tuple(i for i, c in enumerate(sb) if c in contracted))
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, pos, theta):
    """x [T, heads, dh] rotated by half rotation at integer positions."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * freq                   # [T, half]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def hidden_states(w: Dict, m: Dict, tokens, precision: str = "float32"):
    """Final-norm hidden states [T, D] of one causal sequence."""
    L, D, H, Hk, dh, F, V = dims(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(F32)

    def layer(x, lw):
        h = _rmsnorm(x, lw["attn_norm"], eps)
        q = einsum("td,de->te", h, lw["wq"], precision).reshape(T, H, dh)
        k = einsum("td,de->te", h, lw["wk"], precision).reshape(T, Hk, dh)
        v = einsum("td,de->te", h, lw["wv"], precision).reshape(T, Hk, dh)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, H // Hk, axis=1)
        v = jnp.repeat(v, H // Hk, axis=1)
        s = einsum("qhd,khd->hqk", q, k, precision) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = einsum("hqk,khd->qhd", p, v, precision).reshape(T, H * dh)
        x = x + einsum("te,ed->td", o, lw["wo"], precision)
        h = _rmsnorm(x, lw["mlp_norm"], eps)
        g = einsum("td,df->tf", h, lw["w_gate"], precision)
        u = einsum("td,df->tf", h, lw["w_up"], precision)
        x = x + einsum("tf,fd->td", jax.nn.silu(g) * u, lw["w_down"],
                       precision)
        return x, None

    layers = {k: w[k] for k in ("attn_norm", "mlp_norm", "wq", "wk", "wv",
                                "wo", "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(layer, x, layers)
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
    out = fn(w, jnp.asarray(seq), jnp.asarray(positions), jnp.asarray(chosen))
    return np.asarray(out)[:n]
