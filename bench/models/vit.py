"""ViT-B/16 classifier: seeded weights and the plain float32 reference,
with the paper's PRISM attention as a second mode.

This module imports nothing of the program.  Reference: 16 x 16 patches
flattened in (row, column, channel) order and embedded, a CLS token and
learned positions, pre-norm blocks (LayerNorm, bidirectional attention,
tanh-GELU MLP), a final LayerNorm and a linear head on the CLS token, all
in float32 at ``highest`` matmul precision.

PRISM (``partitions=P, means=L``): the tokens, padded to a multiple of
P·L, split into P equal partitions; queries of partition p attend to p's
own (unpadded) keys and to the L segment means of every other partition's
keys and values, each mean taken over the segment's real tokens and its
logit raised by log(count), so one mean carries its segment's mass.
``partitions=1`` is exact attention.

``precision="fp8"`` is the control: every matrix product takes both
operands rounded to float8 e4m3 and accumulates in float32.
"""
from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.lm_dense import einsum

F32 = jnp.float32


def make_weights(m: Dict, key_int: int, sharding=None):
    """All weights from one seed, on the device, in one jitted call
    (``sharding``: where every leaf is made, e.g. replicated on a mesh)."""
    D, F, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    N = (m["image_size"] // m["patch_size"]) ** 2 + 1
    P = m["patch_size"] ** 2 * m["num_channels"]
    C = m["num_labels"]
    bf16 = jnp.bfloat16

    def build(key):
        ks = jax.random.split(key, 18)

        def normal(k, shape, std, dtype=bf16, mean=0.0):
            return (mean + jax.random.normal(k, shape, F32) * std
                    ).astype(dtype)
        return {
            "patch_embed": normal(ks[0], (P, D), P ** -0.5),
            "patch_bias": normal(ks[1], (D,), 0.02),
            "cls": normal(ks[2], (1, 1, D), 0.02),
            "pos": normal(ks[3], (1, N, D), 0.02),
            "ln1_scale": normal(ks[4], (L, D), 0.1, F32, 1.0),
            "ln1_bias": normal(ks[5], (L, D), 0.1, F32),
            "wq": normal(ks[6], (L, D, D), D ** -0.5),
            "wk": normal(ks[7], (L, D, D), D ** -0.5),
            "wv": normal(ks[8], (L, D, D), D ** -0.5),
            "wo": normal(ks[9], (L, D, D), D ** -0.5),
            "ln2_scale": normal(ks[10], (L, D), 0.1, F32, 1.0),
            "ln2_bias": normal(ks[11], (L, D), 0.1, F32),
            "w_up": normal(ks[12], (L, D, F), D ** -0.5),
            "w_down": normal(ks[13], (L, F, D), F ** -0.5),
            "final_scale": normal(ks[14], (D,), 0.1, F32, 1.0),
            "final_bias": normal(ks[15], (D,), 0.1, F32),
            "head": normal(ks[16], (D, C), D ** -0.5),
            "head_bias": normal(ks[17], (C,), 0.1),
        }
    return jax.jit(build, out_shardings=sharding)(jax.random.key(key_int))


def program_params(w: Dict) -> Dict:
    """The same arrays in the program's parameter tree (no copies)."""
    return {
        "patch_embed": w["patch_embed"], "patch_bias": w["patch_bias"],
        "cls": w["cls"], "pos": w["pos"],
        "layers": {
            "ln1": {"scale": w["ln1_scale"], "bias": w["ln1_bias"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": {"scale": w["ln2_scale"], "bias": w["ln2_bias"]},
            "mlp": {"w_up": w["w_up"], "w_down": w["w_down"]},
        },
        "final_norm": {"scale": w["final_scale"], "bias": w["final_bias"]},
        "head": w["head"], "head_bias": w["head_bias"],
    }


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _attention(q, k, v, valid, partitions: int, means: int, precision):
    """q, k, v [B, N, H, dh] (N padded); ``valid`` [N] marks real tokens."""
    B, N, H, dh = q.shape
    scale = dh ** -0.5
    if partitions == 1:
        s = einsum("bqhd,bkhd->bhqk", q, k, precision) * scale
        s = jnp.where(valid[None, None, None], s, -jnp.inf)
        return einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision)
    P, L = partitions, means
    Np, seg = N // P, N // P // L
    kp = k.reshape(B, P, L, seg, H, dh)
    vp = v.reshape(B, P, L, seg, H, dh)
    vm = valid.reshape(P, L, seg)
    cnt = vm.sum(-1).astype(F32)                               # [P, L]
    w8 = vm.astype(F32)[None, :, :, :, None, None]
    k_mean = (kp * w8).sum(3) / jnp.maximum(cnt, 1.0)[None, :, :, None, None]
    v_mean = (vp * w8).sum(3) / jnp.maximum(cnt, 1.0)[None, :, :, None, None]
    outs = []
    for p in range(P):
        sl = slice(p * Np, (p + 1) * Np)
        qs, ks, vs = q[:, sl], k[:, sl], v[:, sl]
        s_loc = einsum("bqhd,bkhd->bhqk", qs, ks, precision) * scale
        s_loc = jnp.where(valid[sl][None, None, None], s_loc, -jnp.inf)
        others = [r for r in range(P) if r != p]
        km = k_mean[:, others].reshape(B, -1, H, dh)
        vmn = v_mean[:, others].reshape(B, -1, H, dh)
        c = cnt[jnp.asarray(others)].reshape(-1)
        s_m = einsum("bqhd,bkhd->bhqk", qs, km, precision) * scale
        s_m = jnp.where((c > 0)[None, None, None],
                        s_m + jnp.log(jnp.maximum(c, 1.0)), -jnp.inf)
        a = jax.nn.softmax(jnp.concatenate([s_loc, s_m], -1), -1)
        outs.append(einsum("bhqk,bkhd->bqhd", a[..., :Np], vs, precision)
                    + einsum("bhqk,bkhd->bqhd", a[..., Np:], vmn, precision))
    return jnp.concatenate(outs, axis=1)


def logits(w: Dict, m: Dict, images, partitions: int = 1, means: int = 0,
           precision: str = "float32"):
    """Class logits [B, C] of float32 images [B, 224, 224, 3]."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    ps, eps = m["patch_size"], m["layer_norm_eps"]
    B, g = images.shape[0], m["image_size"] // ps
    x = images.astype(F32).reshape(B, g, ps, g, ps, m["num_channels"])
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, g * g, -1)
    x = einsum("bnp,pd->bnd", x, w["patch_embed"], precision) \
        + w["patch_bias"].astype(F32)
    x = jnp.concatenate([jnp.broadcast_to(w["cls"].astype(F32), (B, 1, D)),
                         x], 1) + w["pos"].astype(F32)
    N0 = x.shape[1]
    quantum = partitions * max(means, 1)
    N = -(-N0 // quantum) * quantum
    x = jnp.pad(x, ((0, 0), (0, N - N0), (0, 0)))
    valid = jnp.arange(N) < N0
    dh = D // H

    def layer(x, lw):
        h = _layernorm(x, lw["ln1_scale"], lw["ln1_bias"], eps)
        q, k, v = (einsum("bnd,de->bne", h, lw[n], precision
                          ).reshape(B, N, H, dh) for n in ("wq", "wk", "wv"))
        o = _attention(q, k, v, valid, partitions, means, precision)
        x = x + einsum("bne,ed->bnd", o.reshape(B, N, D), lw["wo"], precision)
        h = _layernorm(x, lw["ln2_scale"], lw["ln2_bias"], eps)
        u = jax.nn.gelu(einsum("bnd,df->bnf", h, lw["w_up"], precision),
                        approximate=True)
        return x + einsum("bnf,fd->bnd", u, lw["w_down"], precision), None

    layers = {k: w[k] for k in ("ln1_scale", "ln1_bias", "wq", "wk", "wv",
                                "wo", "ln2_scale", "ln2_bias", "w_up",
                                "w_down")}
    x, _ = jax.lax.scan(layer, x, layers)
    cls = _layernorm(x[:, 0], w["final_scale"], w["final_bias"], eps)
    return einsum("bd,dc->bc", cls, w["head"], precision) \
        + w["head_bias"].astype(F32)


@functools.lru_cache(maxsize=None)
def _logits_fn(model_json: str, partitions: int, means: int, precision: str):
    m = json.loads(model_json)
    return jax.jit(lambda w, x: logits(w, m, x, partitions, means, precision))


def reference_logits(w: Dict, m: Dict, images: np.ndarray, partitions: int,
                     means: int, batch: int,
                     precision: str = "float32") -> np.ndarray:
    """Logits of ``images``, run as one zero-padded batch of ``batch``
    (one program per mode, whatever the sampled batch sizes)."""
    n = images.shape[0]
    x = np.zeros((batch,) + images.shape[1:], np.float32)
    x[:n] = images
    fn = _logits_fn(json.dumps(m, sort_keys=True), partitions, means,
                    precision)
    return np.asarray(fn(w, jnp.asarray(x)))[:n]
