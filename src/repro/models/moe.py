"""Mixture-of-Experts FFN (DeepSeek-style: fine-grained routed + shared).

Dropless, and told which experts it holds.  The router scores every one
of its ``n_experts`` outputs (softmax, top-k, optionally renormalised over
the k and scaled by ``routed_scaling_factor``); the layer computes only
the part of the result that its held experts (``MoECfg.held``, a range of
the router's experts) give: each (token, choice) pair routed to a held
expert goes through that expert, the pairs are sorted by expert, and the
three expert matrices run as grouped products over the sorted pairs
(``jax.lax.ragged_dot``).  Pairs routed to experts held elsewhere are
sorted past every group and contribute nothing here.  No capacity, so no
token is ever dropped and a token's result does not depend on what else
is in the batch: a prompt run in one pass routes exactly as its tokens
decoded one at a time.  The shared experts run on every token, once.

With every expert held (``held=None``) this is the whole layer.  Under
expert parallelism each device holds one range and the partial results
add up across devices to the whole layer (the exchange that sums them is
not here: a one-chip deployment runs its share alone).

The load-balance auxiliary loss (Switch-style f·p over all experts) is
returned for training, with the tokens each held expert received.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoECfg
from repro.models.layers import _act, apply_mlp, dense_init, init_mlp

Params = Dict[str, Any]


def init_moe(key, d: int, cfg: MoECfg, dtype) -> Params:
    ks = jax.random.split(key, 5)
    E, f = cfg.n_held, cfg.d_ff_expert
    scale = d ** -0.5
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, jnp.float32, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (E, d, f), jnp.float32)
                   * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, f), jnp.float32)
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, f, d), jnp.float32)
                   * (f ** -0.5)).astype(dtype),
    }
    if cfg.n_shared:
        p["shared"] = init_mlp(ks[4], d, cfg.n_shared * f, dtype)
    return p


def route(router: jnp.ndarray, x: jnp.ndarray, cfg: MoECfg
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x [T, D] → (weights [T, k], experts [T, k], probs [T, E]): softmax
    over all the router's experts, the top k, renormalised over the k if
    ``norm_topk_prob``, times ``routed_scaling_factor``."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p * cfg.routed_scaling_factor, top_i, probs


def apply_moe(params: Params, x: jnp.ndarray, cfg: MoECfg,
              act: str = "silu"
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: [..., D] → (y [..., D], aux_loss scalar, tokens [n_held] int32:
    the (token, choice) pairs each held expert computed)."""
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    T, k = xt.shape[0], cfg.top_k
    lo, hi = cfg.held_range
    n_held = hi - lo
    top_p, top_i, probs = route(params["router"], xt, cfg)

    # (token, choice) pairs sorted by held expert; pairs routed elsewhere
    # take the id n_held and sort past every group
    e = top_i.reshape(-1)
    held = (e >= lo) & (e < hi)
    local = jnp.where(held, e - lo, n_held)
    order = jnp.argsort(local, stable=True)
    tok = order // k
    tokens = jnp.bincount(local, length=n_held + 1)[:n_held].astype(jnp.int32)
    xs = xt[tok]                                               # [T·k, D]
    h = _act(jax.lax.ragged_dot(xs, params["w_gate"], tokens), act) \
        * jax.lax.ragged_dot(xs, params["w_up"], tokens)
    ys = jax.lax.ragged_dot(h, params["w_down"], tokens)       # [T·k, D]
    w = jnp.where(held, top_p.reshape(-1), 0.0)[order]
    y = jnp.zeros_like(xt).at[tok].add((ys * w[:, None]).astype(xt.dtype))

    if cfg.n_shared and "shared" in params:
        y = y + apply_mlp(params["shared"], xt, act)

    # Switch-style load balance over all experts: E * Σ_e f_e · p_e
    E = cfg.n_experts
    frac = jnp.bincount(e, length=E) / T                       # f_e [E]
    aux = cfg.router_aux_weight * E * jnp.sum(frac * probs.mean(axis=0))
    return y.reshape(shape), aux, tokens
