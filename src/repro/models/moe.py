"""Mixture-of-Experts FFN (DeepSeek-style: fine-grained routed + shared).

Dispatch is the capacity-based einsum formulation (MaxText-style) because it
shards cleanly under GSPMD: the dispatch tensor ``[G, S, E, C]`` carries the
``G`` (batch-group) dim on the data axis and the ``E`` (expert) dim on the
model axis, so the big intermediates ``[G, E, C, ...]`` are 2-D sharded and
the expert matmuls are fully local; the only collective is the combine-side
reduction over E (one all-reduce / reduce-scatter per MoE layer).

Router: softmax over routed experts, top-k, probabilities renormalized over
the selected k (DeepSeek convention); shared experts always execute. The
load-balance auxiliary loss (Switch-style f·p) is returned for training.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoECfg
from repro.models.layers import _act, dense_init, init_mlp, apply_mlp

Params = Dict[str, Any]


def expert_capacity(tokens_per_group: int, cfg: MoECfg) -> int:
    c = int(tokens_per_group * cfg.top_k * cfg.capacity_factor // cfg.n_experts)
    return max(c, 1)


def init_moe(key, d: int, cfg: MoECfg, dtype) -> Params:
    ks = jax.random.split(key, 5)
    E, f = cfg.n_experts, cfg.d_ff_expert
    scale = d ** -0.5
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32, scale=0.02),
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


GROUP_TOKENS = 4096      # re-group long sequences so capacity (∝S) stays sane


def _pin_expert(t: jnp.ndarray) -> jnp.ndarray:
    """Pin dim 1 (the expert dim of [G, E, C, ...]) to the `model` axis.

    In sequence-distributed modes GSPMD sometimes resolves the expert
    einsums by REPLICATING the expert weight stack (f32!) instead of
    keeping E sharded — 10 GB/device for DeepSeek-V2. Pinning the
    activation side forces the expert-parallel schedule."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if (mesh.empty or "model" not in mesh.axis_names
                or t.shape[1] % mesh.shape["model"]):
            return t
        from jax.sharding import PartitionSpec as P
        U = P.UNCONSTRAINED
        return jax.lax.with_sharding_constraint(
            t, P(U, "model", *([U] * (t.ndim - 2))))
    except (ValueError, RuntimeError, AttributeError, TypeError):
        return t


def apply_moe(params: Params, x: jnp.ndarray, cfg: MoECfg,
              act: str = "silu") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [G, S, D] → (y: [G, S, D], aux_loss scalar).

    Long sequences are re-grouped to ~GROUP_TOKENS tokens per group: the
    dispatch tensors scale as [G, S, E, C] with C ∝ S, so a 32k sequence in
    one group costs 64× the HBM of eight 4k groups."""
    G0, S0, D0 = x.shape
    if S0 > GROUP_TOKENS and S0 % GROUP_TOKENS == 0:
        f = S0 // GROUP_TOKENS
        y, aux = apply_moe(params,
                           x.reshape(G0 * f, GROUP_TOKENS, D0), cfg, act)
        return y.reshape(G0, S0, D0), aux
    G, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = expert_capacity(S, cfg)

    logits = (x.astype(jnp.float32) @ params["router"])      # [G, S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                   # [G, S, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity buffer
    sel = jax.nn.one_hot(top_i, E, dtype=jnp.float32)        # [G, S, k, E]
    mask = sel.reshape(G, S * k, E)
    pos = (jnp.cumsum(mask, axis=1) - 1.0) * mask            # [G, S*k, E]
    pos = pos.reshape(G, S, k, E)
    fits = (pos < C) & (sel > 0)

    # dispatch / combine tensors — [G, S, E, C]; E goes on the model axis
    oh_pos = jax.nn.one_hot(pos.max(-1), C, dtype=jnp.float32)   # [G, S, k, C]
    disp = jnp.einsum("gske,gskc->gsec", sel * fits, oh_pos)
    comb = jnp.einsum("gske,gskc->gsec", sel * fits * top_p[..., None], oh_pos)

    xe = _pin_expert(jnp.einsum("gsec,gsd->gecd", disp.astype(x.dtype), x))
    h = _pin_expert(jnp.einsum("gecd,edf->gecf", xe, params["w_gate"]))
    u = _pin_expert(jnp.einsum("gecd,edf->gecf", xe, params["w_up"]))
    h = _act(h, act) * u
    ye = _pin_expert(jnp.einsum("gecf,efd->gecd", h, params["w_down"]))
    y = jnp.einsum("gsec,gecd->gsd", comb.astype(x.dtype), ye)   # [G,S,D]

    if cfg.n_shared and "shared" in params:
        y = y + apply_mlp(params["shared"], x, act)

    # Switch-style load balance: E * Σ_e f_e · p_e
    frac = sel.sum(axis=2).mean(axis=(0, 1))                     # f_e [E]
    mean_p = probs.mean(axis=(0, 1))                             # p_e [E]
    aux = cfg.router_aux_weight * E * jnp.sum(frac * mean_p)
    return y, aux
