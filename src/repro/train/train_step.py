"""Loss and train-step builders (pjit-ready, donated, remat inside models).

The forward already scans layers under ``jax.checkpoint``; the step adds
cross-entropy over the (possibly vocab-sharded) logits, MoE aux losses, and
the AdamW update. Gradient compression over the slow (DCN/pod) axis —
the paper's Segment-Means idea applied to training comms — is an optional
hook (``grad_compress``): gradients are reduced normally over the fast axes
by GSPMD, while the hook row-compresses what crosses pods.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.exchange import ExchangeConfig
from repro.models import registry
from repro.train.optimizer import OptConfig, adamw_init, adamw_update


def _pin_vocab(t: jnp.ndarray, xcfg: ExchangeConfig) -> jnp.ndarray:
    """Pin the trailing vocab dim of [B, N, V] to the axis the embedding
    tables use in distributed modes (`data` — see sharding/specs.py): the
    one-hot iota otherwise materializes unsharded-V and drags the logits,
    their cotangent, and the [D, V] table-grad partials to full V."""
    if xcfg.seq_axis is None or not xcfg.batch_axes:
        return t
    try:
        from jax.sharding import PartitionSpec as P
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return t
        vax = next((a for a in xcfg.batch_axes[::-1]
                    if a in mesh.axis_names
                    and t.shape[-1] % mesh.shape[a] == 0), None)
        if vax is None:
            return t
        # keep the batch dim sharded on the remaining batch axes — pinning
        # only V lets propagation fall back to batch-replicated logits
        rem = tuple(a for a in xcfg.batch_axes
                    if a in mesh.axis_names and a != vax)
        bsz = 1
        for a in rem:
            bsz *= mesh.shape[a]
        b_spec = rem if (rem and t.shape[0] % bsz == 0) else P.UNCONSTRAINED
        spec = P(b_spec, *([P.UNCONSTRAINED] * (t.ndim - 2)), vax)
        return jax.lax.with_sharding_constraint(t, spec)
    except (ValueError, RuntimeError, AttributeError, TypeError):
        return t


def loss_fn(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            xcfg: ExchangeConfig):
    """Next-token cross-entropy (causal LMs) in f32 with z-loss."""
    logits, aux = registry.forward_fn(cfg)(params, batch, xcfg)
    labels = batch["labels"]
    logits = _pin_vocab(logits, xcfg)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # one-hot contraction instead of take_along_axis: reduces over the vocab
    # dim with a partial-sum (+psum when V is sharded) under GSPMD instead of
    # forcing a replicating gather.
    onehot = _pin_vocab(jax.nn.one_hot(labels, logits.shape[-1],
                                       dtype=logits.dtype), xcfg)
    gold = jnp.einsum("bnv,bnv->bn", logits, onehot)
    nll = (logz - gold).mean()
    zloss = 1e-4 * jnp.square(logz).mean()
    return nll + zloss + aux, {"nll": nll, "aux": aux}


def build_train_step(cfg: ModelConfig, xcfg: ExchangeConfig,
                     opt_cfg: Optional[OptConfig] = None,
                     grad_accum: int = 1,
                     acc_shardings=None,
                     acc_dtype=jnp.float32) -> Callable:
    """(params, opt_state, batch) → (params, opt_state, metrics).

    ``grad_accum`` > 1 splits the global batch into microbatches scanned
    sequentially with an f32 gradient accumulator — the standard
    memory/throughput trade at large batch: live activations shrink by the
    accumulation factor while keeping the global batch size.
    ``acc_shardings`` (a params-shaped tree of shardings, normally the ZeRO-1
    optimizer-state specs) keeps the f32 accumulator maximally sharded.
    """
    opt_cfg = opt_cfg or OptConfig()

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, xcfg), has_aux=True)(params)

    def pin_acc(tree):
        if acc_shardings is None:
            return tree
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, acc_shardings)

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            (loss, parts), grads = grads_of(params, batch)
        else:
            micro = jax.tree_util.tree_map(
                lambda t: t.reshape(grad_accum, t.shape[0] // grad_accum,
                                    *t.shape[1:]), batch)

            def mb(acc, mbatch):
                (l, parts), g = grads_of(params, mbatch)
                acc = pin_acc(jax.tree_util.tree_map(
                    lambda a, gi: a + gi.astype(acc_dtype), acc, g))
                return acc, (l, parts)

            zeros = pin_acc(jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params))
            gacc, (ls, partss) = jax.lax.scan(mb, zeros, micro)
            # keep acc_dtype here: adamw casts per-leaf (transient), a
            # whole-tree astype would materialize a full f32 copy
            grads = jax.tree_util.tree_map(lambda a: a / grad_accum, gacc)
            loss = ls.mean()
            parts = jax.tree_util.tree_map(lambda t: t.mean(), partss)
        new_params, new_state, om = adamw_update(grads, opt_state, params,
                                                 opt_cfg)
        metrics = {"loss": loss, **parts, **om}
        return new_params, new_state, metrics

    return train_step


def build_eval_step(cfg: ModelConfig, xcfg: ExchangeConfig) -> Callable:
    def eval_step(params, batch):
        loss, parts = loss_fn(params, batch, cfg, xcfg)
        return {"loss": loss, **parts}
    return eval_step
