"""Sharding plans: param / activation / cache PartitionSpecs per execution
mode (DESIGN.md §5).

Mesh axes: ``("data", "model")`` single-pod (16×16) or
``("pod", "data", "model")`` multi-pod (2×16×16).

Execution modes map the paper's deployment choices onto the mesh:

* **LOCAL** (paper's single-device inference, generalized): batch shards
  over (pod, data); the model axis does tensor parallelism — attention
  head-sharded where head counts divide, FFN column/row sharded, vocab
  sharded. No sequence partitioning.
* **VOLTAGE / PRISM** (paper's distributed execution): the *sequence*
  shards over the model axis — the paper's position-wise partitions P=16 —
  and attention communicates via full-tensor or Segment-Means all-gather
  inside shard_map. Attention projections are replicated over `model`
  (heads live unsharded inside the manual region); FFN stays
  column/row-sharded over `model`, which under a sequence-sharded
  activation becomes the standard all-gather → FFN → reduce-scatter
  sequence-parallel TP schedule chosen by GSPMD.

FSDP: architectures whose parameters exceed ``FSDP_THRESHOLD_GB`` are
additionally sharded over the batch axes (ZeRO-3; XLA inserts just-in-time
all-gathers). Optimizer state is always sharded over the batch axes where
divisible (ZeRO-1) regardless of size.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.exchange import ExchangeConfig, ExchangeMode

FSDP_THRESHOLD_GB = 4.0

# [in, out] column-parallel mats (output dim is the TP dim in LOCAL mode)
_COL = {"wq", "wk", "wv", "w_up", "w_gate", "w_uq", "patch_embed", "head",
        "w_in", "w_x", "w_bc", "w_dt", "w_if", "w_q", "w_k", "w_v"}
_ROW = {"wo", "w_down", "w_out"}
_ATTN = {"wq", "wk", "wv", "wo"}          # replicated over model when the
                                          # sequence occupies the model axis
_EMBED = {"table"}


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    mesh: Mesh
    mode: ExchangeMode
    batch_axes: Tuple[str, ...]          # axes sharding the batch dim
    tp_axis: str                          # "model"
    seq_axis: Optional[str]               # "model" in distributed modes
    fsdp: bool                            # ZeRO-3 params over batch axes
    L: int = 0                            # PRISM segment means per partition
    decode: bool = False                  # one-token steps: no seq/TP conflict
    train: bool = False

    @property
    def xcfg(self) -> ExchangeConfig:
        n = self.mesh.shape[self.seq_axis] if self.seq_axis else 1
        return ExchangeConfig(self.mode, self.seq_axis, n, L=self.L,
                              batch_axes=tuple(self.batch_axes))

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def make_plan(mesh: Mesh, cfg: ModelConfig, mode: ExchangeMode,
              L: int = 0, train: bool = False,
              decode: bool = False) -> ShardingPlan:
    axes = list(mesh.axis_names)
    tp = "model"
    batch_axes = tuple(a for a in axes if a != tp)
    seq_axis = tp if mode in (ExchangeMode.PRISM, ExchangeMode.VOLTAGE) else None
    nbytes = _param_gb(cfg)
    # Training always shards params (ZeRO-3 over the batch axes): replicated
    # params replicate the f32 optimizer math and its temporaries too.
    # Inference replicates small archs (zero weight comm — paper layout).
    return ShardingPlan(mesh=mesh, mode=mode, batch_axes=batch_axes,
                        tp_axis=tp, seq_axis=seq_axis,
                        fsdp=train or nbytes > FSDP_THRESHOLD_GB, L=L,
                        decode=decode, train=train)


def _param_gb(cfg: ModelConfig) -> float:
    """Analytic parameter-byte estimate (for the FSDP threshold only)."""
    d, f, V, nl = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    per_layer = 4 * d * d + 3 * d * f
    if cfg.moe:
        m = cfg.moe
        per_layer = 4 * d * d + 3 * d * m.d_ff_expert * (m.n_held + m.n_shared)
    total = nl * per_layer + 2 * V * d
    return total * 2 / 1e9


def _divides(n: int, mesh: Mesh, axes) -> bool:
    if not axes:
        return False
    size = int(np.prod([mesh.shape[a] for a in (
        axes if isinstance(axes, tuple) else (axes,))]))
    return n % size == 0


def _fsdp_axes(plan: ShardingPlan, dim: int) -> Any:
    """Batch-axes (pod+data) sharding for a dim if enabled & divisible."""
    if not plan.fsdp:
        return None
    ax = plan.batch_axes if len(plan.batch_axes) > 1 else plan.batch_axes[0]
    return ax if _divides(dim, plan.mesh, ax) else None


def _leaf_spec(path: str, leaf, plan: ShardingPlan, cfg: ModelConfig,
               for_opt: bool = False) -> P:
    """Spec for one (possibly scan-stacked) parameter leaf.

    Layer params carry 1–2 leading *stack* dims (lax.scan layout); rules
    apply to the logical trailing dims and FSDP prefers the stack dim
    (per-layer just-in-time gather — ZeRO-3 granularity) falling back to the
    logical in-dim when the stack size doesn't divide the batch axes.
    """
    shape = leaf.shape
    mesh = plan.mesh
    tp = plan.tp_axis
    name = path.rsplit("/", 1)[-1]
    distributed = plan.seq_axis is not None

    if len(shape) <= 1:
        return P()

    def fsdp_ax(dim_size: int):
        return _fsdp_axes(plan, dim_size)

    # --- embeddings / unembedding (top-level, unstacked [V, D]) ------------
    # Feature dim stays replicated: sharding it leaks a feature-sharded
    # layout into the activations (embedding gather output), which destroys
    # the batch sharding downstream. The vocab dim shards over `model` in
    # LOCAL mode but over `data` in distributed modes — there the sequence
    # owns the model axis, and an unsharded vocab makes the unembed-gradient
    # partials materialize as full [D, V] f32 per device.
    if name in _EMBED:
        v_ax = None
        if not distributed and _divides(shape[0], mesh, tp):
            v_ax = tp
        elif distributed:
            for cand in plan.batch_axes[::-1]:
                if _divides(shape[0], mesh, cand):
                    v_ax = cand
                    break
        d_ax = None
        if for_opt:
            cands = [a for a in (plan.batch_axes + (tp,)) if a != v_ax]
            d_ax = next((a for a in cands if _divides(shape[1], mesh, a)),
                        None)
        elif distributed and not plan.train and _divides(shape[1], mesh, tp):
            # inference: 2-D shard the table — GSPMD lowers a vocab-sharded
            # gather via a table-sized f32 select, so shrink the table shard
            # both ways; pin_activations re-gathers D right after the lookup
            # (one small AG instead of a 3 GB f32 select).
            d_ax = tp
        return P(v_ax, d_ax)

    # --- MoE expert weights: [..., E, D, F] / [..., E, F, D] ----------------
    if "moe/" in path and name in ("w_gate", "w_up", "w_down"):
        stack = len(shape) - 3
        e_ax = tp if _divides(shape[stack], mesh, tp) else None
        inner = fsdp_ax(shape[stack + 1])
        spec = [None] * stack + [e_ax, inner, None]
        return P(*spec)

    # --- MLA up-projections [..., r, H, dh] ---------------------------------
    if name in ("w_uk", "w_uv"):
        stack = len(shape) - 3
        head_ax = (tp if (not distributed
                          and _divides(shape[stack + 1], mesh, tp)) else None)
        r_ax = fsdp_ax(shape[stack]) if (plan.fsdp or for_opt) else None
        return P(*([None] * stack), r_ax, head_ax, None)

    # --- xLSTM sLSTM recurrent [4, H, dh, dh] (unstackable, small) ----------
    if name == "r":
        return P()

    def dense_spec(kind: str):
        """kind: 'col' (out dim TP) | 'row' (in dim TP)."""
        stack = len(shape) - 2
        d_in, d_out = shape[-2], shape[-1]
        tp_ok_out = _divides(d_out, mesh, tp)
        tp_ok_in = _divides(d_in, mesh, tp)
        # TP uses the model axis only in LOCAL mode. In distributed modes the
        # model axis carries the sequence: sharding an activation-adjacent
        # weight dim over it makes GSPMD un-shard the sequence (full-N
        # activations per device) — weights there shard over data only.
        # Decode is the exception: activations are [B, 1, D], so MLP TP over
        # model is conflict-free (the cache owns the seq axis, weights can
        # still use model for their own dims). Attention projections stay
        # off-model (head reshape).
        use_tp = (not distributed) or (plan.decode and name not in _ATTN)
        col_ax = tp if (use_tp and kind == "col" and tp_ok_out) else None
        row_ax = tp if (use_tp and kind == "row" and tp_ok_in) else None
        # FSDP: shard a LOGICAL dim (never the stack dim — lax.scan's
        # dynamic-slice over a sharded stack dim makes GSPMD replicate the
        # whole stacked tensor every iteration).
        spec = [None] * len(shape)
        if kind == "col" and col_ax is not None:
            spec[-1] = col_ax
        if kind == "row" and row_ax is not None:
            spec[-2] = row_ax
        if plan.fsdp or for_opt:
            if kind == "col" and fsdp_ax(d_in) is not None:
                spec[-2] = fsdp_ax(d_in)
            elif kind == "row" and spec[-1] is None and fsdp_ax(d_out) is not None:
                spec[-1] = fsdp_ax(d_out)
            elif spec[-1] is None and fsdp_ax(d_out) is not None:
                spec[-1] = fsdp_ax(d_out)
        if for_opt:
            # optimizer state additionally shards the other logical dim over
            # the model axis (ZeRO-1): the update is elementwise, so the
            # head-reshape / sequence-axis constraints that stop the PARAM
            # from using `model` don't apply to m/v.
            if kind == "col" and spec[-1] is None and tp_ok_out:
                spec[-1] = tp
            elif kind == "row" and spec[-2] is None and tp_ok_in:
                spec[-2] = tp
        return P(*spec)

    if name in _ROW:
        return dense_spec("row")
    if name in _COL or name in _ATTN or len(shape) >= 2:
        return dense_spec("col")
    return P()


def _opt_force_data(spec: P, leaf, plan: ShardingPlan) -> P:
    """ZeRO-1: ensure optimizer state is sharded over the batch axes on some
    dim even when the param itself is replicated."""
    if any(s is not None for s in spec):
        return spec
    shape = leaf.shape
    if len(shape) == 0:
        return P()
    ax = plan.batch_axes if len(plan.batch_axes) > 1 else (
        plan.batch_axes[0] if plan.batch_axes else None)
    if ax is None:
        return spec
    for i, dim in enumerate(shape):
        if _divides(dim, plan.mesh, ax):
            return P(*([None] * i), ax)
    return spec


def _path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


def param_shardings(plan: ShardingPlan, cfg: ModelConfig, params):
    """NamedSharding tree matching an (abstract) param tree."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: plan.named(_leaf_spec(_path_str(p), l, plan, cfg)),
        params)


def opt_state_shardings(plan: ShardingPlan, cfg: ModelConfig, params):
    def spec(p, l):
        s = _leaf_spec(_path_str(p), l, plan, cfg, for_opt=True)
        return plan.named(_opt_force_data(s, l, plan))
    return jax.tree_util.tree_map_with_path(spec, params)


def _batch_ax(plan: ShardingPlan, dim: int):
    """Largest batch-axes group that divides ``dim`` (None if none does)."""
    cands = []
    if len(plan.batch_axes) > 1:
        cands.append(plan.batch_axes)
    cands.extend(plan.batch_axes)
    for c in cands:
        if _divides(dim, plan.mesh, c):
            return c
    return None


def _seq_ax(plan: ShardingPlan, dim: int):
    if plan.seq_axis and _divides(dim, plan.mesh, plan.seq_axis):
        return plan.seq_axis
    return None


def batch_shardings(plan: ShardingPlan, cfg: ModelConfig, specs,
                    kind: str):
    """Shardings for the input batch dict (tokens / labels / frames / ...)."""

    def one(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        bax = _batch_ax(plan, leaf.shape[0])
        if "tokens" in name or "labels" in name:
            if kind == "decode" or nd < 2 or leaf.shape[1] == 1:
                return plan.named(P(bax, None))
            return plan.named(P(bax, _seq_ax(plan, leaf.shape[1])))
        if "frames" in name or "image_embeds" in name:
            # memory: batch over data; memory length stays unsharded here —
            # the forward pads it, then partitions it (pad_len is known only
            # inside the model), so the raw stub input is replicated on seq.
            return plan.named(P(bax, None, None))
        if "images" in name:
            return plan.named(P(bax, None, None, None))
        return plan.named(P(*([bax] + [None] * (nd - 1))))

    return jax.tree_util.tree_map_with_path(one, specs)


def cache_shardings(plan: ShardingPlan, cfg: ModelConfig, cache):
    """Decode-cache shardings: [layers, B, S, ...] — batch over (pod, data),
    sequence over the model axis (flash-decoding merge), SSM states batch-
    sharded only."""

    def one(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if "mem_mask" in name:                     # [B, M]
            return plan.named(P(_batch_ax(plan, leaf.shape[0]),
                                _seq_ax(plan, leaf.shape[1])))
        if "mem_kv" in name:                       # [layers, B, M, Hk, dh]
            return plan.named(P(None, _batch_ax(plan, leaf.shape[1]),
                                _seq_ax(plan, leaf.shape[2]), None, None))
        if any(k in name for k in ("/k", "/v", "c_kv", "k_pe")) and nd >= 3:
            # [layers(, inner), B, S, ...] — S right after batch
            lead = 2 if nd > 5 else 1
            spec = [None] * nd
            spec[lead] = _batch_ax(plan, leaf.shape[lead])
            spec[lead + 1] = _seq_ax(plan, leaf.shape[lead + 1])
            return plan.named(P(*spec))
        # recurrent states: xlstm mLSTM stacks are [groups, n_m, B, ...]
        bdim = 2 if name.startswith("m/") else 1
        spec = [None] * nd
        if nd > bdim:
            spec[bdim] = _batch_ax(plan, leaf.shape[bdim])
        return plan.named(P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache)
