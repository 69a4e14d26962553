"""Distributed exchange strategies: LOCAL / VOLTAGE / PRISM.

This is the paper's communication layer mapped onto JAX-native constructs:
``torch.distributed`` AllGather over GLOO  →  ``jax.lax.all_gather`` over a
named mesh axis inside ``jax.shard_map`` (manual over the *sequence* axis
only; every other mesh axis — `model` TP, `pod`/`data` batch — stays under
GSPMD auto-sharding).

Per Transformer block and device p:
  * VOLTAGE  — one all_gather of the full projected K/V:
               (P-1)/P · N · D received elements per device.
  * PRISM    — one all_gather of L projected segment means per partition:
               (P-1) · L · D received elements — smaller by the compression
               rate CR = N/(L·P); scaling-aware softmax consumes them.
  * LOCAL    — no sequence sharding; attention is ordinary full attention.

Decode-time analogue: the KV cache is sequence-sharded and partial attention
results merge with a numerically-stable log-sum-exp reduction (flash-decoding
style `psum`) — position-wise partitioning for autoregressive steps.
"""
from __future__ import annotations

import dataclasses
import enum
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.prism_attention import (
    NEG_INF,
    _expand_kv,
    _grouped_scores,
    _grouped_values,
    _softcap,
    reference_attention,
)
from repro.kernels import dispatch as kdsp


def all_gather_grad_safe(x: jnp.ndarray, axis_name: str, *, axis: int = 0,
                         tiled: bool = False) -> jnp.ndarray:
    """``jax.lax.all_gather`` whose backward reduce-scatters in f32.

    Rationale: XLA-CPU's AllReducePromotion pass crashes on bf16
    reduce-scatter reducers that carry layout copies ("Invalid binary
    instruction opcode copy"). Doing the cotangent reduce-scatter in f32
    sidesteps the promotion pass entirely; it is numerically a strict
    improvement and on TPU costs one extra cast pair. The forward collective
    is unchanged (bf16 wire bytes — what the roofline counts).
    """
    dtype = x.dtype

    @jax.custom_vjp
    def ag(v):
        return jax.lax.all_gather(v, axis_name, axis=axis, tiled=tiled)

    def fwd(v):
        return ag(v), None

    def bwd(_, ct):
        ct32 = ct.astype(jnp.float32)
        out = jax.lax.psum_scatter(ct32, axis_name, scatter_dimension=axis,
                                   tiled=tiled)
        return (out.astype(dtype),)

    ag.defvjp(fwd, bwd)
    return ag(x)


class ExchangeMode(str, enum.Enum):
    LOCAL = "local"          # no sequence partitioning (single-device analogue)
    VOLTAGE = "voltage"      # full-tensor exchange (Hu & Li, ICDCS'24)
    PRISM = "prism"          # Segment Means exchange + scaling-aware softmax
    PRISM_SIM = "prism_sim"  # PRISM math on unpartitioned tensors (training /
                             # finetuning / single-host validation)


@dataclass(frozen=True)
class ExchangeConfig:
    """How attention communicates across the sequence-partition axis."""
    mode: ExchangeMode = ExchangeMode.LOCAL
    seq_axis: Optional[str] = None   # mesh axis carrying sequence partitions
    seq_shards: int = 1              # P — number of sequence partitions
    L: int = 0                       # segment means per partition (PRISM)
    batch_axes: tuple = ()           # mesh axes sharding the batch dim
    strategy: Optional[str] = None   # registry name when it differs from the
                                     # mode (custom strategies reusing a
                                     # built-in ExchangeMode); None → mode
    codec: str = ""                  # repro.transport codec; "" = the
                                     # strategy's default (segment_means
                                     # for PRISM)
    codec_param: int = 0             # codec knob (quant tile / topk k)
    overlap_chunks: int = 0          # >0: ring exchange with this many
                                     # ppermute chunks per block transfer
                                     # (compute/comm overlap); 0 = gather

    def with_mode(self, mode: ExchangeMode) -> "ExchangeConfig":
        return dataclasses.replace(self, mode=mode, strategy=None)


def pin_activations(x: jnp.ndarray, cfg: ExchangeConfig) -> jnp.ndarray:
    """Pin [B, N, D...] activations to (batch over data axes, sequence over
    the partition axis, features replicated). Re-asserted at block
    boundaries so GSPMD never drifts into batch-replicated layouts."""
    if x.ndim < 2 or (not cfg.batch_axes and cfg.seq_axis is None):
        return x
    mesh = _current_mesh(cfg)
    if mesh is None:
        return x
    bsize = 1
    for a in cfg.batch_axes:
        bsize *= mesh.shape[a]
    b_spec = (cfg.batch_axes if (cfg.batch_axes and x.shape[0] % bsize == 0)
              else P.UNCONSTRAINED)
    seq_ok = (cfg.seq_axis is not None and x.shape[1] > 1 and
              x.shape[1] % mesh.shape[cfg.seq_axis] == 0)
    s_spec = cfg.seq_axis if seq_ok else P.UNCONSTRAINED
    spec = P(b_spec, s_spec, *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, spec)


def _current_mesh(cfg: ExchangeConfig):
    """The mesh in context, or None when there is none (single-host use).
    A mesh that lacks an axis ``cfg`` names is a configuration error."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    named = tuple(cfg.batch_axes) + ((cfg.seq_axis,) if cfg.seq_axis else ())
    missing = [a for a in named if a not in mesh.axis_names]
    if missing:
        raise ValueError(f"mesh axes {mesh.axis_names} lack {missing}, "
                         f"which the exchange config names")
    return mesh


def exchange_attention(
    q: jnp.ndarray,   # [B, N, H, dh]  (N sharded over cfg.seq_axis unless LOCAL)
    k: jnp.ndarray,   # [B, N, Hk, dh]
    v: jnp.ndarray,   # [B, N, Hk, dh]
    cfg: ExchangeConfig,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    kv_mask: Optional[jnp.ndarray] = None,  # [B, N] bool; False → padding
) -> jnp.ndarray:
    """Attention with the configured cross-partition exchange.

    Dispatches through the ``repro.api.strategies`` registry — each registered
    ``ExchangeStrategy`` binds one of the ``*_prefill_attention`` functions
    below. Returns [B, N, H, dh] with the same sequence sharding as inputs.
    """
    from repro.api.strategies import get_strategy
    try:
        strategy = get_strategy(cfg.strategy or cfg.mode.value)
    except KeyError as e:                  # preserve the old contract
        raise ValueError(f"unknown exchange mode {cfg.mode}") from e
    return strategy.prefill_attention(
        q, k, v, cfg, causal=causal, window=window,
        logit_softcap=logit_softcap, scale=scale, kv_mask=kv_mask)


def local_prefill_attention(q, k, v, cfg, *, causal=False, window=None,
                            logit_softcap=None, scale=None, kv_mask=None):
    """No sequence partitioning: ordinary full attention (chunked above a
    memory threshold)."""
    B, Nq, H = q.shape[0], q.shape[1], q.shape[2]
    if B * H * Nq * k.shape[1] * 4 > 0.5e9:
        from repro.core.prism_attention import chunked_reference_attention
        return chunked_reference_attention(
            q, k, v, causal=causal, window=window,
            logit_softcap=logit_softcap, scale=scale, kv_mask=kv_mask)
    return reference_attention(
        q, k, v, causal=causal, window=window,
        logit_softcap=logit_softcap, scale=scale, kv_mask=kv_mask)


def prism_sim_prefill_attention(q, k, v, cfg, *, causal=False, window=None,
                                logit_softcap=None, scale=None, kv_mask=None):
    """PRISM math on unpartitioned tensors (training / single-host)."""
    from repro.core.partition import simulate_prism_attention
    if window is not None:
        raise NotImplementedError("PRISM_SIM with sliding window")
    return simulate_prism_attention(
        q, k, v, cfg.seq_shards, cfg.L, causal=causal,
        logit_softcap=logit_softcap, scale=scale, kv_mask=kv_mask)


def voltage_prefill_attention(q, k, v, cfg, *, causal=False, window=None,
                              logit_softcap=None, scale=None, kv_mask=None):
    """Full-tensor K/V all-gather (the paper's Voltage baseline).

    With ``cfg.overlap_chunks > 0`` (and no sliding window) the exchange
    runs through the chunked ring executor instead: ``ppermute`` block
    transfers double-buffered under per-block attention compute."""
    if cfg.overlap_chunks > 0 and window is None:
        from repro.transport.executor import ring_prefill_attention
        return ring_prefill_attention(q, k, v, cfg, causal=causal,
                                      logit_softcap=logit_softcap,
                                      scale=scale, kv_mask=kv_mask)
    axis = cfg.seq_axis
    if kv_mask is None:
        kv_mask = jnp.ones(k.shape[:2], dtype=bool)
    # Pin the projections to (batch-propagated, seq-sharded, replicated
    # heads): without this, GSPMD sometimes picks a partial head sharding
    # (e.g. 8-way on 40 heads) for the QKV matmuls and then involuntarily
    # replicates the stacked scan weights to reshard — catastrophic.
    q, k, v = (_pin_seq_sharding(t, axis) for t in (q, k, v))

    def volt(qs, ks, vs, ms):
        p = jax.lax.axis_index(axis)
        Np = qs.shape[1]
        # full-tensor exchange: the paper's Voltage baseline
        kg = all_gather_grad_safe(ks, axis, axis=1, tiled=True)
        vg = all_gather_grad_safe(vs, axis, axis=1, tiled=True)
        mg = jax.lax.all_gather(ms, axis, axis=1, tiled=True)  # bool: no grad
        from repro.core.prism_attention import chunked_reference_attention
        return chunked_reference_attention(
            qs, kg, vg, causal=causal, q_offset=p * Np,
            window=window, logit_softcap=logit_softcap, scale=scale,
            kv_mask=mg)
    bax = _manual_batch_axes(q.shape[0], cfg)
    return _seq_shard_map(volt, axis, n_masks=1, batch_axes=bax)(
        q, k, v, kv_mask)


def prism_prefill_attention(q, k, v, cfg, *, causal=False, window=None,
                            logit_softcap=None, scale=None, kv_mask=None):
    """Segment-Means exchange + scaling-aware softmax (the paper's PRISM)."""
    axis = cfg.seq_axis
    Pn = cfg.seq_shards
    had_mask = kv_mask is not None      # no mask → unmasked segment means
    if kv_mask is None:                 # (kernel-eligible) and exact log(seg)
        kv_mask = jnp.ones(k.shape[:2], dtype=bool)
    q, k, v = (_pin_seq_sharding(t, axis) for t in (q, k, v))

    L = cfg.L
    if window is not None:
        # Windowed layers: segment means of far context are invisible
        # under the window anyway, so exchange only the HALO — the
        # ceil(window / shard_len) preceding shards, fetched by
        # collective_permute — instead of a full gather. Comm drops from
        # (P-1)/P*N*D to n_halo/P*N*D per device.
        Np_g = q.shape[1] // Pn
        n_halo = min(-(-window // max(Np_g, 1)), Pn - 1)
        if causal and n_halo < Pn - 1:
            def halo(qs, ks, vs, ms):
                p = jax.lax.axis_index(axis)
                Np = qs.shape[1]
                parts_k, parts_v = [], []
                for sft in range(n_halo, 0, -1):
                    perm = [(i, i + sft) for i in range(Pn - sft)]
                    parts_k.append(jax.lax.ppermute(ks, axis, perm))
                    parts_v.append(jax.lax.ppermute(vs, axis, perm))
                kg = jnp.concatenate(parts_k + [ks], axis=1)
                vg = jnp.concatenate(parts_v + [vs], axis=1)
                base = (p - n_halo) * Np
                gpos = base + jnp.arange((n_halo + 1) * Np)
                valid = (gpos >= 0)[None, :]
                from repro.core.prism_attention import (
                    chunked_reference_attention)
                return chunked_reference_attention(
                    qs, kg, vg, causal=True, q_offset=n_halo * Np,
                    window=window, logit_softcap=logit_softcap,
                    scale=scale,
                    kv_mask=jnp.broadcast_to(
                        valid, (qs.shape[0], gpos.shape[0])))
            bax = _manual_batch_axes(q.shape[0], cfg)
            return _seq_shard_map(halo, axis, n_masks=1,
                                  batch_axes=bax)(q, k, v, kv_mask)
        return exchange_attention(
            q, k, v, cfg.with_mode(ExchangeMode.VOLTAGE), causal=causal,
            window=window, logit_softcap=logit_softcap, scale=scale,
            kv_mask=kv_mask)

    def prism(qs, ks, vs, ms):
        p = jax.lax.axis_index(axis)
        Np = qs.shape[1]
        seg = Np // L
        # L projected segment means per partition (linearity: no
        # re-projection of remote features — scaling-aware reformulation)
        if had_mask:
            km, cnt = kdsp.segment_means_masked(ks, L, ms, axis=1)
            vm, _ = kdsp.segment_means_masked(vs, L, ms, axis=1)
            cnt_all = jnp.moveaxis(jax.lax.all_gather(cnt, axis), 0, 1)
        else:
            km = kdsp.segment_means(ks, L, axis=1)    # [B, L, Hk, dh]
            vm = kdsp.segment_means(vs, L, axis=1)
            cnt_all = None                # exact log(seg) scaling bias
        km_all = all_gather_grad_safe(km, axis)       # [P, B, L, Hk, dh]
        vm_all = all_gather_grad_safe(vm, axis)
        km_all = jnp.moveaxis(km_all, 0, 1)         # [B, P, L, Hk, dh]
        vm_all = jnp.moveaxis(vm_all, 0, 1)
        return kdsp.prism_attention(qs, ks, vs, km_all, vm_all, p, seg,
                                    causal=causal,
                                    logit_softcap=logit_softcap,
                                    scale=scale,
                                    kv_mask=ms if had_mask else None,
                                    mean_counts=cnt_all)
    bax = _manual_batch_axes(q.shape[0], cfg)
    return _seq_shard_map(prism, axis, n_masks=1, batch_axes=bax)(
        q, k, v, kv_mask)



def _pin_seq_sharding(t: jnp.ndarray, axis: str) -> jnp.ndarray:
    """with_sharding_constraint: dim1 (sequence) on ``axis``, dim0 (batch)
    left to propagation, all trailing dims replicated.  No-op without a
    mesh (single-host tests); raises if the mesh has no ``axis``."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return t
    if axis not in mesh.axis_names:
        raise ValueError(f"sequence axis {axis!r} is not in the mesh "
                         f"{mesh.axis_names}")
    spec = P(P.UNCONSTRAINED, axis, *([None] * (t.ndim - 2)))
    return jax.lax.with_sharding_constraint(t, spec)


def _manual_batch_axes(batch: int, cfg: ExchangeConfig):
    """Batch axes to make manual in the exchange shard_map (device-local
    view = the paper's per-device partition).  Empty without batch axes,
    without a mesh, or when the batch does not divide over them (the
    exchange then sees the whole batch on every device, e.g. global batch 1
    on a data axis); raises when the mesh lacks one of them."""
    if not cfg.batch_axes:
        return ()
    mesh = _current_mesh(cfg)
    if mesh is None:
        return ()
    size = 1
    for a in cfg.batch_axes:
        size *= mesh.shape[a]
    return tuple(cfg.batch_axes) if batch % size == 0 else ()


def _seq_shard_map(fn, axis: str, n_masks: int = 0, batch_axes=()):
    """shard_map wrapper: manual over the sequence axis (+ batch axes when
    divisible, giving each device its true [B_loc, N_p, H, dh] partition);
    q/k/v share the [B, N, heads, dh] layout with N split over ``axis``;
    optional trailing [B, N] masks."""
    b = batch_axes if batch_axes else None
    spec = P(b, axis, None, None)
    in_specs = (spec, spec, spec) + (P(b, axis),) * n_masks
    manual = set((axis,) + tuple(batch_axes))
    return jax.shard_map(fn, in_specs=in_specs, out_specs=spec,
                         axis_names=manual, check_vma=False)


# ---------------------------------------------------------------------------
# Cross-attention exchange (whisper encoder memory, VLM image tokens)
# ---------------------------------------------------------------------------

def exchange_cross_attention(
    q: jnp.ndarray,       # [B, Nq, H, dh] — Nq sharded over cfg.seq_axis
    k_mem: jnp.ndarray,   # [B, M, Hk, dh] — memory, M sharded likewise
    v_mem: jnp.ndarray,
    mem_mask: jnp.ndarray,  # [B, M] bool — False for padding
    cfg: ExchangeConfig,
    *,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Cross-attention where the memory is position-partitioned.

    The paper's scheme applied to an encoder/image memory: each device owns a
    memory partition; PRISM broadcasts only mask-aware segment means of the
    other partitions (comm (P-1)·L·D vs Voltage's (P-1)/P·M·D).
    """
    if (cfg.mode in (ExchangeMode.LOCAL, ExchangeMode.PRISM_SIM)
            or cfg.seq_axis is None or cfg.seq_shards == 1):
        # PRISM_SIM never uses real collectives; these paths have no
        # simulation analogue (unsharded cache / memory), so run exact
        return reference_attention(q, k_mem, v_mem, kv_mask=mem_mask,
                                   logit_softcap=logit_softcap, scale=scale)
    axis, Pn, L = cfg.seq_axis, cfg.seq_shards, cfg.L
    q, k_mem, v_mem = (_pin_seq_sharding(t, axis) for t in (q, k_mem, v_mem))

    if cfg.mode == ExchangeMode.VOLTAGE:
        def volt(qs, ks, vs, ms):
            kg = all_gather_grad_safe(ks, axis, axis=1, tiled=True)
            vg = all_gather_grad_safe(vs, axis, axis=1, tiled=True)
            mg = jax.lax.all_gather(ms, axis, axis=1, tiled=True)  # bool: no grad
            return reference_attention(qs, kg, vg, kv_mask=mg,
                                       logit_softcap=logit_softcap, scale=scale)
        bax = _manual_batch_axes(q.shape[0], cfg) or None
        manual = {axis} | set(bax or ())
        return jax.shard_map(
            volt,
            in_specs=(P(bax, axis, None, None), P(bax, axis, None, None),
                      P(bax, axis, None, None), P(bax, axis)),
            out_specs=P(bax, axis, None, None),
            axis_names=manual, check_vma=False)(q, k_mem, v_mem, mem_mask)

    def prism_x(qs, ks, vs, ms):
        p = jax.lax.axis_index(axis)
        km, cnt = kdsp.segment_means_masked(ks, L, ms, axis=1)  # [B,L,Hk,dh]
        vm, _ = kdsp.segment_means_masked(vs, L, ms, axis=1)
        km_all = jnp.moveaxis(jax.lax.all_gather(km, axis), 0, 1)
        vm_all = jnp.moveaxis(jax.lax.all_gather(vm, axis), 0, 1)
        cnt_all = jnp.moveaxis(jax.lax.all_gather(cnt, axis), 0, 1)  # [B,P,L]
        return kdsp.prism_attention(qs, ks, vs, km_all, vm_all, p,
                                    seg_size=ks.shape[1] // L, causal=False,
                                    logit_softcap=logit_softcap, scale=scale,
                                    kv_mask=ms, mean_counts=cnt_all)
    bax = _manual_batch_axes(q.shape[0], cfg) or None
    manual = {axis} | set(bax or ())
    return jax.shard_map(
        prism_x,
        in_specs=(P(bax, axis, None, None), P(bax, axis, None, None),
                  P(bax, axis, None, None), P(bax, axis)),
        out_specs=P(bax, axis, None, None),
        axis_names=manual, check_vma=False)(q, k_mem, v_mem, mem_mask)


# ---------------------------------------------------------------------------
# MLA latent exchange (DeepSeek-V2): compress-then-exchange the latent c_kv
# ---------------------------------------------------------------------------

def exchange_attention_mla(
    q: jnp.ndarray,        # [B, N, H, dq]  (dq = nope+rope), N seq-sharded
    c_kv: jnp.ndarray,     # [B, N, r]      latent KV (post-norm)
    k_pe: jnp.ndarray,     # [B, N, dr]     shared rotary key
    w_uk: jnp.ndarray,     # [r, H, d_nope] up-projection for keys
    w_uv: jnp.ndarray,     # [r, H, d_v]    up-projection for values
    cfg: ExchangeConfig,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """PRISM over the MLA latent: devices exchange segment means of
    ``[c_kv ‖ k_pe]`` (r+dr floats/token — MLA's own compression compounds
    with PRISM's CR), then expand locally. Linearity of the up-projections
    makes mean-then-expand == expand-then-mean, so remote K/V are never
    re-projected (the paper's reformulation, in latent space).
    """
    B, N, H, dq = q.shape
    r = c_kv.shape[-1]
    d_nope = w_uk.shape[-1]
    d_v = w_uv.shape[-1]

    def expand(c, pe):
        # c: [B, n, r], pe: [B, n, dr] → k: [B, n, H, dq], v: [B, n, H, d_v]
        k_nope = jnp.einsum("bnr,rhd->bnhd", c, w_uk)
        pe_b = jnp.broadcast_to(pe[:, :, None, :], (*k_nope.shape[:3], pe.shape[-1]))
        k = jnp.concatenate([k_nope, pe_b], axis=-1)
        v = jnp.einsum("bnr,rhd->bnhd", c, w_uv)
        return k, v

    if (cfg.mode in (ExchangeMode.LOCAL, ExchangeMode.PRISM_SIM)
            or cfg.seq_axis is None or cfg.seq_shards == 1):
        # PRISM_SIM never uses real collectives; these paths have no
        # simulation analogue (unsharded cache / memory), so run exact
        k, v = expand(c_kv, k_pe)
        B_, Nq_, H_ = q.shape[0], q.shape[1], q.shape[2]
        if B_ * H_ * Nq_ * k.shape[1] * 4 > 0.5e9:
            from repro.core.prism_attention import chunked_reference_attention
            return chunked_reference_attention(q, k, v, causal=causal,
                                               scale=scale)
        return reference_attention(q, k, v, causal=causal, scale=scale)

    axis, Pn, L = cfg.seq_axis, cfg.seq_shards, cfg.L
    q = _pin_seq_sharding(q, axis)
    c_kv = _pin_seq_sharding(c_kv, axis)
    k_pe = _pin_seq_sharding(k_pe, axis)

    if cfg.mode == ExchangeMode.VOLTAGE:
        def volt(qs, cs, ps):
            p = jax.lax.axis_index(axis)
            Np = qs.shape[1]
            cg = all_gather_grad_safe(cs, axis, axis=1, tiled=True)
            pg = all_gather_grad_safe(ps, axis, axis=1, tiled=True)
            k, v = expand(cg, pg)   # full re-expansion on every device
            from repro.core.prism_attention import chunked_reference_attention
            return chunked_reference_attention(qs, k, v, causal=causal,
                                               q_offset=p * Np, scale=scale)
        bax = _manual_batch_axes(q.shape[0], cfg) or None
        manual = {axis} | set(bax or ())
        return jax.shard_map(
            volt, in_specs=(P(bax, axis, None, None), P(bax, axis, None),
                            P(bax, axis, None)),
            out_specs=P(bax, axis, None, None),
            axis_names=manual, check_vma=False)(q, c_kv, k_pe)

    def prism_mla(qs, cs, ps):
        p = jax.lax.axis_index(axis)
        Bl, Np = cs.shape[0], cs.shape[1]     # local (manual-region) shapes
        seg = Np // L
        cm = kdsp.segment_means(cs, L, axis=1)       # [Bl, L, r]
        pm = kdsp.segment_means(ps, L, axis=1)       # [Bl, L, dr]
        cm_all = jnp.moveaxis(all_gather_grad_safe(cm, axis), 0, 1)
        pm_all = jnp.moveaxis(all_gather_grad_safe(pm, axis), 0, 1)
        k_loc, v_loc = expand(cs, ps)
        km, vm = expand(cm_all.reshape(Bl, Pn * L, r),
                        pm_all.reshape(Bl, Pn * L, -1))
        km = km.reshape(Bl, Pn, L, H, dq)
        vm = vm.reshape(Bl, Pn, L, H, d_v)
        return kdsp.prism_attention(qs, k_loc, v_loc, km, vm, p, seg,
                                    causal=causal, scale=scale)
    bax = _manual_batch_axes(q.shape[0], cfg) or None
    manual = {axis} | set(bax or ())
    return jax.shard_map(
        prism_mla, in_specs=(P(bax, axis, None, None), P(bax, axis, None),
                             P(bax, axis, None)),
        out_specs=P(bax, axis, None, None),
        axis_names=manual, check_vma=False)(q, c_kv, k_pe)


def mla_decode_attention_sharded(
    q_lat: jnp.ndarray,    # [B, 1, H, r]  absorbed no-pe query
    q_pe: jnp.ndarray,     # [B, 1, H, dr] rotary query
    c_cache: jnp.ndarray,  # [B, S, r]     latent cache, S sharded over seq axis
    pe_cache: jnp.ndarray, # [B, S, dr]
    cache_len,             # scalar int32 — global valid prefix
    cfg: ExchangeConfig,
    *,
    scale: float,
) -> jnp.ndarray:
    """One-token absorbed MLA attention over a position-sharded latent cache.

    Exact flash-decoding merge: per-shard partial softmax in the latent space
    followed by a global LSE-weighted psum of [B, H, r]-sized partials.
    """
    def partial_attn(ql, qp, c, pe, off):
        # logits [B, H, 1, S]
        lg = (jnp.einsum("bqhr,bsr->bhqs", ql.astype(jnp.float32),
                         c.astype(jnp.float32))
              + jnp.einsum("bqhd,bsd->bhqs", qp.astype(jnp.float32),
                           pe.astype(jnp.float32))) * scale
        S = c.shape[1]
        gpos = off + jnp.arange(S)
        lg = jnp.where((gpos < cache_len)[None, None, None, :], lg, NEG_INF)
        return lg

    if (cfg.mode in (ExchangeMode.LOCAL, ExchangeMode.PRISM_SIM)
            or cfg.seq_axis is None or cfg.seq_shards == 1):
        # PRISM_SIM never uses real collectives; these paths have no
        # simulation analogue (unsharded cache / memory), so run exact
        lg = partial_attn(q_lat, q_pe, c_cache, pe_cache, 0)
        p = jax.nn.softmax(lg, axis=-1)
        o = jnp.einsum("bhqs,bsr->bqhr", p, c_cache.astype(jnp.float32))
        return o.astype(q_lat.dtype)

    axis = cfg.seq_axis

    def shard_fn(ql, qp, c, pe):
        i = jax.lax.axis_index(axis)
        Sp = c.shape[1]
        lg = partial_attn(ql, qp, c, pe, i * Sp)
        m_p = jnp.max(lg, axis=-1, keepdims=True)
        m_g = jax.lax.pmax(m_p, axis)
        w = jnp.exp(lg - m_g)
        l_p = jnp.sum(w, axis=-1)                                  # [B,H,1]
        o_p = jnp.einsum("bhqs,bsr->bqhr", w, c.astype(jnp.float32))
        l_g = jax.lax.psum(l_p, axis)
        o_g = jax.lax.psum(o_p, axis)
        return (o_g / l_g.transpose(0, 2, 1)[..., None]).astype(ql.dtype)

    return jax.shard_map(
        shard_fn,
        in_specs=(P(None, None, None, None), P(None, None, None, None),
                  P(None, axis, None), P(None, axis, None)),
        out_specs=P(None, None, None, None),
        axis_names={axis}, check_vma=False)(q_lat, q_pe, c_cache, pe_cache)


# ---------------------------------------------------------------------------
# Decode-time attention over a sequence-sharded KV cache
# ---------------------------------------------------------------------------

def decode_attention_sharded(
    q: jnp.ndarray,        # [B, 1, H, dh] — replicated over seq axis
    k_cache: jnp.ndarray,  # [B, S, Hk, dh] — S sharded over seq axis
    v_cache: jnp.ndarray,  # [B, S, Hk, dh]
    cache_len,             # [B] or scalar — valid prefix length (global)
    cfg: ExchangeConfig,
    *,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,           # sliding-window validity
    k_means: Optional[jnp.ndarray] = None,  # [B, P, L, Hk, dh] PRISM-decode
    v_means: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """One-token attention against a position-sharded cache.

    VOLTAGE/exact: per-shard partial softmax + global LSE merge (one psum of
    [B, H, dh]-sized partials — tiny; this is the flash-decoding scheme).
    PRISM-decode (beyond-paper): each shard holds locally-refreshed segment
    means of *remote* shards, so no collective is needed on the seq axis.
    """
    def _valid(gpos, clen):
        ok = gpos[None, :] < jnp.reshape(clen, (-1, 1))
        if window is not None:
            ok &= gpos[None, :] >= jnp.reshape(clen, (-1, 1)) - window
        return ok

    if (cfg.mode in (ExchangeMode.LOCAL, ExchangeMode.PRISM_SIM)
            or cfg.seq_axis is None or cfg.seq_shards == 1):
        # PRISM_SIM never uses real collectives; these paths have no
        # simulation analogue (unsharded cache / memory), so run exact.
        # Routed through the kernel-dispatch layer: the flash-decode Pallas
        # kernel when the backend supports it, masked reference otherwise.
        return kdsp.decode_attention(q, k_cache, v_cache, cache_len,
                                     window=window,
                                     logit_softcap=logit_softcap,
                                     scale=scale)

    axis = cfg.seq_axis
    Pn = cfg.seq_shards
    use_prism = cfg.mode == ExchangeMode.PRISM and k_means is not None

    def shard_fn(qs, ks, vs, clen, km, vm):
        p = jax.lax.axis_index(axis)
        B, Sp, Hk, dh = ks.shape
        H = qs.shape[2]
        scl = (dh ** -0.5) if scale is None else scale
        f32 = jnp.float32
        # local logits (grouped-GQA, bf16 operands, f32 accumulation),
        # masked by global validity of each cache slot
        logits = _grouped_scores(qs, ks) * scl
        logits = _softcap(logits, logit_softcap)
        gpos = p * Sp + jnp.arange(Sp)
        valid = _valid(gpos, clen)
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)

        if use_prism:
            # attend additionally to locally stored means of remote shards
            km_f = km.reshape(B, -1, Hk, dh)
            vm_f = vm.reshape(B, -1, Hk, dh)
            Lm = km.shape[2]
            seg = jnp.maximum(Sp // max(Lm, 1), 1)
            mlog = _grouped_scores(qs, km_f) * scl
            mlog = _softcap(mlog, logit_softcap) + jnp.log(
                jnp.asarray(seg, f32))
            owner = jnp.arange(Pn * Lm) // Lm
            mlog = jnp.where((owner != p)[None, None, None, :], mlog, NEG_INF)
            logits = jnp.concatenate([logits, mlog], axis=-1)
            # no collective: summaries already local
            m = jnp.max(logits, axis=-1, keepdims=True)
            w = jnp.exp(logits - m)
            o = (_grouped_values(w[..., :Sp], vs)
                 + _grouped_values(w[..., Sp:], vm_f))
            denom = jnp.sum(w, axis=-1).transpose(0, 2, 1)[..., None]
            return (o / denom).astype(qs.dtype)

        # exact flash-decoding merge across shards
        m_p = jnp.max(logits, axis=-1, keepdims=True)          # [B,H,1,1]
        m_g = jax.lax.pmax(m_p, axis)
        w = jnp.exp(logits - m_g)
        l_p = jnp.sum(w, axis=-1)                              # [B,H,1]
        o_p = _grouped_values(w, vs)                           # [B,1,H,dh]
        l_g = jax.lax.psum(l_p, axis)
        o_g = jax.lax.psum(o_p, axis)
        denom = l_g.transpose(0, 2, 1)[..., None]
        return (o_g / denom).astype(qs.dtype)

    bax = _manual_batch_axes(q.shape[0], cfg) or None
    manual = {axis} | set(bax or ())
    cache_spec = P(bax, axis, None, None)
    q_spec = P(bax, None, None, None)
    mean_spec = P(bax, None, None, None, None)
    clen = jnp.atleast_1d(cache_len)
    clen_spec = P(bax) if (bax and clen.shape[0] == q.shape[0]) else P(None)
    in_specs = (q_spec, cache_spec, cache_spec, clen_spec,
                mean_spec, mean_spec)
    if not use_prism:
        B0 = q.shape[0]
        k_means = (jnp.zeros((B0, Pn, 1, k_cache.shape[2], k_cache.shape[3]),
                             q.dtype) if k_means is None else k_means)
        v_means = (jnp.zeros((B0, Pn, 1, k_cache.shape[2], k_cache.shape[3]),
                             q.dtype) if v_means is None else v_means)
    out = jax.shard_map(shard_fn, in_specs=in_specs, out_specs=q_spec,
                        axis_names=manual, check_vma=False)(
        q, k_cache, v_cache, clen, k_means, v_means)
    return out
