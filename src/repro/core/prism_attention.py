"""Scaling-aware softmax attention over Segment-Means-augmented keys (PRISM).

The reference (pure ``jnp``) semantics of the paper's attention:

  * Queries come from the local partition ``X_p``.
  * Keys/Values are the local partition's full K/V **plus** the Segment Means
    of every other partition (Eq. 2).  Because projections are linear,
    ``mean(X_seg)·W_k == mean(X_seg·W_k)`` — so devices exchange *projected*
    segment means and never re-project remote features (this is the
    "eliminates redundant Key/Value recomputation" part of the paper's
    scaling-aware softmax reformulation).
  * Scaling-aware softmax: a mean key standing in for a segment of ``s`` real
    keys receives an additive logit bias ``log(s)`` so that
    ``s·exp(q·k̄) ≈ Σ_{i∈seg} exp(q·k_i)`` — one compressed key carries the
    attention mass of its whole segment.

Exactness property (tested): with segment size 1 (``CR·P == 1`` per
partition) the bias is ``log 1 = 0`` and the means are the tokens themselves,
so PRISM attention equals full (Voltage) attention bit-for-bit in f32.

Causal extension (ours; the paper evaluates bidirectional ViT): a segment
mean is visible to a query iff its *entire* segment lies in the query's past,
which at partition granularity means "partition index strictly less than the
query's partition".  Local keys use the ordinary causal mask.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(logits: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def _expand_kv(kv: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """Broadcast grouped KV heads [..., Hk, d] to query heads [..., H, d]."""
    hk = kv.shape[-2]
    if hk == n_heads:
        return kv
    assert n_heads % hk == 0, f"GQA heads {n_heads} not a multiple of {hk}"
    return jnp.repeat(kv, n_heads // hk, axis=-2)


def _grouped_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q [B,Nq,H,dh] · k [B,Nk,Hk,dh] → [B,H,Nq,Nk] f32 without
    materializing the GQA head repeat or f32 input copies (bf16 operands,
    f32 accumulation via preferred_element_type — MXU-native)."""
    B, Nq, H, dh = q.shape
    Hk = k.shape[2]
    if Hk == H:
        return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                          preferred_element_type=jnp.float32)
    g = H // Hk
    qg = q.reshape(B, Nq, Hk, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    return s.reshape(B, H, Nq, k.shape[1])


def _grouped_values(p: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """p [B,H,Nq,Nk] f32 · v [B,Nk,Hk,dh] → [B,Nq,H,dh] f32 (grouped)."""
    B, H, Nq, Nk = p.shape
    Hk, dh = v.shape[2], v.shape[3]
    if Hk == H:
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=jnp.float32)
    g = H // Hk
    pg = p.reshape(B, Hk, g, Nq, Nk)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pg, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Nq, H, dh)


def reference_attention(
    q: jnp.ndarray,               # [B, Nq, H, dh]
    k: jnp.ndarray,               # [B, Nk, Hk, dh]
    v: jnp.ndarray,               # [B, Nk, Hk, dh]
    *,
    causal: bool = False,
    q_offset: int = 0,            # global position of q[0] (sequence sharding)
    kv_offset: int = 0,           # global position of k[0]
    window: Optional[int] = None,  # sliding-window size (gemma2 local layers)
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    bias: Optional[jnp.ndarray] = None,   # [..., Nq, Nk] additive logit bias
    kv_mask: Optional[jnp.ndarray] = None,  # [B, Nk] bool; False → masked
) -> jnp.ndarray:
    """Plain full attention — the oracle for every optimized path."""
    B, Nq, H, dh = q.shape
    Nk = k.shape[1]
    scale = (dh ** -0.5) if scale is None else scale
    logits = _grouped_scores(q, k) * scale
    logits = _softcap(logits, logit_softcap)
    if bias is not None:
        logits = logits + bias
    qpos = q_offset + jnp.arange(Nq)[:, None]
    kpos = kv_offset + jnp.arange(Nk)[None, :]
    mask = jnp.ones((Nq, Nk), dtype=bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = _grouped_values(p, v)
    return out.astype(q.dtype)


def chunked_reference_attention(
    q: jnp.ndarray,               # [B, Nq, H, dh]
    k: jnp.ndarray,               # [B, Nk, Hk, dh]
    v: jnp.ndarray,
    *,
    chunk: Optional[int] = None,
    causal: bool = False,
    q_offset: int = 0,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    kv_mask: Optional[jnp.ndarray] = None,
    target_bytes: float = 0.5e9,
) -> jnp.ndarray:
    """``reference_attention`` evaluated in query chunks via ``lax.map``.

    Bounds the live score matrix to [B, H, chunk, Nk] (flash-style memory
    behaviour without a kernel — the Pallas kernel is the TPU fast path);
    backward recomputes per chunk. Exact same math as the unchunked oracle.
    The chunk size adapts so the f32 score block stays under
    ``target_bytes``.
    """
    B, Nq, H, dh = q.shape
    if chunk is None:
        per_row = B * H * k.shape[1] * 4.0
        chunk = max(int(target_bytes / max(per_row, 1.0)), 16)
        chunk = 1 << (chunk.bit_length() - 1)          # floor pow2
    C = min(chunk, Nq)
    if Nq % C:
        return reference_attention(q, k, v, causal=causal, q_offset=q_offset,
                                   window=window, logit_softcap=logit_softcap,
                                   scale=scale, kv_mask=kv_mask)
    nc = Nq // C
    qc = jnp.moveaxis(q.reshape(B, nc, C, H, dh), 1, 0)    # [nc, B, C, H, dh]
    offs = q_offset + jnp.arange(nc, dtype=jnp.int32) * C

    def one(args):
        qi, off = args
        return reference_attention(qi, k, v, causal=causal, q_offset=off,
                                   window=window, logit_softcap=logit_softcap,
                                   scale=scale, kv_mask=kv_mask)

    out = jax.lax.map(one, (qc, offs))                 # [nc, B, C, H, dv]
    return jnp.moveaxis(out, 0, 1).reshape(B, Nq, H, out.shape[-1])


def prism_attention(
    q: jnp.ndarray,        # [B, Np, H, dh]   local queries (partition p)
    k_local: jnp.ndarray,  # [B, Np, Hk, dh]  local full keys
    v_local: jnp.ndarray,  # [B, Np, Hk, dh]
    k_means: jnp.ndarray,  # [B, P, L, Hk, dh] segment-mean keys, ALL partitions
    v_means: jnp.ndarray,  # [B, P, L, Hk, dh]
    part_idx,              # scalar int — this device's partition index p
    seg_size: int,         # tokens represented by each segment mean
    *,
    causal: bool = False,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    kv_mask: Optional[jnp.ndarray] = None,      # [B, Np] bool; False → pad
    mean_counts: Optional[jnp.ndarray] = None,  # [B, P, L] real tokens per mean
    q_offset=0,                                 # local offset (chunking)
) -> jnp.ndarray:
    """Scaling-aware softmax attention over [local full ‖ remote means].

    ``k_means[:, p]`` (own partition) is always masked out — the local full
    keys already cover it.  Under ``causal=True`` only partitions strictly
    before ``part_idx`` contribute their means.  Padded sequences pass
    ``kv_mask`` (local keys) and ``mean_counts`` (mask-aware means; the
    scaling bias becomes ``log(count)`` and empty segments are dropped).
    Long query blocks are processed in chunks (bounded f32 score memory).
    """
    B, Nq, H, dh = q.shape
    Nk_loc = k_local.shape[1]
    P, L = k_means.shape[1], k_means.shape[2]
    scale = (dh ** -0.5) if scale is None else scale

    # q-chunking: bound the [B, H, Nq, Nk_loc + P·L] f32 score block
    total_k = Nk_loc + P * L
    if (isinstance(q_offset, int) and q_offset == 0
            and B * H * Nq * total_k * 4 > 0.5e9
            and Nq % 2 == 0 and Nq >= 256):
        C = max(Nq // 2, 128)
        while B * H * C * total_k * 4 > 0.5e9 and C % 2 == 0 and C > 128:
            C //= 2
        if Nq % C == 0:
            nc = Nq // C
            qc = jnp.moveaxis(q.reshape(B, nc, C, H, dh), 1, 0)
            offs = jnp.arange(nc, dtype=jnp.int32) * C

            def one(args):
                qi, off = args
                return prism_attention(
                    qi, k_local, v_local, k_means, v_means, part_idx,
                    seg_size, causal=causal, logit_softcap=logit_softcap,
                    scale=scale, kv_mask=kv_mask, mean_counts=mean_counts,
                    q_offset=off)
            out = jax.lax.map(one, (qc, offs))
            return jnp.moveaxis(out, 0, 1).reshape(B, Nq, H, out.shape[-1])

    km_flat = k_means.reshape(B, P * L, *k_means.shape[3:])
    vm_flat = v_means.reshape(B, P * L, *v_means.shape[3:])

    # --- local block: ordinary (optionally causal) attention within X_p ---
    logits_loc = _grouped_scores(q, k_local) * scale
    logits_loc = _softcap(logits_loc, logit_softcap)
    if causal:
        qpos = q_offset + jnp.arange(Nq)[:, None]
        cmask = qpos >= jnp.arange(Nk_loc)[None, :]
        logits_loc = jnp.where(cmask[None, None], logits_loc, NEG_INF)
    if kv_mask is not None:
        logits_loc = jnp.where(kv_mask[:, None, None, :], logits_loc, NEG_INF)

    # --- segment-means block: scaling-aware softmax ---
    logits_mean = _grouped_scores(q, km_flat) * scale
    logits_mean = _softcap(logits_mean, logit_softcap)
    # scaling-aware bias: one mean key carries the mass of its segment.
    if mean_counts is None:
        logits_mean = logits_mean + jnp.log(float(seg_size))
        nonempty = jnp.ones((B, P * L), dtype=bool)
    else:
        counts = mean_counts.reshape(B, P * L)
        logits_mean = logits_mean + jnp.log(jnp.maximum(counts, 1.0)
                                            )[:, None, None, :]
        nonempty = counts > 0
    part_of_mean = jnp.arange(P * L) // L                   # [P*L]
    if causal:
        visible = part_of_mean < part_idx                   # strictly past
    else:
        visible = part_of_mean != part_idx                  # everyone else
    logits_mean = jnp.where(visible[None, None, None, :], logits_mean, NEG_INF)
    logits_mean = jnp.where(nonempty[:, None, None, :], logits_mean, NEG_INF)

    logits = jnp.concatenate([logits_loc, logits_mean], axis=-1)
    p_attn = jax.nn.softmax(logits, axis=-1)
    out = (_grouped_values(p_attn[..., :Nk_loc], v_local)
           + _grouped_values(p_attn[..., Nk_loc:], vm_flat))
    return out.astype(q.dtype)


def prism_attention_dense_oracle(
    x: jnp.ndarray,        # [B, N, D] full (unpartitioned) sequence features
    wq, wk, wv,            # projection fns or matrices applied outside
    **_,
):  # pragma: no cover - placeholder guard
    raise NotImplementedError(
        "Use repro.core.partition.simulate_partitioned_forward for the "
        "single-host oracle of the distributed computation.")


@partial(jax.jit, static_argnames=("L", "seg_size", "causal"))
def prism_attention_from_projected(
    q, k, v, part_idx, *, L: int, seg_size: int, causal: bool = False
):
    """Convenience wrapper: derive means from the local projected K/V then
    run PRISM attention for a single partition against provided means of all
    partitions being just its own (P=1 degenerate case used in unit tests)."""
    km = segment_means_nd(k, L)[:, None]
    vm = segment_means_nd(v, L)[:, None]
    return prism_attention(q, k, v, km, vm, part_idx, seg_size, causal=causal)


def segment_means_nd(x: jnp.ndarray, L: int) -> jnp.ndarray:
    """Segment means over the token axis of [B, N, Hk, dh] → [B, L, Hk, dh]."""
    from repro.core.segment_means import segment_means
    return segment_means(x, L, axis=1)
