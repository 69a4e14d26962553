"""Unified architecture config covering all assigned families.

Every assigned architecture is one ``ModelConfig``; the model registry
(`repro.models.registry`) turns a config into init/apply functions. Shapes
(`ShapeSpec`) are the assigned (seq_len × global_batch) input grids.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int                # routed experts: the router's outputs
    top_k: int
    n_shared: int = 0             # always-on shared experts
    d_ff_expert: int = 0          # expert hidden dim
    first_dense_layers: int = 1   # leading layers that use a dense MLP
    d_ff_dense: int = 0           # hidden dim of those dense MLPs
    norm_topk_prob: bool = True   # renormalise the top-k probabilities
    routed_scaling_factor: float = 1.0
    # [first, end) of the router's experts whose weights this device holds
    # (expert parallelism: the device computes their part of the result);
    # None holds every expert
    held: Optional[Tuple[int, int]] = None
    router_aux_weight: float = 0.001

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held or (0, self.n_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held_range
        return hi - lo


@dataclasses.dataclass(frozen=True)
class MLACfg:
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = 1536   # None: queries projected directly
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YaRNCfg:
    """YaRN rope scaling (arXiv:2309.00071) in DeepSeek-V2's form: the
    rotary frequencies blend the original and the ``factor``-interpolated
    ones by a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow``; cos/sin are scaled by mscale(mscale) /
    mscale(mscale_all_dim), and the softmax scale by
    mscale(mscale_all_dim)^2."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class SSMCfg:
    state_size: int = 16
    conv_width: int = 4
    expand: int = 2               # d_inner = expand * d_model (mamba)
    chunk: int = 128              # chunked-scan block length
    slstm_every: int = 8          # xLSTM: one sLSTM per this many blocks
    mlstm_heads: int = 4
    proj_factor: float = 2.0      # xLSTM up-projection factor


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | audio | vlm | hybrid | ssm | vit
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 → d_model // n_heads
    # attention behaviour
    qkv_bias: bool = False
    causal: bool = True
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    window: Optional[int] = None            # sliding window (local layers)
    local_global: bool = False              # gemma2 alternation local,global,...
    rope_theta: float = 10000.0
    rope_scaling: Optional[YaRNCfg] = None  # latent (MLA) attention only
    query_scale: Optional[float] = None     # override 1/sqrt(head_dim)
    # block structure
    norm_type: str = "rmsnorm"
    post_norms: bool = False                # gemma2 extra post-block norms
    act: str = "silu"
    tie_embeddings: bool = True
    embed_scale: bool = False               # multiply embeddings by sqrt(d)
    # family extensions
    moe: Optional[MoECfg] = None
    mla: Optional[MLACfg] = None
    ssm: Optional[SSMCfg] = None
    # enc-dec (whisper): decoder uses fields above; encoder below
    encoder_layers: int = 0
    encoder_seq: int = 1500                 # fixed 30 s mel window (stub frontend)
    # vlm: 1-in-k layers are cross-attention to image tokens
    cross_attn_every: int = 0
    image_tokens: int = 1601                # llama3.2-vision: 1 tile × (40² + 1)
    image_embed_dim: int = 0                # 0 → d_model (stub projects already)
    # serving
    kv_quant: bool = False        # int8 KV cache (per-token/head scales)
    # dtypes
    dtype: str = "bfloat16"
    # notes for DESIGN/docs
    source: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def reduced(self, **over) -> "ModelConfig":
        """Tiny same-family variant for CPU smoke tests."""
        mha = self.n_kv_heads == self.n_heads
        base = dict(
            n_layers=min(self.n_layers, 2 if not self.local_global else 2),
            d_model=64, n_heads=4, n_kv_heads=4 if mha else 2,
            head_dim=16, d_ff=128, vocab_size=512,
        )
        if self.local_global:
            base["window"] = 16
        if self.moe:
            base["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=2, n_shared=min(self.moe.n_shared, 1),
                d_ff_expert=32, d_ff_dense=128, first_dense_layers=1,
                held=None)
        if self.mla:
            base["mla"] = MLACfg(
                kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                q_lora_rank=48 if self.mla.q_lora_rank else None)
        if self.ssm:
            base["ssm"] = dataclasses.replace(self.ssm, state_size=8, chunk=8,
                                              slstm_every=2, mlstm_heads=2)
        if self.encoder_layers:
            base["encoder_layers"] = 2
            base["encoder_seq"] = 16
        if self.cross_attn_every:
            base["cross_attn_every"] = 2
            base["image_tokens"] = 8
        base.update(over)
        return dataclasses.replace(self, **base)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


TRAIN_4K = ShapeSpec("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeSpec("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeSpec("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeSpec("long_500k", 524288, 1, "decode")

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}

# long_500k requires a sub-quadratic sequence path. PRISM's segment-means
# attention bounds remote context to (P-1)·L keys, but the paper's technique
# keeps the LOCAL partition dense — at N=524288, P=16 a 32k dense local block
# per device stays quadratic-in-shard. Per the brief we therefore run
# long_500k only for the state-space / hybrid archs (O(1) state decode) and
# skip it for the 8 pure-attention archs (noted in DESIGN.md §4).
LONG_CONTEXT_ARCHS = ("hymba-1.5b", "xlstm-350m")


def shapes_for(arch: str) -> Tuple[ShapeSpec, ...]:
    if arch in LONG_CONTEXT_ARCHS:
        return ALL_SHAPES
    return (TRAIN_4K, PREFILL_32K, DECODE_32K)
