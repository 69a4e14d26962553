"""Operations and bytes of latent attention with routed experts
(DeepSeek-V2-Lite), computed from the configuration's sizes; the
conventions of ``counts.py`` (one multiply-add is 2 operations; bytes are
the least that must cross HBM; cached prompt tokens are not work).

Attention is counted as the architecture's math (keys of nope + rope
dims, values of v dims per head) for the model's operations, and in the
absorbed form the paged latent kernel computes (every head against the
latent rank and the rope key) for the kernel's roofline.  A token's
routed work here is the experts held here: ``top_k · held / router
outputs`` (token, expert) pairs a token on average, or the pairs the
program counted where it counts them.
"""
from __future__ import annotations

from typing import Dict, Sequence


def expert_params(m: Dict) -> int:
    """Weights of one routed expert (gate, up and down)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_weight_bytes(m: Dict) -> int:
    """Bytes a held expert's weights take, read once per step it runs."""
    return expert_params(m) * m["bytes_per_element"]


def expert_pair_flops(m: Dict) -> int:
    """Operations of one (token, expert) pair through one expert."""
    return 2 * expert_params(m)


def attn_params(m: Dict) -> int:
    """Weights of one layer's latent attention (query, latent down
    projection, key and value up projections, output)."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    return D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D


def held_pairs_per_token(m: Dict) -> float:
    """(token, expert) pairs a token sends to the experts held here, on
    average: top-k x held / router outputs."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["router_outputs"])


def token_matmul_flops(m: Dict) -> float:
    """A token's operations through every layer's weights and the head
    (the embedding is a lookup): attention, the dense layers' MLP, each
    MoE layer's router, shared experts and its average held pairs."""
    D, L = m["hidden_size"], m["num_hidden_layers"]
    n0 = m["first_k_dense_replace"]
    shared = 3 * D * m["moe_intermediate_size"] * m["n_shared_experts"]
    moe = (D * m["router_outputs"] + shared) * 2 \
        + held_pairs_per_token(m) * expert_pair_flops(m)
    return (2 * L * attn_params(m) + 2 * n0 * 3 * D * m["intermediate_size"]
            + (L - n0) * moe + 2 * D * m["vocab_size"])


def attn_flops(m: Dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context`` keys,
    all layers (keys of nope + rope dims, values of v dims)."""
    H = m["num_attention_heads"]
    dq = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return 2 * m["num_hidden_layers"] * H * (dq + m["v_head_dim"]) * context


def token_flops(m: Dict, context: int) -> float:
    """Forward operations of one token attending to ``context`` keys
    (itself included)."""
    return token_matmul_flops(m) + attn_flops(m, context)


def prefill_flops(m: Dict, start: int, n: int) -> float:
    """``n`` prompt tokens at positions ``start .. start+n-1``, causal."""
    ctx = n * start + n * (n + 1) // 2
    return token_matmul_flops(m) * n + attn_flops(m, 1) * ctx


def latent_decode_call(m: Dict, lengths: Sequence[int]) -> Dict[str, float]:
    """One paged latent-decode call (one layer, one step) over rows of the
    given valid lengths: each valid key's ``c_kv`` and ``k_pe`` read (the
    rope columns only), each row's absorbed query read and latent output
    written; per (head, key) 2·(r + dr) operations for the score and 2·r
    for the weighted latent."""
    H, r, dr, b = (m["num_attention_heads"], m["kv_lora_rank"],
                   m["qk_rope_head_dim"], m["bytes_per_element"])
    keys = int(sum(lengths))
    rows = len(lengths)
    return {"flops": 2.0 * H * (2 * r + dr) * keys,
            "bytes": float(b * (r + dr) * keys
                           + b * rows * H * (2 * r + dr))}
