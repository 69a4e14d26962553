"""Operations and bytes the algorithms need, computed from shapes, and the
chip's peaks.  Nothing here reads the program: the sizes come from the
configuration files.

Conventions: one multiply-add is 2 operations; bytes are the least that
must cross HBM for the call (each operand read once, each result written
once); cached prompt tokens are not work.
"""
from __future__ import annotations

import functools
import json
import pathlib
from typing import Dict, Sequence

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


@functools.lru_cache(maxsize=None)
def _peak_table() -> Dict:
    with open(PEAKS) as f:
        return json.load(f)


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``.  A device that is
    not in the table is an error, never a default."""
    table = _peak_table()["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)} ({PEAKS.name})")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peak: Dict[str, float]):
    """(seconds, bound): the roofline's least time and which limit sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ---------------------------------------------------------------------------
# dense GQA decoder (internlm2-1.8b)
# ---------------------------------------------------------------------------

def lm_matmul_params(m: Dict) -> int:
    """Weights a token multiplies through: every layer's projections and
    MLP, and the output head (the embedding is a lookup)."""
    D, H, Hk, dh, F = (m["hidden_size"], m["num_attention_heads"],
                       m["num_key_value_heads"], m["head_dim"],
                       m["intermediate_size"])
    per_layer = D * H * dh * 2 + D * Hk * dh * 2 + 3 * D * F
    return m["num_hidden_layers"] * per_layer + D * m["vocab_size"]


def lm_kv_bytes_per_token(m: Dict) -> int:
    """K and V of one token over all layers."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * m["bytes_per_element"])


def lm_attn_flops(m: Dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context`` keys,
    all layers."""
    return (4 * m["num_hidden_layers"] * m["num_attention_heads"]
            * m["head_dim"] * context)


def lm_token_flops(m: Dict, context: int) -> int:
    """Forward operations of one token that attends to ``context`` keys
    (itself included)."""
    return 2 * lm_matmul_params(m) + lm_attn_flops(m, context)


def lm_prefill_flops(m: Dict, start: int, n: int) -> int:
    """``n`` prompt tokens at positions ``start .. start+n-1``, causal (the
    ``start`` cached tokens are not recomputed)."""
    ctx = n * start + n * (n + 1) // 2
    return 2 * lm_matmul_params(m) * n + lm_attn_flops(m, 1) * ctx


def paged_decode_call(m: Dict, lengths: Sequence[int]) -> Dict[str, float]:
    """One paged flash-decode call (one layer, one step) over rows of the
    given valid lengths: the valid K/V pages read, the query read and the
    output written; 4·dh operations per (head, key)."""
    H, Hk, dh, b = (m["num_attention_heads"], m["num_key_value_heads"],
                    m["head_dim"], m["bytes_per_element"])
    keys = int(sum(lengths))
    rows = len(lengths)
    return {"flops": 4.0 * H * dh * keys,
            "bytes": 2.0 * Hk * dh * b * keys + 2.0 * rows * H * dh * b}


# ---------------------------------------------------------------------------
# ViT-B/16
# ---------------------------------------------------------------------------

def vit_tokens(m: Dict) -> int:
    return (m["image_size"] // m["patch_size"]) ** 2 + 1


def vit_image_flops(m: Dict) -> int:
    """Forward operations of one image: patch embedding, every layer's
    projections, attention over all tokens and MLP, and the head."""
    D, F, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    N = vit_tokens(m)
    patch = (N - 1) * (m["patch_size"] ** 2 * m["num_channels"]) * D
    layer = N * (4 * D * D + 2 * D * F) + 2 * N * N * D
    head = D * m["num_labels"]
    return 2 * (patch + L * layer + head)


def segment_means_call(batch: int, n: int, d: int, segments: int,
                       itemsize: int = 4) -> Dict[str, float]:
    """One segment-means kernel call over ``[batch, n, d]``: reads the
    input, writes ``segments`` rows; one add per element."""
    return {"flops": float(batch * n * d),
            "bytes": float(itemsize * batch * d * (n + segments))}
