"""Operation and byte counts, and the table of peaks."""
import json

import pytest

from bench import counts, harness


def model(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")[
        "model"]


def test_vit_b16_image_flops_match_the_paper():
    # the PRISM paper's Table 3: 35.15 GFLOP per 224 x 224 image
    assert counts.vit_image_flops(model("vit-base-16")) == pytest.approx(
        35.2e9, rel=0.01)
    assert counts.vit_tokens(model("vit-base-16")) == 197


def test_internlm2_token_flops_and_kv_bytes():
    m = model("internlm2-1.8b")
    # 24 x (2 x 2048^2 + 2 x 2048 x 1024 + 3 x 2048 x 8192) + 2048 x 92544
    assert counts.lm_matmul_params(m) == 1_699_479_552
    assert counts.lm_token_flops(m, 0) == 2 * 1_699_479_552
    assert counts.lm_token_flops(m, 1000) - counts.lm_token_flops(m, 0) == \
        4 * 24 * 16 * 128 * 1000
    assert counts.lm_kv_bytes_per_token(m) == 96 * 1024


def test_prefill_counts_skip_cached_tokens():
    m = model("internlm2-1.8b")
    whole = counts.lm_prefill_flops(m, 0, 1056)
    suffix = counts.lm_prefill_flops(m, 1024, 32)
    per_token = sum(counts.lm_token_flops(m, c) for c in range(1025, 1057))
    assert suffix == per_token
    assert whole == sum(counts.lm_token_flops(m, c) for c in range(1, 1057))


def test_paged_decode_call_bytes_follow_the_valid_lengths():
    m = model("internlm2-1.8b")
    call = counts.paged_decode_call(m, [100, 300, 1])
    kv = 2 * 8 * 128 * 2 * 401            # K and V of the valid keys
    q_out = 2 * 3 * 16 * 128 * 2          # queries read, outputs written
    assert call["bytes"] == kv + q_out
    assert call["flops"] == 4 * 16 * 128 * 401
    t, bound = counts.least_time_s(call["flops"], call["bytes"],
                                   counts.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(call["bytes"] / 819e9)


def test_v5e_peaks_and_unknown_device():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with open(counts.PEAKS) as f:
        assert "Google Cloud" in json.load(f)["source"]
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
