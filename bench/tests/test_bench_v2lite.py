"""The DeepSeek-V2-Lite cell on the CPU at reduced widths: the serving loop
over latent pages and held experts against the plain float32 reference,
its end-to-end and per-layer metrics, the readers of the new kernels'
rooflines on a trace laid out as the chip's, and the configuration's
byte arithmetic against the program's parameter tree at full size."""
import dataclasses
import json
import time
import types

import jax
import numpy as np
import pytest

from bench import counts_mla_moe, harness
from bench.models import lm_mla_moe
from bench.tests import rehearse
from bench.trace_reduce import Device, Event, Trace

CELL = "deepseek-v2-lite.decode-backlog"
CONFIG = rehearse.ROOT / "bench" / "configs" / "deepseek-v2-lite.json"
# the published shape at CPU size: one dense layer and two MoE layers,
# two of the router's eight experts held, q projected directly, YaRN
TINY = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=2, router_outputs=8,
            num_experts_per_tok=2, n_shared_experts=1, vocab_size=512)
NEW_METRICS = {"decode_step_ms.v2lite", "latent_decode_roofline.v2lite",
               "expert_roofline.v2lite", "experts_touched_share.v2lite",
               "mfu.v2lite"}


def tiny_config(config):
    config["model"].update(TINY)
    config.update({k: v for k, v in TINY.items() if k in config})
    config["deployment"].update(n_pages=256, n_rows=8, max_len=256)
    return config


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    bench = rehearse.tiny_tree(tmp_path_factory.mktemp("bench"))
    path = bench / "configs" / "deepseek-v2-lite.json"
    path.write_text(json.dumps(tiny_config(json.loads(path.read_text()))))
    return bench


@pytest.fixture(scope="module")
def traced(tree):
    rehearse.cpu_peaks()
    args = types.SimpleNamespace(seed=2**31 + 1818, seconds=2.0, trace=1)
    return harness.run_record(CELL, args, time.perf_counter(),
                              platform="cpu", bench_dir=tree,
                              compile_cache=False)


def test_backlog_run_is_correct_and_reports_throughput(tree):
    out = rehearse.run(CELL, 0, tree)
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"output_tok_s", "tpot_p90_ms", "setup_s"}
    assert out["metrics"]["output_tok_s"]["value"] > 0


def test_traced_run_reports_the_program_metrics(tree, traced):
    got = harness.read_metrics(traced, traced.cell.per_layer, tree)
    assert {m["name"] for m in traced.cell.per_layer} == NEW_METRICS
    # the CPU runs the reference attention and XLA's CPU grouped products:
    # neither kernel is in the trace, so neither roofline reads anything
    assert set(got) == NEW_METRICS - {"latent_decode_roofline.v2lite",
                                      "expert_roofline.v2lite"}
    assert 0 < got["experts_touched_share.v2lite"]["value"] <= 100
    assert got["decode_step_ms.v2lite"]["value"] > 0
    assert 0 < got["mfu.v2lite"]["value"]


def test_chunk_spans_count_the_held_experts(traced):
    """Each traced chunk: experts_held = steps x MoE layers x held;
    touched within it; one pair per row and choice at most."""
    m = traced.data["model"]
    steps = traced.data["chunk_steps"]
    moe_layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    rows = 8
    spans = [s for s in traced.data["spans"] if s.name == "decode_chunk"]
    assert spans
    for s in spans:
        a = s.attrs
        assert a["experts_held"] == steps * moe_layers * m["n_routed_experts"]
        assert 0 <= a["experts_touched"] <= a["experts_held"]
        assert a["experts_touched"] <= a["expert_tokens"] <= \
            steps * moe_layers * rows * m["num_experts_per_tok"]


def _chip_like(rec, kernel_s: float, grouped_s: float):
    """The record with a device trace laid out as a TPU's: a decode
    program holding the latent kernel and the grouped products (names as
    XLA gives them on a v5e), and a grouped product outside it."""
    k, g = int(1e9 * kernel_s), int(1e9 * grouped_s)
    mods = [Event("jit_chunk(1)", 0, 10**8, {})]
    ops = [Event("flash_decode_latent_pallas.3", 10, 10 + k, {}),
           Event("ragged-dot-none.1", 20 + k, 20 + k + g, {}),
           Event("ragged-dot-none", 2 * 10**8, 3 * 10**8, {})]
    trace = Trace([Device(0, ops, mods)], [], 0, 10**9)
    return dataclasses.replace(rec, trace=trace,
                               device={**rec.device, "kind": "TPU v5 lite"})


def test_roofline_readers_on_a_chip_like_trace(tree, traced):
    """Both kernel rooflines: least time at the v5e peaks over the kernel
    time in the decode programs, between 0 and 100% when the kernel takes
    no less than its least time; a grouped product outside the decode
    programs is not counted."""
    m = traced.data["model"]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    lo, hi = traced.data["trace_window"]
    steps, L = traced.data["chunk_steps"], m["num_hidden_layers"]
    latent_bytes = sum(L * counts_mla_moe.latent_decode_call(
        m, [n + s + 1 for n in lens])["bytes"]
        for w, lens, _, _ in traced.data["chunks"] if lo <= w < hi
        for s in range(steps))
    spans = [s.attrs for s in traced.data["spans"]
             if s.name == "decode_chunk" and lo <= s.start < hi]
    expert_bytes = sum(a["experts_touched"] for a in spans) \
        * counts_mla_moe.expert_weight_bytes(m)
    t_latent = latent_bytes / peak["hbm_bytes_per_s"]
    t_expert = expert_bytes / peak["hbm_bytes_per_s"]
    rec = _chip_like(traced, 4 * t_latent, 2 * t_expert)
    latent = harness.load_module("metrics", "latent_decode_roofline.v2lite",
                                 tree).read(rec)
    expert = harness.load_module("metrics", "expert_roofline.v2lite",
                                 tree).read(rec)
    assert latent == pytest.approx(25.0, rel=1e-2)
    assert expert == pytest.approx(50.0, rel=1e-2)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_reader_finds_nothing_without_the_new_program(tree, traced, metric):
    """A program without held-expert counts, the latent kernel or the
    grouped products (the parent's) leaves every new metric but the
    decode programs' time and the model's share of the peak unread."""
    spans = [dataclasses.replace(s, attrs={
        k: v for k, v in s.attrs.items()
        if k not in ("experts_touched", "experts_held", "expert_tokens")})
        for s in traced.data["spans"]]
    rec = dataclasses.replace(_chip_like(traced, 0.0, 0.0),
                              data={**traced.data, "spans": spans})
    value = harness.load_module("metrics", metric, tree).read(rec)
    if metric in ("decode_step_ms.v2lite", "mfu.v2lite"):
        assert value is not None and value > 0
    else:
        assert value is None


def test_control_is_not_correct(tree):
    """The reference in fp8 in the program's place reads above the limit
    that the program keeps under."""
    rehearse.cpu_peaks()
    args = types.SimpleNamespace(seed=2**31 + 4343, seconds=2.0, trace=0)
    rec = harness.run_record(CELL, args, 0.0, platform="cpu",
                             bench_dir=tree, compile_cache=False)
    program, control = rec.checks[0], rec.recheck("fp8")
    assert program.ok and not control.ok


def test_configuration_counts_the_program_parameters():
    """The file's byte arithmetic: its parameter count is the program's
    tree at the file's sizes (full size, shapes only), and its bytes per
    latent token the pool's."""
    from repro.models import registry, transformer as tfm
    config = json.loads(CONFIG.read_text())
    m = config["model"]
    cfg = lm_mla_moe.program_config(config)
    tree = registry.abstract_params(cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert n == pytest.approx(4.91e9, rel=1e-3)
    L, D = m["num_hidden_layers"], m["hidden_size"]
    per_moe = (counts_mla_moe.attn_params(m) + 2 * D
               + m["kv_lora_rank"] + D * m["router_outputs"]
               + 3 * D * m["moe_intermediate_size"]
               * (m["n_routed_experts"] + m["n_shared_experts"]))
    layer0 = (counts_mla_moe.attn_params(m) + 2 * D + m["kv_lora_rank"]
              + 3 * D * m["intermediate_size"])
    assert n == (L - 1) * per_moe + layer0 + 2 * m["vocab_size"] * D + D
    pool = jax.eval_shape(lambda: tfm.init_page_pool(cfg, 2, 16))
    per_token = sum(x.dtype.itemsize * int(np.prod(x.shape[3:])) * x.shape[0]
                    for x in jax.tree_util.tree_leaves(pool))
    assert per_token == 27 * 1280


@pytest.fixture(scope="module")
def f32_model():
    """The tiny configuration's weights in float32, the program's config
    and parameters at float32, and a token sequence."""
    import dataclasses as dc
    config = tiny_config(json.loads(CONFIG.read_text()))
    m = config["model"]
    w = jax.tree_util.tree_map(lambda t: t.astype(np.float32),
                               lm_mla_moe.make_weights(m, 7))
    cfg = dc.replace(lm_mla_moe.program_config(config), dtype="float32")
    toks = np.random.RandomState(0).randint(0, m["vocab_size"], 40)
    return m, w, cfg, lm_mla_moe.program_params(w), toks


def _reference_logits(m, w, toks):
    with jax.default_matmul_precision("highest"):
        h = lm_mla_moe.hidden_states(w, m, jax.numpy.asarray(toks))
        return np.asarray(h @ w["lm_head"].T)


def test_program_forward_equals_the_reference_in_float32(f32_model):
    """At float32 the program's forward (latent attention in the
    expanded form, YaRN, 2 of 8 experts held) is the reference's."""
    from repro.api import ExecutionPlan
    from repro.models import transformer as tfm
    m, w, cfg, params, toks = f32_model
    with jax.default_matmul_precision("highest"):
        full, _ = tfm.forward_lm(params, {"tokens": toks[None]}, cfg,
                                 ExecutionPlan.local().to_exchange_config())
    want = _reference_logits(m, w, toks)
    np.testing.assert_allclose(np.asarray(full[0]), want, atol=1e-5)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_paged_latent_decode_equals_the_reference_in_float32(f32_model,
                                                             backend):
    """Token by token through the paged latent pool (absorbed form, the
    latent kernel in interpret mode under ``pallas``), scattered pages:
    each step's logits are the reference's full forward at that position."""
    import jax.numpy as jnp
    from repro.api import ExecutionPlan
    from repro.kernels import dispatch as kdsp
    from repro.models import transformer as tfm
    m, w, cfg, params, toks = f32_model
    xcfg = ExecutionPlan.local().to_exchange_config()
    pool = tfm.init_page_pool(cfg, 12, 8)
    table = jnp.asarray([[7, 2, 9, 0, 11]], jnp.int32)
    with kdsp.force_backend(backend), \
            jax.default_matmul_precision("highest"):
        step = jax.jit(lambda p, t, n: tfm.decode_step_paged(
            params, {"tokens": t}, p, table, n, cfg, xcfg)[:2])
        got = []
        for i, t in enumerate(toks):
            logits, pool = step(pool, jnp.asarray([[t]], jnp.int32),
                                jnp.asarray([i], jnp.int32))
            got.append(np.asarray(logits[0, 0]))
    np.testing.assert_allclose(np.stack(got), _reference_logits(m, w, toks),
                               atol=2e-5)
