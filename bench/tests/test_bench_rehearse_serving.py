"""The serving loops end to end on the CPU at reduced widths, and the
harness finding a new cell by name."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import rehearse

CELL = rehearse.SHARED_PREFIX


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return rehearse.tiny_tree(tmp_path_factory.mktemp("bench"))


def test_open_loop_run_reports_end_to_end_metrics(tree):
    out = rehearse.run(CELL, 0, tree)
    assert out["correct"] is True
    assert out["attempted"] == 20 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["widest_logit_gap"]["value"] <= \
        out["checks"]["widest_logit_gap"]["limit"]


def test_traced_run_reports_per_layer_metrics(tree):
    out = rehearse.run(CELL, 1, tree)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "queue_wait_p50_ms.prefix", "prefix_hit_share.prefix",
        "prefill_device_ms.prefix", "mfu.prefix",
        "first_token_wait_p50_ms.prefix", "rows_busy_share.prefix"}
    assert out["metrics"]["prefix_hit_share.prefix"]["value"] == 100.0
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_a_new_cell_is_one_workload_file(tree):
    """A mix of an existing loop kind: one JSON file and its entry in
    BENCHMARK.json; no code changes."""
    name = "internlm2-1.8b.throwaway"
    w = json.loads((tree / "workloads" / f"{CELL}.json").read_text())
    w["traffic"].update(rate=4, zipf_s=0.0)
    (tree / "workloads" / f"{name}.json").write_text(json.dumps(w))
    bm_path = tree.parent / "BENCHMARK.json"
    bm = json.loads(bm_path.read_text())
    bm["workloads"].append({"name": name, "config": "internlm2-1.8b",
                           "traffic": "throwaway", "chips": 1,
                           "why": "rehearsal"})
    for m in bm["end_to_end"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(name)
    bm_path.write_text(json.dumps(bm))
    out = rehearse.run(name, 0, tree)
    assert out["correct"] is True and out["attempted"] == 8
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert "internlm2-1.8b.throwaway" not in json.loads(
        (rehearse.ROOT / "BENCHMARK.json").read_text())["workloads"]


STUB_REFERENCE = '''\
"""A throwaway reference: the dense decoder's, recording its calls."""
import pathlib

from bench.models import lm_dense
from bench.models.lm_dense import program_config, program_params

CALLS = pathlib.Path(__file__).with_suffix(".calls")


def _called(name):
    with open(CALLS, "a") as f:
        f.write(name + "\\n")


def make_weights(m, key_int):
    _called("make_weights")
    return lm_dense.make_weights(m, key_int)


def served_gaps(*args):
    _called("served_gaps")
    return lm_dense.served_gaps(*args)
'''


def test_a_new_configuration_brings_its_reference_by_name(tree):
    """A configuration file names its reference module, and the serving
    loop takes weights, program sizes and the check from that module:
    one configuration file, one module, one workload file and their
    entries in BENCHMARK.json; no code changes."""
    cell = "stub-lm.backlog"
    config = json.loads((tree / "configs" / "internlm2-1.8b.json").read_text())
    config.update(name="stub-lm", reference="stub_lm")
    (tree / "configs" / "stub-lm.json").write_text(json.dumps(config))
    (tree / "models" / "stub_lm.py").write_text(STUB_REFERENCE)
    w = json.loads((tree / "workloads" /
                    "internlm2-1.8b.decode-backlog.json").read_text())
    w["config"] = "stub-lm"
    (tree / "workloads" / f"{cell}.json").write_text(json.dumps(w))
    bm_path = tree.parent / "BENCHMARK.json"
    bm = json.loads(bm_path.read_text())
    bm["configs"].append({"name": "stub-lm", "source": "rehearsal",
                          "file": "bench/configs/stub-lm.json",
                          "reduced": [], "why": "rehearsal"})
    bm["workloads"].append({"name": cell, "config": "stub-lm",
                            "traffic": "backlog", "chips": 1,
                            "why": "rehearsal"})
    for m in bm["end_to_end"]:
        if "internlm2-1.8b.decode-backlog" in m.get("workloads", []):
            m["workloads"].append(cell)
    bm_path.write_text(json.dumps(bm))
    out = rehearse.run(cell, 0, tree)
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"output_tok_s", "tpot_p90_ms", "setup_s"}
    calls = (tree / "models" / "stub_lm.calls").read_text().split()
    assert calls[0] == "make_weights" and calls.count("make_weights") == 1
    assert 1 <= calls.count("served_gaps") <= w["check"]["sample"]
    assert not (rehearse.ROOT / "bench" / "models" / "stub_lm.py").exists()


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(rehearse.ROOT / "bench" / "run.py"),
         "--workload", "internlm2-1.8b.decode-backlog",
         "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=rehearse.ROOT, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_backlog_run_reports_throughput(tree):
    out = rehearse.run("internlm2-1.8b.decode-backlog", 0, tree)
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"output_tok_s", "tpot_p90_ms", "setup_s"}
    assert out["metrics"]["output_tok_s"]["value"] > 0


def test_backlog_traced_run(tree):
    out = rehearse.run("internlm2-1.8b.decode-backlog", 1, tree)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"pages_valid_share.backlog", "decode_step_ms.backlog",
            "idle_share.backlog", "mfu.backlog"} <= set(m)
    assert 0 < m["pages_valid_share.backlog"]["value"] <= 100
    # the CPU runs the paged decode reference, not the kernel: no reading
    assert "paged_decode_roofline.backlog" not in m
