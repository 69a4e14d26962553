"""The serving cell's check fails a broken timed path, and fails its
control: each run on the CPU at reduced widths, in its own process."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import rehearse

CELL = "internlm2-1.8b.decode-backlog"


def rehearse_with(tmp_path, fault):
    p = subprocess.run([sys.executable, "-m", "bench.tests.rehearse", CELL,
                        "0", str(tmp_path), fault], capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=rehearse.ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["token", "stale_state"])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    out = rehearse_with(tmp_path, fault)
    assert out["correct"] is False
    c = out["checks"]["widest_logit_gap"]
    assert c["value"] > c["limit"]


def test_control_is_not_correct(tmp_path):
    """The reference in fp8 in the program's place reads above the limit
    that the program itself keeps under."""
    from bench import harness
    tree = rehearse.tiny_tree(tmp_path)
    rehearse.cpu_peaks()
    args = type("A", (), {"seed": 2**31 + 4242, "seconds": 2.0,
                          "trace": 0})()
    rec = harness.run_record(CELL, args, 0.0, platform="cpu",
                             bench_dir=tree, compile_cache=False)
    program, control = rec.checks[0], rec.recheck("fp8")
    assert program.ok and not control.ok
    assert control.value > 3 * max(program.value, 1e-3)
