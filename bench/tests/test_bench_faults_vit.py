"""The ViT cell's check fails a broken timed path, and fails its control:
each run on four virtual CPU devices at reduced widths."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import rehearse

CELL = "vit-base-16.adaptive-4chip"


def rehearse_with(tmp_path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-m", "bench.tests.rehearse", CELL,
                        "0", str(tmp_path), *extra], capture_output=True,
                       text=True, env=env, cwd=rehearse.ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["no_exchange", "answer", "control"])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    out = rehearse_with(tmp_path, fault)
    assert out["correct"] is False
    c = out["checks"]["logit_rel_err"]
    assert c["value"] > c["limit"]
