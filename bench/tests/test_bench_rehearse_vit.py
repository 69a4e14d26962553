"""The closed-loop ViT loop on four virtual CPU devices at reduced
widths: the policy splits the large batches across the mesh."""
import json
import os
import subprocess
import sys

from bench.tests import rehearse

CELL = "vit-base-16.adaptive-4chip"


def rehearse_4(tmp_path, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-m", "bench.tests.rehearse", CELL,
                        str(trace), str(tmp_path)], capture_output=True,
                       text=True, env=env, cwd=rehearse.ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_closed_loop_run(tmp_path):
    out = rehearse_4(tmp_path, 0)
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"images_s", "setup_s"}
    assert out["device"]["count"] == 4


def test_traced_run(tmp_path):
    out = rehearse_4(tmp_path, 1)
    assert out["correct"] is True
    m = out["metrics"]
    # the policy splits batches of 8 and more: (8 + 16 + 32) / 63 of images
    assert abs(m["split_share.vit"]["value"] - 100 * 56 / 63) < 10
    assert m["collective_share.vit"]["value"] > 0
    assert {"idle_share.vit", "mfu.vit"} <= set(m)
    # the CPU runs the segment-means reference, not the kernel: no reading
    assert "segment_means_roofline.vit" not in m
