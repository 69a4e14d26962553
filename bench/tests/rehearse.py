"""Run benchmark cells on the CPU at reduced widths, from a throwaway copy
of the benchmark whose configuration and workload files are cut down.

    python -m bench.tests.rehearse <cell> <trace 0|1> <out dir> [fault]

(a separate process for cells on several chips: the CPU backend's device
count is fixed before JAX starts)."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY_MODELS = {
    "internlm2-1.8b": dict(num_hidden_layers=2, hidden_size=128,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=32, intermediate_size=256,
                           vocab_size=512),
    "vit-base-16": dict(num_hidden_layers=2, hidden_size=64,
                        num_attention_heads=4, intermediate_size=128),
}
TINY_DEPLOYMENT = {"internlm2-1.8b": dict(n_pages=256, n_rows=8, max_len=256)}
TINY_TRAFFIC = {
    "serve_open_loop": dict(system_prompt_len=64, suffix_lens=[16, 32],
                            suffix_weights=[0.6, 0.4], n_new=[12, 20],
                            n_new_weights=[0.5, 0.5], rate=10, ramp_s=0.5),
    "serve_backlog": dict(prompt_lens=[16, 32], n_new=[12, 20],
                          n_new_weights=[0.5, 0.5], prompt_weights=[0.5, 0.5],
                          block=8),
    "vit_closed_loop": dict(image_pool=8),
}
# limits at reduced widths: the tiny models' logits are some ten times
# smaller than the published models', and the program reads 0 and about
# 0.01 here (the full-size limits are set from chip runs, PERF.md)
TINY_CHECK = {"serve_open_loop": dict(limit=0.05),
              "serve_backlog": dict(limit=0.05),
              "vit_closed_loop": dict(limit=0.05)}


def tiny_tree(dest: pathlib.Path) -> pathlib.Path:
    """A copy of BENCHMARK.json (with the pending cells added) and the
    benchmark's files under ``dest``, every configuration and workload cut
    to CPU size; returns the copy's benchmark directory."""
    bench = dest / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__",
                                                  "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for path in (bench / "configs").glob("*.json"):
        c = json.loads(path.read_text())
        c["model"].update(TINY_MODELS.get(c["name"], {}))
        c["deployment"].update(TINY_DEPLOYMENT.get(c["name"], {}))
        path.write_text(json.dumps(c))
    for path in (bench / "workloads").glob("*.json"):
        w = json.loads(path.read_text())
        w["traffic"].update(TINY_TRAFFIC.get(w["driver"], {}))
        w["check"].update(TINY_CHECK.get(w["driver"], {}))
        w["trace_s"] = 1
        path.write_text(json.dumps(w))
    add_pending(bench)
    return bench


# cells whose loop, workload and metric files are in the benchmark but
# which are not in BENCHMARK.json (PERF.md says why): their entries as a
# later benchmark PR would add them, so the tests still run them
SHARED_PREFIX = "internlm2-1.8b.shared-prefix"
VIT = "vit-base-16.adaptive-4chip"


def _layer(name, unit, better, moves, cell):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "rehearsal", "moves": moves,
            "workloads": [cell]}


PENDING = {
    SHARED_PREFIX: {
        "config": {"name": "internlm2-1.8b"},
        "end_to_end": [("ttft_p90_ms", "ms", "lower"),
                       ("tpot_p90_ms", "ms", "lower")],
        "per_layer": [("queue_wait_p50_ms.prefix", "ms", "lower"),
                      ("prefix_hit_share.prefix", "%", "higher"),
                      ("prefill_device_ms.prefix", "ms", "lower"),
                      ("mfu.prefix", "%", "higher")],
        "chips": 1},
    VIT: {
        "config": {"name": "vit-base-16", "source": "arXiv:2010.11929",
                   "file": "bench/configs/vit-base-16.json", "reduced": [],
                   "why": "rehearsal"},
        "end_to_end": [("images_s", "images/s", "higher")],
        "per_layer": [("split_share.vit", "%", "higher"),
                      ("collective_share.vit", "%", "lower"),
                      ("segment_means_roofline.vit", "%", "higher"),
                      ("idle_share.vit", "%", "lower"),
                      ("mfu.vit", "%", "higher")],
        "chips": 4},
}


def add_pending(bench: pathlib.Path) -> None:
    """Add the pending cells to a throwaway tree's BENCHMARK.json."""
    path = bench.parent / "BENCHMARK.json"
    bm = json.loads(path.read_text())
    listed = {w["name"] for w in bm["workloads"]}
    for cell, e in PENDING.items():
        if cell in listed:
            continue
        config = e["config"]["name"]
        if all(c["name"] != config for c in bm["configs"]):
            bm["configs"].append(e["config"])
        bm["workloads"].append({"name": cell, "config": config,
                                "traffic": cell.split(".", 1)[1],
                                "chips": e["chips"], "why": "rehearsal"})
        moves = e["end_to_end"][0][0]
        for name, unit, better in e["end_to_end"]:
            old = next((m for m in bm["end_to_end"] if m["name"] == name),
                       None)
            if old is None:
                bm["end_to_end"].append(
                    {"name": name, "unit": unit, "better": better,
                     "bound": 0.25, "source": "host_clock",
                     "workloads": [cell]})
            elif "workloads" in old:
                old["workloads"].append(cell)
        bm["per_layer"] += [_layer(n, u, b, moves, cell)
                            for n, u, b in e["per_layer"]]
    path.write_text(json.dumps(bm))


def cpu_peaks() -> None:
    """Nominal peaks for the CPU, so traced rehearsals can compute shares
    (the committed table knows only the chips measured on)."""
    from bench import counts
    counts._peak_table()["devices"].setdefault(
        "cpu", {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def fault_token(vocab: int) -> None:
    """A token altered where it is produced: the decode chunk hands the
    client each row's first chunk token shifted by one."""
    from repro.serving import pages
    real = pages.PagedPool.decode_chunk

    def altered(self, n_steps):
        before = {i: len(a.tokens) for i, a in enumerate(self.slots)
                  if a is not None}
        wall = real(self, n_steps)
        for i, n in before.items():
            toks = self.slots[i].tokens
            if n == 0 and toks:
                toks[0] = (toks[0] + 1) % vocab
        return wall
    pages.PagedPool.decode_chunk = altered


def fault_stale_state() -> None:
    """A step that returns its state unchanged: the decode chunk's K/V
    writes are dropped (the pool comes back as it went in)."""
    from repro.api.session import InferenceSession
    real = InferenceSession.paged_decode_chunk

    def stale(self, pool, *args, **kw):
        import jax.numpy as jnp
        keep = [jnp.array(x, copy=True) for x in
                __import__("jax").tree_util.tree_leaves(pool)]
        toks, _, lengths, keys = real(self, pool, *args, **kw)
        tree = __import__("jax").tree_util.tree_structure(pool)
        return toks, tree.unflatten(keep), lengths, keys
    InferenceSession.paged_decode_chunk = stale


def fault_no_exchange() -> None:
    """The exchange between chips left out: every partition is handed its
    own segment means in place of the others'."""
    from repro.core import exchange
    import jax.numpy as jnp

    def local_only(x, axis_name, *, axis=0, tiled=False):
        n = __import__("jax").lax.psum(1, axis_name)
        if tiled:
            return jnp.concatenate([x] * n, axis=axis)
        return jnp.stack([x] * n, axis=axis)
    exchange.all_gather_grad_safe = local_only


def fault_answer() -> None:
    """An answer altered where it is produced: the first logit of every
    dispatched batch is shifted."""
    from repro.api.session import InferenceSession
    real = InferenceSession.dispatch

    def altered(self, batch_inputs, batch_size=None):
        out = real(self, batch_inputs, batch_size)
        return out.at[0, 0].add(1.0)
    InferenceSession.dispatch = altered


FAULTS = {"token": lambda: fault_token(TINY_MODELS["internlm2-1.8b"][
              "vocab_size"]),
          "stale_state": fault_stale_state,
          "no_exchange": fault_no_exchange,
          "answer": fault_answer}


def run(name: str, trace: int, bench: pathlib.Path, seconds: float = 2.0,
        seed: int = 2**31 + 12345) -> dict:
    """One run of cell ``name`` from ``bench`` on the CPU → its result."""
    from bench import harness
    cpu_peaks()
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    line = harness.run_cell(name, args, time.perf_counter(), platform="cpu",
                            bench_dir=bench, compile_cache=False)
    return json.loads(line)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    out = pathlib.Path(sys.argv[3])
    fault = sys.argv[4] if len(sys.argv) > 4 else ""
    if fault == "control":
        # the reference in fp8 in the program's place
        from bench import harness
        cpu_peaks()
        args = types.SimpleNamespace(seed=2**31 + 99, seconds=2.0, trace=0)
        rec = harness.run_record(sys.argv[1], args, time.perf_counter(),
                                 platform="cpu", bench_dir=tiny_tree(out),
                                 compile_cache=False)
        rec.checks = [rec.recheck("fp8")]
        print(harness.result_line(rec, {}))
        sys.exit(0)
    if fault:
        FAULTS[fault]()
    result = run(sys.argv[1], int(sys.argv[2]), tiny_tree(out))
    print(json.dumps(result))
