"""Shared plumbing of the benchmark: cells, seeds, devices, metrics, result.

Everything a cell needs is found by name: ``BENCHMARK.json`` (at the root
of the checkout) names the cell's configuration, chips and metrics;
``workloads/<cell>.json`` holds the loop kind and every traffic
parameter; ``configs/<config>.json`` the model and its deployment;
``drivers/<kind>.py`` the loop; ``metrics/<metric>.py`` one reader per
metric.  A new cell of an existing loop kind is one JSON file.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
# fixed path inside the checkout: the path is part of the cache's key
COMPILE_CACHE = BENCH / ".jax_cache"


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    workload: Dict[str, Any]          # workloads/<name>.json
    config: Dict[str, Any]            # configs/<config>.json
    end_to_end: List[Dict[str, Any]]  # BENCHMARK.json metrics of this cell
    per_layer: List[Dict[str, Any]]


def load_cell(name: str, benchmark: Optional[Dict[str, Any]] = None,
              bench_dir: pathlib.Path = BENCH) -> Cell:
    """The cell ``name`` with its workload, configuration and metrics.

    A cell's end-to-end metrics are those that list it (or list no cells);
    its per-layer metrics are those that list it, or, without a list,
    those whose ``moves`` metric the cell reports."""
    if benchmark is None:
        benchmark = load_json(bench_dir.parent / "BENCHMARK.json")
    entry = next((w for w in benchmark["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        known = sorted(w["name"] for w in benchmark["workloads"])
        raise SystemExit(f"unknown workload {name!r}; known: {known}")
    workload = load_json(bench_dir / "workloads" / f"{name}.json")
    config = load_json(bench_dir / "configs" / f"{entry['config']}.json")
    if workload.get("config", entry["config"]) != entry["config"]:
        raise SystemExit(f"{name}: workload file names config "
                         f"{workload['config']!r}, BENCHMARK.json "
                         f"{entry['config']!r}")

    def listed(m):
        return "workloads" not in m or name in m["workloads"]
    e2e = [m for m in benchmark["end_to_end"] if listed(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(entry["chips"]), workload=workload,
                config=config, end_to_end=e2e, per_layer=per_layer)


def load_module(kind: str, name: str, bench_dir: pathlib.Path = BENCH):
    """``<bench>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = bench_dir / kind / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"no {kind[:-1]} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def rng(seed: int, *salt: int) -> np.random.Generator:
    """Host generator for ``seed`` (any size) and a stream tag."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *salt])


def key_int(seed: int, *salt: int) -> int:
    """A 31-bit integer for ``jax.random.key`` drawn from ``seed``."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), *salt])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------------

def require_chips(n: int, platform: str = "tpu"):
    """The first ``n`` devices, or exit non-zero: a measurement never
    falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"bench: no {platform.upper()} (JAX found "
                     f"{devs[0].platform})")
    if len(devs) < n:
        raise NoChip(f"bench: the cell needs {n} chips, JAX found "
                     f"{len(devs)}")
    return devs[:n]


def configure_compile_cache(path: pathlib.Path = COMPILE_CACHE) -> None:
    """JAX's persistent compile cache at a fixed path in the checkout,
    holding every program, so a second run compiles nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(devices) -> Dict[str, Any]:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:  # noqa: BLE001 - backends without stats
            peaks.append(0)
    return max(peaks)


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile (``q`` in [0, 100]); None if empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# the run's record, and the metrics read from it
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, with its limit (pass: <=)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class RunRecord:
    """What a loop hands the metric readers.  ``data`` holds the
    loop's host-clock records; ``trace`` the reduced device trace (traced
    runs only).  ``recheck(precision)`` repeats the check with the
    reference in the program's place at that precision (the control)."""
    cell: Cell
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    checks: List[Check]
    device: Dict[str, Any]
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Optional[Any] = None
    recheck: Optional[Any] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def read_metrics(rec: RunRecord, specs: List[Dict[str, Any]],
                 bench_dir: pathlib.Path = BENCH) -> Dict[str, Any]:
    """Each metric's reader applied to the record; a reader that finds
    nothing to read returns None and its metric is left out."""
    out: Dict[str, Any] = {}
    for m in specs:
        value = load_module("metrics", m["name"], bench_dir).read(rec)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(rec: RunRecord, metrics: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    doc: Dict[str, Any] = {
        "correct": rec.correct, "attempted": int(rec.attempted),
        "failed": int(rec.failed), "metrics": metrics,
        "device": rec.device}
    if breakdown is not None:
        doc["breakdown"] = breakdown
    doc["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in rec.checks}
    return json.dumps(doc)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def print_checks(checks: List[Check]) -> None:
    """The numbers compared, each beside its limit: the last lines of
    standard error."""
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")


def run_record(name: str, args, t_start: float, platform: str = "tpu",
               bench_dir: pathlib.Path = BENCH,
               compile_cache: bool = True) -> RunRecord:
    """Run one cell once (``args``: seed, seconds, trace) → its record.
    Raises ``NoChip`` when the devices are not there."""
    cell = load_cell(name, bench_dir=bench_dir)
    if compile_cache:
        configure_compile_cache(bench_dir / ".jax_cache")
    devices = require_chips(cell.chips, platform)
    driver = load_module("drivers", cell.workload["driver"], bench_dir)
    return driver.run(cell, args, t_start, devices)


def run_cell(name: str, args, t_start: float, platform: str = "tpu",
             bench_dir: pathlib.Path = BENCH,
             compile_cache: bool = True) -> str:
    """Run one cell once and return its result line."""
    rec = run_record(name, args, t_start, platform, bench_dir, compile_cache)
    breakdown = None
    if args.trace:
        rec.device["busy_s"] = rec.trace.busy_s()
        rec.device["window_s"] = rec.trace.window_s
        breakdown = {"device_ops": rec.trace.top_ops(),
                     "idle_gaps": rec.trace.idle_gaps()}
    metrics = read_metrics(rec, rec.cell.per_layer if args.trace
                           else rec.cell.end_to_end, bench_dir)
    print_checks(rec.checks)
    return result_line(rec, metrics, breakdown)


class CompileCounter:
    """Counts the programs JAX traces or compiles between ``start`` and
    ``stop``: inside a measured window there should be none."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        self.names: List[str] = []

    def _event(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def start(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def stop(self) -> None:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._event)


class GcWatch:
    """Times Python's garbage collections between ``start`` and ``stop``:
    a full collection scans every object the process holds, and a long
    one stalls the loop."""

    def __init__(self):
        self.passes: List[tuple] = []      # (generation, seconds)
        self._t0 = 0.0

    def _callback(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.passes.append((info["generation"],
                                time.perf_counter() - self._t0))

    def start(self) -> None:
        gc.callbacks.append(self._callback)

    def stop(self) -> None:
        gc.callbacks.remove(self._callback)

    def summary(self) -> str:
        """The window's collections, and what one full collection takes
        now (timed here, after the window)."""
        full = [t for g, t in self.passes if g == 2]
        longest = max((t for _, t in self.passes), default=0.0)
        t0 = time.perf_counter()
        gc.collect()
        now = time.perf_counter() - t0
        return (f"gc: {len(self.passes)} collections in the window, "
                f"{len(full)} full, longest {1e3 * longest:.1f} ms, all "
                f"{1e3 * sum(t for _, t in self.passes):.1f} ms; a full "
                f"collection after it {1e3 * now:.1f} ms")
