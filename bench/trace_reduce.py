"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``: one plane per device
(``/device:TPU:<n>``) whose ``XLA Ops`` line holds every operation that
ran on the device and whose ``XLA Modules`` line holds every program, and
host planes whose lines hold the host's spans (``TraceAnnotation``s and
the Python calls into jitted functions).  A trace with no device plane
(the CPU backend) takes its operations from the host events that carry an
``hlo_op`` stat, and its programs from their ``hlo_module`` stat.

Numbers, per device: busy time (the union of operation intervals), time
per program and per operation name, time in collectives, and the idle
gaps, each named by the innermost host span that covers its middle.
Times are in seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
# a TPU runs an all-gather in part as ``async-collective-start``/``-done``
COLLECTIVE = re.compile(
    r"all[-_]?gather|all[-_]?reduce|reduce[-_]?scatter|"
    r"collective[-_]?permute|all[-_]?to[-_]?all|async[-_]?collective",
    re.IGNORECASE)
_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")
CONTAINERS = ("while", "conditional", "call")
# a TPU trace names each operation by its HLO text: "%fusion.12 = f32[..."
_HLO_NAME = re.compile(r"^%?([^\s=]+) = ")

Interval = Tuple[int, int]          # [start_ns, end_ns)
# host annotations that open and close the traced window (``Profile``)
OPEN, CLOSE = "bench.trace_open", "bench.trace_close"


def base_name(name: str) -> str:
    """An operation or program name without XLA's numeric suffix
    (``fusion.12`` → ``fusion``, ``jit_chunk(3)`` → ``jit_chunk``)."""
    return _SUFFIX.sub("", name)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered_ns(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int
    stats: Dict[str, object]


@dataclasses.dataclass
class Device:
    index: int
    ops: List[Event]
    modules: List[Event]

    def busy_ns(self, lo: int, hi: int) -> int:
        return covered_ns([(max(e.start, lo), min(e.end, hi))
                           for e in self.ops if e.end > lo and e.start < hi])

    def op_time_s(self, match) -> float:
        """Seconds of the operations whose name ``match`` accepts (the
        union of their intervals: nested events count once)."""
        return 1e-9 * covered_ns([(e.start, e.end) for e in self.ops
                                  if match(e.name)])

    def module_time_s(self, match) -> float:
        return 1e-9 * covered_ns([(e.start, e.end) for e in self.modules
                                  if match(e.name)])

    def module_calls(self, match) -> int:
        return sum(1 for e in self.modules if match(e.name))


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]            # host spans (annotations, jitted calls)
    lo: int                      # traced window [lo, hi) in ns
    hi: int
    offset: int = 0              # trace ns = host seconds * 1e9 + offset
    epoch_ns: Optional[int] = None   # wall clock (ns) at the trace's 0

    def at(self, host_s: float) -> int:
        """A host-clock time (``Profile``'s clock) on the trace's clock."""
        return int(1e9 * host_s) + self.offset

    @property
    def window_s(self) -> float:
        return 1e-9 * (self.hi - self.lo)

    def busy_s(self) -> float:
        """Device-busy seconds, averaged over the devices."""
        return 1e-9 * sum(d.busy_ns(self.lo, self.hi)
                          for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def busy_shares(self) -> List[float]:
        """Each device's busy share of the traced window."""
        return [d.busy_ns(self.lo, self.hi) / (self.hi - self.lo)
                for d in self.devices]

    def collective_s(self, device: int = 0) -> float:
        return self.devices[device].op_time_s(
            lambda n: bool(COLLECTIVE.search(n)))

    def top_ops(self, k: int = 10, device: int = 0) -> List[List]:
        """The ``k`` operation names (suffix stripped) with the most device
        time on one device; control flow (``while``, ``conditional``,
        ``call``), whose events hold others, is left out."""
        tot: Dict[str, int] = {}
        for e in self.devices[device].ops:
            if base_name(e.name) in CONTAINERS:
                continue
            tot[base_name(e.name)] = tot.get(base_name(e.name), 0) + (
                e.end - e.start)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, 1e-9 * ns] for n, ns in top]

    def idle_gaps(self, k: int = 10, device: int = 0) -> List[List]:
        """The ``k`` longest idle gaps of one device, each named by the
        innermost host span covering its middle (``"(no host span)"``)."""
        busy = union([(e.start, e.end) for e in self.devices[device].ops])
        gaps, t = [], self.lo
        for s, e in busy:
            if s > t:
                gaps.append((t, min(s, self.hi)))
            t = max(t, e)
        if t < self.hi:
            gaps.append((t, self.hi))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in gaps:
            mid = (s + e) // 2
            cover = [h for h in self.host if h.start <= mid < h.end]
            name = (min(cover, key=lambda h: h.end - h.start).name
                    if cover else "(no host span)")
            out.append([name, 1e-9 * (e - s)])
        return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(text: str) -> str:
    """The instruction name of an HLO text (``%fusion.12 = ...`` →
    ``fusion.12``); other names unchanged."""
    m = _HLO_NAME.match(text)
    return m.group(1) if m else text


def _events(line, stats: bool = False) -> List[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(op_name(e.name), start, start + int(e.duration_ns),
                         dict(e.stats) if stats else {}))
    return out


def load(path: str, window: Optional[Tuple[int, int]] = None) -> Trace:
    """Reduce one ``.xplane.pb``.  ``window`` (ns, the trace's clock)
    bounds the traced window; by default the ``OPEN``/``CLOSE``
    annotations do, or else every event."""
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(path).planes, window)


def from_planes(planes, window: Optional[Tuple[int, int]] = None) -> Trace:
    """The trace of the profiler's planes (each with a ``name`` and
    ``lines``, a line with a ``name`` and ``events``).  Every device plane
    counts, one that holds no operation as idle all through."""
    devices: List[Device] = []
    host_lines = []
    epoch = None
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
            mods = (_events(lines["XLA Modules"]) if "XLA Modules" in lines
                    else [])
            devices.append(Device(int(m.group(1)), ops, mods))
        elif plane.name.startswith("/host:"):
            host_lines.extend(plane.lines)
        elif plane.name == "Task Environment":
            epoch = dict(plane.stats).get("profile_start_time")
    host: List[Event] = []
    cpu_ops: List[Event] = []
    for ln in host_lines:
        for ev in _events(ln, stats=not devices):
            if "hlo_op" in ev.stats:
                cpu_ops.append(ev)
            elif ev.end > ev.start:
                host.append(ev)
    if not devices and cpu_ops:
        spans: Dict[Tuple, List[int]] = {}
        for ev in cpu_ops:
            key = (str(ev.stats.get("hlo_module", "")),
                   ev.stats.get("run_id"))
            span = spans.setdefault(key, [ev.start, ev.end])
            span[0], span[1] = min(span[0], ev.start), max(span[1], ev.end)
        devices.append(Device(0, cpu_ops, [Event(k[0], s, e, {})
                                           for k, (s, e) in spans.items()]))
    devices.sort(key=lambda d: d.index)
    anchors = {e.name: e for e in host if e.name in (OPEN, CLOSE)}
    if window is None and len(anchors) == 2:
        window = (anchors[OPEN].start, anchors[CLOSE].end)
    if window is None:
        every = [e for d in devices for e in d.ops + d.modules] + host
        window = (min(e.start for e in every), max(e.end for e in every))
    return Trace(devices, host, int(window[0]), int(window[1]),
                 epoch_ns=None if epoch is None else int(epoch))


class Profile:
    """The profiler over one window of a run: ``start``/``stop`` write the
    anchor annotations, ``reduce`` reads the trace back.  ``spans`` maps
    host-clock spans (seconds, ``time.perf_counter``) onto the trace's
    clock, so the program's own spans can name idle gaps too.

    ``options`` (a workload's ``"profile"``) sets the profiler's
    ``host_tracer_level`` and ``python_tracer_level``.  A host tracer that
    records every small host event can slow a host-bound loop several
    times over while it runs, and the traced window then stands for
    nothing else.  With the host tracer off (level 0) the anchors are not
    recorded, and the wall clock, read beside the host clock, places the
    window on the trace's clock (whose 0 is the profile's start time)."""

    def __init__(self, log_dir: str, clock,
                 options: Optional[Dict[str, int]] = None):
        self.log_dir, self.clock, self.options = log_dir, clock, options
        self.t_open = self.t_close = None

    def start(self) -> None:
        import jax
        opts = None
        if self.options:
            opts = jax.profiler.ProfileOptions()
            for k, v in self.options.items():
                setattr(opts, k, v)
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(OPEN):
            self.t_open = self.clock()
            self.wall_open = time.time_ns()

    def stop(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(CLOSE):
            self.t_close = self.clock()
            self.wall_close = time.time_ns()
        jax.profiler.stop_trace()

    def reduce(self, spans: Sequence[Tuple[str, float, float]] = ()
               ) -> Trace:
        trace = load(find_xplane(self.log_dir))
        anchor = next((e for e in trace.host if e.name == OPEN), None)
        if anchor is not None:
            opened = anchor.start
        else:                        # host tracer off: the wall clock
            opened = self.wall_open - trace.epoch_ns
            trace.lo, trace.hi = opened, self.wall_close - trace.epoch_ns
        trace.offset = opened - int(1e9 * self.t_open)
        for name, s, e in spans:
            trace.host.append(Event(name, trace.at(s), trace.at(e), {}))
        return trace
