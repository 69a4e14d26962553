"""The trace reduction on small recorded traces (``data/``): a CPU trace
of two devices, and TPU traces where recorded."""
import pathlib
import types

import pytest

from bench import trace_reduce as T

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_interval_union():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert T.covered_ns([(0, 10), (2, 4), (12, 13)]) == 11


def test_names():
    assert T.op_name("%fusion.12 = f32[8]{0} fusion(...)") == "fusion.12"
    assert T.op_name("jit_chunk(1046534)") == "jit_chunk(1046534)"
    assert T.base_name("flash_decode_paged_pallas.14") == \
        "flash_decode_paged_pallas"
    assert T.base_name("jit_pf(3454421210361394139)") == "jit_pf"
    assert T.base_name("all-gather-start.3") == "all-gather-start"


def test_cpu_trace():
    t = T.load(str(DATA / "cpu_2dev.xplane.pb"))
    # the window is the span between the anchor annotations
    assert 0.03 < t.window_s < 0.1
    busy = t.busy_s()
    assert 0 < busy < t.window_s
    assert t.idle_share() == pytest.approx(1 - busy / t.window_s)
    d = t.devices[0]
    assert [T.base_name(m.name) for m in d.modules] == ["jit_body"] * 2
    assert 0 < t.collective_s() <= busy
    names = [n for n, _ in t.top_ops()]
    assert names[0] == "all_gather" and len(names) <= 10
    gaps = t.idle_gaps(3)
    # the two 20 ms sleeps are the longest gaps, named by the host span
    # around them
    assert [g[1] > 0.018 for g in gaps[:2]] == [True, True]
    assert all("sleep" in g[0] or g[0] == "bench.test_gap" for g in gaps[:2])


def test_host_spans_map_onto_the_trace_clock():
    t = T.load(str(DATA / "cpu_2dev.xplane.pb"))
    t.offset = 1_000
    assert t.at(2.0) == 2_000_001_000


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        T.find_xplane(str(tmp_path))
    p = tmp_path / "plugins" / "profile" / "x" / "h.xplane.pb"
    p.parent.mkdir(parents=True)
    p.write_bytes(b"")
    assert T.find_xplane(str(tmp_path)) == str(p)


def _plane(name, lines):
    """A profiler plane: ``lines`` maps a line's name to its events,
    each (name, start ns, duration ns)."""
    ns = types.SimpleNamespace
    return ns(name=name, lines=[
        ns(name=ln, events=[ns(name=e, start_ns=s, duration_ns=d, stats=())
                            for e, s, d in evs])
        for ln, evs in lines.items()])


@pytest.mark.parametrize("empty", [{}, {"XLA Ops": [], "XLA Modules": []}],
                         ids=["no lines", "lines without events"])
def test_four_devices_average_and_an_empty_one_is_idle(empty):
    """Busy time is averaged over every device plane of the trace: a chip
    on which nothing ran counts as idle, not as absent."""
    host = _plane("/host:CPU", {"python": [(T.OPEN, 0, 10),
                                           (T.CLOSE, 990, 10)]})
    busy = [_plane(f"/device:TPU:{i}", {
        "XLA Ops": [("fusion.1", 100, 300), ("all-gather.2", 350, 150),
                    ("fusion.3", 2_000, 50)],      # after the window
        "XLA Modules": [("jit_step(7)", 100, 400)]}) for i in range(3)]
    t = T.from_planes([host, *busy, _plane("/device:TPU:3", empty)])
    assert [d.index for d in t.devices] == [0, 1, 2, 3]
    assert t.window_s == pytest.approx(1000e-9)
    assert t.busy_s() == pytest.approx(3 * 400e-9 / 4)
    assert t.idle_share() == pytest.approx(1 - 0.3)
    assert t.busy_shares() == pytest.approx([0.4, 0.4, 0.4, 0.0])


@pytest.mark.parametrize("name,collective", [
    ("all-gather", True), ("all-gather-start", True), ("all-reduce", True),
    ("async-collective-start", True), ("async-collective-done", True),
    ("collective-permute-done", True), ("fusion", False),
    ("copy-start", False), ("segment_means_pallas", False)])
def test_collectives_include_the_async_parts_of_an_all_gather(name,
                                                              collective):
    assert bool(T.COLLECTIVE.search(name)) is collective


def test_with_the_host_tracer_off_the_wall_clock_places_the_window(
        monkeypatch):
    """No anchors are recorded without the host tracer: the window is
    where the wall clock read at its open and close falls on the trace's
    clock, whose 0 is the profile's start."""
    epoch = 1_700_000_000_000_000_000
    env = types.SimpleNamespace(name="Task Environment", lines=[],
                                stats=[("profile_start_time", epoch)])
    dev = _plane("/device:TPU:0", {"XLA Ops": [("fusion.1", 1_000, 500),
                                               ("fusion.2", 9_000, 500)]})
    monkeypatch.setattr(T, "find_xplane", lambda log_dir: log_dir)
    monkeypatch.setattr(T, "load", lambda path: T.from_planes([env, dev]))
    p = T.Profile("unused", clock=None)
    p.t_open, p.t_close = 5.0, 5.000002
    p.wall_open, p.wall_close = epoch + 800, epoch + 2_800
    t = p.reduce([("vit.batch", 5.0000002, 5.0000009)])
    assert (t.lo, t.hi) == (800, 2_800)
    assert t.busy_s() == pytest.approx(500e-9)
    assert t.at(5.0) == 800
    assert [h.name for h in t.host] == ["vit.batch"]
    assert (t.host[0].start, t.host[0].end) == (1_000, 1_700)
