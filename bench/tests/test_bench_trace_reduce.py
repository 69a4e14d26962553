"""The trace reduction on small recorded traces (``data/``): a CPU trace
of two devices, and TPU traces where recorded."""
import pathlib

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
