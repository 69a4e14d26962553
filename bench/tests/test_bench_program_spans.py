"""The readers of the program's own spans and counters (first-token wait,
rows busy, pages valid, host-phase idle) on traced rehearsals of both
serving cells, and on the same records stripped of what an older program
does not record."""
import dataclasses
import time
import types

import pytest

from bench import harness
from bench.tests import rehearse

BACKLOG = "internlm2-1.8b.decode-backlog"
PREFIX = rehearse.SHARED_PREFIX
READERS = {"first_token_wait_p50_ms.prefix": PREFIX,
           "rows_busy_share.prefix": PREFIX,
           "pages_valid_share.backlog": BACKLOG,
           "host_idle_ms_per_chunk.backlog": BACKLOG}
NEW_SPANS = ("first_token", "admit_pass", "chunk_dispatch", "chunk_wait",
             "chunk_emit")
NEW_COUNTS = ("rows_busy", "n_rows", "tokens", "pages_valid", "pages_held")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return rehearse.tiny_tree(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def records(tree):
    """One traced run of each serving cell on the CPU."""
    rehearse.cpu_peaks()
    out = {}
    for cell in (PREFIX, BACKLOG):
        args = types.SimpleNamespace(seed=2**31 + 777, seconds=2.0, trace=1)
        out[cell] = harness.run_record(cell, args, time.perf_counter(),
                                       platform="cpu", bench_dir=tree,
                                       compile_cache=False)
    return out


def _read(tree, rec, metric):
    return harness.load_module("metrics", metric, tree).read(rec)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reports_in_its_cell(tree, records, metric):
    value = _read(tree, records[READERS[metric]], metric)
    assert value is not None and value >= 0
    if metric.endswith("share.prefix") or metric.endswith("share.backlog"):
        assert 0 < value <= 100


def test_backlog_metrics_are_in_the_traced_result(tree, records):
    rec = records[BACKLOG]
    got = harness.read_metrics(rec, rec.cell.per_layer, tree)
    assert {"pages_valid_share.backlog",
            "host_idle_ms_per_chunk.backlog"} <= set(got)


def test_chunk_counts_equal_the_benchmark_sample(records):
    """The program's counts at each chunk equal what the benchmark's
    client samples from the pool at that chunk's first delivery."""
    rec = records[BACKLOG]
    spans = [s for s in rec.data["spans"] if s.name == "decode_chunk"]
    assert len(spans) == len(rec.data["chunks"]) > 0
    for s, (_, lens, used, held) in zip(spans, rec.data["chunks"]):
        assert (s.attrs["rows_busy"], s.attrs["pages_valid"],
                s.attrs["pages_held"]) == (len(lens), used, held)


def _older_program(rec):
    """The record as a program without the first-token, phase and count
    spans would leave it."""
    spans = [dataclasses.replace(s, attrs={k: v for k, v in s.attrs.items()
                                           if k not in NEW_COUNTS})
             for s in rec.data["spans"] if s.name not in NEW_SPANS]
    return dataclasses.replace(rec, data={**rec.data, "spans": spans})


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_nothing_in_an_older_program(tree, records, metric):
    assert _read(tree, _older_program(records[READERS[metric]]),
                 metric) is None
