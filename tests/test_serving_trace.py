"""The serving runtime's own account of a request and of a decode chunk:
the first-token stamp, the request leaves with ``first_token`` among
them, the host phases of each chunk and the counts it carries, the
admission pass, and the histograms read from them."""
import numpy as np
import pytest

from repro.api import ExecutionPlan, InferenceSession
from repro.obs import STAGES, Tracer, build_tree, request_breakdown
from repro.serving import ServingRuntime

PAGED = dict(page_size=8, n_pages=24, n_rows=4)
BASE = list(np.random.RandomState(42).randint(1, 64, 13))   # unaligned tail
# (pool, requests served first, the prompt under test, pool stat it hits)
CASES = {
    "dense-miss": ({}, [], BASE, None),
    "paged-miss": (PAGED, [], BASE, "prefix_misses"),
    "paged-full-hit": (PAGED, [BASE], BASE, "full_hits"),
    "paged-partial-hit": (PAGED, [BASE], BASE + [7, 3, 9], "partial_hits"),
}
NEW_STAGES = ("first_token", "admit_pass", "chunk_dispatch", "chunk_wait",
              "chunk_emit")


@pytest.fixture(scope="module")
def session():
    s = InferenceSession.from_config(
        "llama3.2-1b", reduced={"vocab_size": 64},
        plans=[ExecutionPlan.local()])
    s.profile(backend="simulated")
    return s


class Ticker:
    """A clock that moves one second at every read, so no two stamps
    coincide; ``now`` is the last reading, read without moving it."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1.0
        return self.now


def _runtime(session, pool, traced, clock):
    return ServingRuntime(session, n_slots=2, chunk=4, max_len=32,
                          clock=clock,
                          tracer=Tracer("t", clock=clock) if traced else None,
                          **pool)


def _serve_case(session, case, traced):
    """Serve the case's warm-up requests, then its request under test with
    a client that stamps its first delivery."""
    pool, warm, prompt, stat = CASES[case]
    clock = Ticker()
    rt = _runtime(session, pool, traced, clock)
    for i, p in enumerate(warm):
        rt.submit(p, 5, seed=10 + i)
    rt.run()
    before = (next(iter(rt.pools.values())).stats[stat]
              if warm and stat else 0)
    first = {}
    rt.on_progress = lambda rid, toks: first.setdefault(rid, clock.now)
    req = rt.submit(prompt, 6, seed=3)
    [comp] = rt.run()
    if stat:
        assert next(iter(rt.pools.values())).stats[stat] == before + 1
    return rt, req, comp, first


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("case", sorted(CASES))
def test_first_token_stamp_is_the_first_delivery(session, case, traced):
    rt, req, comp, first = _serve_case(session, case, traced)
    assert comp.request_id == req.id
    assert comp.first_token_ts == first[req.id]
    assert comp.admitted_ts < comp.first_token_ts < comp.finished_ts


@pytest.mark.parametrize("case", sorted(CASES))
def test_request_leaves_partition_latency(session, case):
    """Leaves of the request tree run back to back from arrival to
    finish: each gap between them is at most the one clock read that
    separates a span's end from the next one's start."""
    rt, req, comp, _ = _serve_case(session, case, traced=True)
    spans = rt.tracer.trace(req.trace_id)
    tree = build_tree(spans)
    leaves = sorted((s for s in spans if s.span_id not in tree),
                    key=lambda s: s.start)
    names = [s.name for s in leaves]
    assert names[0] == "queue_wait" and names[-2:] == ["first_token",
                                                       "decode"]
    assert leaves[0].start == comp.arrival_ts
    assert leaves[-1].end == comp.finished_ts
    assert leaves[-2].end == leaves[-1].start == comp.first_token_ts
    for a, b in zip(leaves, leaves[1:]):
        assert 0.0 <= b.start - a.end <= 1.0, (a.name, b.name)
    bd = request_breakdown(rt.tracer.spans, req.trace_id)
    assert bd["first_token"] > 0 and bd["decode"] > 0
    gaps = len(leaves) - 1
    assert comp.latency_ms - 1e3 * gaps <= sum(bd.values()) \
        <= comp.latency_ms


def test_new_span_names_are_stages():
    assert set(NEW_STAGES) <= set(STAGES)


def _chunk_spans(rt):
    spans = rt.tracer.spans
    tree = build_tree(spans)
    return [(s, tree.get(s.span_id, [])) for s in spans
            if s.name == "decode_chunk"]


@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_chunk_phases_lie_inside_their_chunk(session, pool):
    rt = _runtime(session, PAGED if pool == "paged" else {}, True, Ticker())
    reqs = [rt.submit(BASE[:6 + i], 5 + 2 * i, seed=i) for i in range(4)]
    done = rt.run()
    assert len(done) == len(reqs)
    chunks = _chunk_spans(rt)
    assert len(chunks) == rt.stats["chunks"]
    for sp, kids in chunks:
        assert [k.name for k in kids] == ["chunk_dispatch", "chunk_wait",
                                          "chunk_emit"]
        assert kids[0].start == sp.start and kids[-1].end == sp.end
        for a, b in zip(kids, kids[1:]):
            assert a.end == b.start and a.start < a.end
        assert all(k.trace_id == sp.trace_id for k in kids)
        assert 1 <= sp.attrs["rows_busy"] <= sp.attrs["n_rows"] == \
            (4 if pool == "paged" else 2)
        assert ("pages_valid" in sp.attrs) == (pool == "paged")
    # every decode step's kept token is counted once; the prefill's
    # first token is not a chunk's
    assert sum(sp.attrs["tokens"] for sp, _ in chunks) == \
        sum(r.n_new - 1 for r in reqs)


def test_paged_chunk_counts_pages_of_busy_rows(session):
    """``pages_valid``: distinct pages under busy rows' valid tokens after
    the chunk; ``pages_held``: pages off the free list.  Checked against
    the pool's state at each chunk's first delivery."""
    rt = _runtime(session, PAGED, True, Ticker())
    pool_state = []

    def sample(rid, toks):
        if len(pool_state) < rt.stats["chunks"]:
            pool = next(iter(rt.pools.values()))
            valid = set()
            for row, act in enumerate(pool.slots):
                if act is not None:
                    n = pool.row_len[row]
                    valid.update(pool.row_pages[row][:-(-n // 8)])
            pool_state.append((len(valid),
                               pool.n_pages - len(pool.alloc.free)))
    rt.on_progress = sample
    rt.submit(BASE, 4, seed=0)
    rt.run()
    for i in range(4):                         # sharers of BASE's pages
        rt.submit(BASE + [9 + i], 6 + 3 * i, seed=i)
    rt.run()
    counts = [(sp.attrs["pages_valid"], sp.attrs["pages_held"])
              for sp, _ in _chunk_spans(rt)]
    assert counts == pool_state
    assert any(v < h for v, h in counts)       # the cache holds pages too


def test_paged_chunk_counts_kernel_blocks(session, monkeypatch):
    """``kv_blocks_walked``: blocks the paged kernel walks at the chunk's
    last step, from each row's attended length there (the lengths the
    chunk returns: ``min(length, cap) + 1``); ``kv_blocks_table``: rows x
    blocks of the whole table.  Blocks of two 8-token pages, so rows of
    more than 16 tokens walk two of a table's two."""
    from repro.kernels.flash_decode import paged
    monkeypatch.setattr(paged, "MAX_BLOCK_TOKENS", 16)
    rt = _runtime(session, PAGED, True, Ticker())
    want = []

    def sample(rid, toks):
        if len(want) < rt.stats["chunks"]:
            pool = next(iter(rt.pools.values()))
            k = pool.pool["kv"]["k"]
            ppb = paged.pages_per_block(8, k.shape[-1], pool.max_pages,
                                        k.dtype.itemsize)
            assert ppb == 2 < pool.max_pages
            lens = np.asarray(pool.lengths)
            want.append((int(sum(-(-lens // (ppb * 8)))),
                         pool.n_rows * -(-pool.max_pages // ppb)))
    rt.on_progress = sample
    for i in range(3):
        rt.submit(BASE[:6 + 4 * i], 5 + 4 * i, seed=i)
    rt.run()
    got = [(sp.attrs["kv_blocks_walked"], sp.attrs["kv_blocks_table"])
           for sp, _ in _chunk_spans(rt)]
    assert got == want
    assert all(w <= t for w, t in got)
    assert any(w > rt.pools[next(iter(rt.pools))].n_rows for w, _ in got)
    assert any(w < t for w, t in got)


def test_admit_pass_spans_cover_the_admissions(session):
    rt = _runtime(session, PAGED, True, Ticker())
    reqs = [rt.submit(BASE[:5 + i], 9, seed=i) for i in range(5)]
    rt.run()
    spans = rt.tracer.spans
    passes = [s for s in spans if s.name == "admit_pass"]
    assert sum(s.attrs["requests"] for s in passes) == len(reqs)
    for s in spans:
        if s.name in ("prefill", "admit"):
            assert any(p.start < s.start and s.end < p.end for p in passes)
    assert all(p.trace_id == "runtime:serving" for p in passes)


def test_histograms_read_the_stamps(session):
    rt = _runtime(session, PAGED, True, Ticker())
    for i in range(5):
        rt.submit(BASE[:5 + i], 9, seed=i)
    done = rt.run()
    h = {k: rt.metrics.histogram(f"serving.{k}")
         for k in ("ttft_ms", "tpot_ms", "chunk_ms")}
    assert h["ttft_ms"].count == h["tpot_ms"].count == len(done)
    assert h["ttft_ms"].sum == pytest.approx(
        sum(1e3 * (c.first_token_ts - c.arrival_ts) for c in done))
    # chunk_ms skips chunks that waited on an admission of their step
    chunks = len(_chunk_spans(rt))
    admitting = sum(1 for s in rt.tracer.spans if s.name == "admit_pass")
    assert 0 < h["chunk_ms"].count == chunks - admitting


@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_untraced_runtime_records_nothing_per_chunk(session, pool):
    rt = _runtime(session, PAGED if pool == "paged" else {}, False,
                  Ticker())
    for i in range(3):
        rt.submit(BASE[:5 + i], 6, seed=i)
    done = rt.run()
    assert all(c.first_token_ts is not None for c in done)
    assert next(iter(rt.pools.values())).last_chunk is None
    assert all(rt.metrics.histogram(f"serving.{k}").count == 0
               for k in ("ttft_ms", "tpot_ms", "chunk_ms"))
