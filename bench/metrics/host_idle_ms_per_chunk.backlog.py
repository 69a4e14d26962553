"""Device-0 idle time in the traced window while the host ran one of the
program's host phases (an admission pass, a chunk's dispatch, a chunk's
token emission), per decode chunk that started in the traced window.  The
program's spans are put on the trace's clock with ``Trace.at``."""
from bench.trace_reduce import union

HOST_PHASES = ("admit_pass", "chunk_dispatch", "chunk_emit")


def read(rec):
    t = rec.trace
    lo, hi = rec.data["trace_window"]
    spans = rec.data["spans"]
    chunks = sum(1 for s in spans
                 if s.name == "decode_chunk" and lo <= s.start < hi)
    phases = union([(max(t.at(s.start), t.lo), min(t.at(s.end), t.hi))
                    for s in spans if s.name in HOST_PHASES
                    and t.at(s.end) > t.lo and t.at(s.start) < t.hi])
    if not chunks or not phases:
        return None
    dev = t.devices[0]
    idle_ns = sum((b - a) - dev.busy_ns(a, b) for a, b in phases)
    return 1e-6 * idle_ns / chunks
