"""Median wait of a request in the scheduler's queue: from when it was
due until its own admission started, the end of the program's last
``queue_wait`` span for it (on the same clock), over the requests due in
the window."""
from bench.harness import percentile


def read(rec):
    due = {r.rid: r.due for r in rec.data["recs"] if r.in_window}
    admitted = {}
    for s in rec.data["spans"]:
        if s.name == "queue_wait" and s.trace_id.startswith("req:"):
            rid = int(s.trace_id.split(":")[1])
            if rid in due:
                admitted[rid] = max(s.end, admitted.get(rid, s.end))
    return percentile([1e3 * (t - due[rid]) for rid, t in admitted.items()],
                      50)
