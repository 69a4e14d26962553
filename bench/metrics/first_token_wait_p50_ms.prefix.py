"""Median wait of a request, over the requests due in the window, from the
end of its own admission until the first decode chunk emission that
carried its tokens: the duration of the program's ``first_token`` span
(on the same clock).  What is left of the time to first token once the
queue wait is taken out: the request's own prefill on the device, the
admissions queued ahead of it, and the chunk that emits its token."""
from bench.harness import percentile


def read(rec):
    due = {r.rid for r in rec.data["recs"] if r.in_window}
    wait = {}
    for s in rec.data["spans"]:
        if s.name == "first_token" and s.trace_id.startswith("req:"):
            rid = int(s.trace_id.split(":")[1])
            if rid in due:
                wait[rid] = 1e3 * (s.end - s.start)
    return percentile(list(wait.values()), 50)
