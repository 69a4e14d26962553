"""90th percentile, over the window's finished requests (due in it, or
for a backlog, finished in it), of
(last delivery - first delivery) / (n_new - 1): the time per output token
a streaming client sees after its first."""
from bench.harness import percentile


def read(rec):
    return percentile([1e3 * (r.last - r.first) / (r.n_new - 1)
                       for r in rec.data["recs"]
                       if r.in_window and r.done and r.n_new > 1], 90)
