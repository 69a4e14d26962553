"""90th percentile, over every request due in the window, of the time
from when the request was due until the client's first delivery of its
tokens.  A request with no delivery by the end of the drain counts with
the time until then (it missed any limit)."""
from bench.harness import percentile


def read(rec):
    end = rec.data["t_end"]
    return percentile([1e3 * ((r.first if r.first is not None else end)
                              - r.due)
                       for r in rec.data["recs"] if r.in_window], 90)
