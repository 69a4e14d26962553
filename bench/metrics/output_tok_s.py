"""Output tokens delivered to clients during the window, per second."""


def read(rec):
    lo, hi = rec.data["t_open"], rec.data["t_close"]
    return sum(n for t, n in rec.data["deliveries"] if lo <= t < hi) \
        / rec.window_s
