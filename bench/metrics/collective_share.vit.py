"""Share of the traced window in which device 0 ran a collective (the
all-gathers of the split plans' exchange)."""


def read(rec):
    t = rec.trace
    return 100.0 * t.collective_s(0) / t.window_s
