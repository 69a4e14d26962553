"""Share of the traced window in which no operation ran on a device,
averaged over the cell's chips."""


def read(rec):
    return 100.0 * rec.trace.idle_share()
