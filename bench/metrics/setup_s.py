"""Set-up: from process start until the measured window opens (loading,
weights, compilation or the compile cache, warm-up and ramp)."""


def read(rec):
    return rec.setup_s
