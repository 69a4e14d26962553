"""The paged flash-decode kernel's share of its roofline: the least time
its calls need at the chip's peaks, from the busy rows' valid K/V bytes
(memory bound: 2 operations per byte), over the kernel's device time, in
the traced window.  Each row's lengths come from the chunk boundaries."""
from bench import counts
from bench.trace_reduce import base_name

KERNEL = "flash_decode_paged_pallas"


def read(rec):
    t, m = rec.trace, rec.data["model"]
    busy = t.devices[0].op_time_s(lambda n: base_name(n) == KERNEL)
    lo, hi = rec.data["trace_window"]
    steps, layers = rec.data["chunk_steps"], m["num_hidden_layers"]
    flops = nbytes = 0.0
    for when, lens, _, _ in rec.data["chunks"]:
        if not lo <= when < hi:
            continue
        for s in range(steps):
            call = counts.paged_decode_call(m, [n + s + 1 for n in lens])
            flops += layers * call["flops"]
            nbytes += layers * call["bytes"]
    if not busy or not nbytes:
        return None
    least, _ = counts.least_time_s(flops, nbytes,
                                   counts.peaks(rec.device["kind"]))
    return 100.0 * least / busy
