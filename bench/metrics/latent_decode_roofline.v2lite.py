"""The paged latent-decode kernel's share of its roofline: the least time
its calls need at the chip's peaks, from the busy rows' valid latent
bytes (``c_kv`` and ``k_pe``) and the absorbed form's operations, over the
kernel's device time, in the traced window.  Each row's lengths come
from the chunk boundaries."""
from bench import counts, counts_mla_moe
from bench.trace_reduce import base_name

KERNEL = "flash_decode_latent_pallas"


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
            call = counts_mla_moe.latent_decode_call(
                m, [n + s + 1 for n in lens])
            flops += layers * call["flops"]
            nbytes += layers * call["bytes"]
    if not busy or not nbytes:
        return None
    least, _ = counts.least_time_s(flops, nbytes,
                                   counts.peaks(rec.device["kind"]))
    return 100.0 * least / busy
