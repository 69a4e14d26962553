"""The segment-means kernel's share of its roofline on device 0: the
least time its calls need at the chip's peaks (memory bound: a call reads
[B, N/P, H*dh] float32 and writes L rows) over the kernel's device time,
over the split batches that ran wholly inside the traced window."""
from bench import counts
from bench.trace_reduce import base_name

KERNEL = "segment_means_pallas"
CALLS_PER_LAYER = 2                 # keys and values


def read(rec):
    t, m = rec.trace, rec.data["model"]
    D, L = m["hidden_size"], m["num_hidden_layers"]
    n_tok = counts.vit_tokens(m)
    lo, hi = rec.data["trace_window"]
    kernel = [e for e in t.devices[0].ops if base_name(e.name) == KERNEL]
    flops = nbytes = busy = 0.0
    for b in rec.data["batches"]:
        parts, means = rec.data["plans"][b["plan"]]
        if parts == 1 or not lo <= b["start"] <= b["end"] <= hi:
            continue
        a, z = t.at(b["start"]), t.at(b["end"])
        busy += sum(e.end - e.start for e in kernel
                    if a <= e.start and e.end <= z)
        per_part = -(-n_tok // (parts * means)) * means
        call = counts.segment_means_call(b["batch"], per_part, D, means)
        flops += CALLS_PER_LAYER * L * call["flops"]
        nbytes += CALLS_PER_LAYER * L * call["bytes"]
    if not busy:
        return None
    least, _ = counts.least_time_s(flops, nbytes,
                                   counts.peaks(rec.device["kind"]))
    return 100.0 * least / (1e-9 * busy)
