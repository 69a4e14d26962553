"""The held experts' grouped matrix products' share of their roofline,
in the decode chunks of the traced window: the least time of the touched
held experts' weight bytes (the program's ``experts_touched``) and of the
operations of the (token, expert) pairs they computed (its
``expert_tokens``), over the device time of the grouped products inside
the decode-chunk programs.  On a TPU v5e, XLA runs ``jax.lax.ragged_dot``
as the custom calls ``ragged-dot-none`` (one per expert matrix) and
``ragged-dot-metadata`` (the groups' offsets), matched here by that
prefix."""
import bisect

from bench import counts, counts_mla_moe
from bench.trace_reduce import base_name

PROGRAM = "jit_chunk"
GROUPED = "ragged-dot"


def read(rec):
    dev, m = rec.trace.devices[0], rec.data["model"]
    chunks = sorted((e.start, e.end) for e in dev.modules
                    if base_name(e.name) == PROGRAM)
    starts = [s for s, _ in chunks]

    def in_chunk(e):
        i = bisect.bisect_right(starts, e.start) - 1
        return i >= 0 and e.end <= chunks[i][1]
    ops = [e for e in dev.ops
           if base_name(e.name).startswith(GROUPED) and in_chunk(e)]
    busy = 1e-9 * sum(e.end - e.start for e in ops)
    lo, hi = rec.data["trace_window"]
    touched = pairs = 0
    for s in rec.data["spans"]:
        if (s.name == "decode_chunk" and lo <= s.start < hi
                and "experts_touched" in s.attrs):
            touched += s.attrs["experts_touched"]
            pairs += s.attrs["expert_tokens"]
    if not busy or not touched:
        return None
    least, _ = counts.least_time_s(
        pairs * counts_mla_moe.expert_pair_flops(m),
        touched * counts_mla_moe.expert_weight_bytes(m),
        counts.peaks(rec.device["kind"]))
    return 100.0 * least / busy
