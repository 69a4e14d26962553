"""Device time of the paged decode-chunk programs (``jit_chunk``) in the
traced window, per decode step."""
from bench.trace_reduce import base_name

PROGRAM = "jit_chunk"


def read(rec):
    dev = rec.trace.devices[0]
    calls = dev.module_calls(lambda n: base_name(n) == PROGRAM)
    if not calls:
        return None
    t = dev.module_time_s(lambda n: base_name(n) == PROGRAM)
    return 1e3 * t / (calls * rec.data["chunk_steps"])
