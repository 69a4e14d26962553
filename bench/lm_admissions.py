"""Admissions of the traced window, read from the program's spans and the
device trace (shared by the ``.prefix`` metrics)."""
from __future__ import annotations

from typing import List, Tuple

# both admission programs are jitted from a function named ``pf``
# (repro.api.generation.build_prefill_fn / build_paged_suffix_fn)
ADMISSION_PROGRAM = "jit_pf"


def admissions(rec) -> List[Tuple[int, int]]:
    """(cached tokens, prefilled tokens) of each admission that ran a
    prefill in the traced window, from the program's ``prefill`` spans."""
    lo, hi = rec.data["trace_window"]
    out = []
    for s in rec.data["spans"]:
        if s.name != "prefill" or not lo <= s.start < hi:
            continue
        cached = int(s.attrs.get("cached", 0))
        out.append((cached, int(s.attrs["prompt_len"]) - cached))
    return out


def admission_program_s(rec) -> float:
    from bench.trace_reduce import base_name
    return rec.trace.devices[0].module_time_s(
        lambda n: base_name(n) == ADMISSION_PROGRAM)
