"""Device time of the admission programs (the miss prefill and the
partial-hit suffix scan, both jitted as ``pf``) per request admitted in
the traced window."""
from bench.lm_admissions import admission_program_s, admissions


def read(rec):
    n = len(admissions(rec))
    return 1e3 * admission_program_s(rec) / n if n else None
