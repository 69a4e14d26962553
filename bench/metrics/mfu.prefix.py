"""Model operations of the prompt tokens prefilled in the traced window
(cached prompt tokens excluded) over the device time of the admission
programs times the chip's peak: the whole admission step's share of the
peak."""
from bench import counts
from bench.lm_admissions import admission_program_s, admissions


def read(rec):
    t = admission_program_s(rec)
    if not t:
        return None
    m = rec.data["model"]
    flops = sum(counts.lm_prefill_flops(m, start, n)
                for start, n in admissions(rec))
    peak = counts.peaks(rec.device["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (t * peak)
