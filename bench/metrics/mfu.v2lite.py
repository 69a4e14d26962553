"""Model operations of the window's work on DeepSeek-V2-Lite, every busy
row's decode steps (at its context) and every prompt prefilled, over the
window times the chip's peak: the whole step's share of the peak.  A
token's routed work is the experts held here (``counts_mla_moe``)."""
from bench import counts, counts_mla_moe


def read(rec):
    m = rec.data["model"]
    lo, hi = rec.data["t_open"], rec.data["t_close"]
    steps = rec.data["chunk_steps"]
    flops = sum(counts_mla_moe.token_flops(m, n + s + 1)
                for t, lens, _, _ in rec.data["chunks"] if lo <= t < hi
                for n in lens for s in range(steps))
    flops += sum(counts_mla_moe.prefill_flops(m, 0,
                                              int(s.attrs["prompt_len"]))
                 for s in rec.data["spans"]
                 if s.name == "prefill" and lo <= s.start < hi)
    peak = counts.peaks(rec.device["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (rec.window_s * peak)
