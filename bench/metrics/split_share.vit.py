"""Share of the window's images that the policy ran on a split plan
(the session's dispatch history)."""
from bench.vit_window import window_batches


def read(rec):
    batches = window_batches(rec)
    total = sum(b["batch"] for b in batches)
    split = sum(b["batch"] for b in batches if b["plan"] != "local")
    return 100.0 * split / total if total else None
