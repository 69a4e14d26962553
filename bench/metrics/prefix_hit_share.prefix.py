"""Share of the window's admissions that found their prompt's prefix in
the prefix cache (the runtime's counters at window open and close)."""


def read(rec):
    a, b = rec.data["stats"]["open"], rec.data["stats"]["close"]
    hits = b["prefix_hits"] - a["prefix_hits"]
    looked = hits + b["prefix_misses"] - a["prefix_misses"]
    return 100.0 * hits / looked if looked else None
