"""Held experts that computed at least one token over the held experts,
summed over MoE layers and steps of the decode chunks that started in the
window: the program's ``experts_touched`` and ``experts_held`` counts on
its ``decode_chunk`` spans."""


def read(rec):
    lo, hi = rec.data["t_open"], rec.data["t_close"]
    touched = held = 0
    for s in rec.data["spans"]:
        if (s.name == "decode_chunk" and lo <= s.start < hi
                and "experts_held" in s.attrs):
            touched += s.attrs["experts_touched"]
            held += s.attrs["experts_held"]
    return 100.0 * touched / held if held else None
