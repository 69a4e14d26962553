"""Pages holding valid tokens of busy rows after a chunk over pages held
(busy rows' and the prefix cache's), summed over the decode chunks that
started in the window: the program's ``pages_valid`` and ``pages_held``
counts on its ``decode_chunk`` spans."""


def read(rec):
    lo, hi = rec.data["t_open"], rec.data["t_close"]
    valid = held = 0
    for s in rec.data["spans"]:
        if (s.name == "decode_chunk" and lo <= s.start < hi
                and "pages_held" in s.attrs):
            valid += s.attrs["pages_valid"]
            held += s.attrs["pages_held"]
    return 100.0 * valid / held if held else None
