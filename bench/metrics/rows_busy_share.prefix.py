"""Rows holding a request over rows of the pool, summed over the decode
chunks that started in the window: the program's ``rows_busy`` and
``n_rows`` counts on its ``decode_chunk`` spans."""


def read(rec):
    lo, hi = rec.data["t_open"], rec.data["t_close"]
    busy = rows = 0
    for s in rec.data["spans"]:
        if (s.name == "decode_chunk" and lo <= s.start < hi
                and "rows_busy" in s.attrs):
            busy += s.attrs["rows_busy"]
            rows += s.attrs["n_rows"]
    return 100.0 * busy / rows if rows else None
