"""Pages holding valid tokens of busy rows over pages held (busy rows'
and the prefix cache's), averaged over the window's chunk boundaries."""


def read(rec):
    lo, hi = rec.data["t_open"], rec.data["t_close"]
    shares = [used / held for t, _, used, held in rec.data["chunks"]
              if lo <= t < hi and held]
    return 100.0 * sum(shares) / len(shares) if shares else None
