"""Page pool: pages reserved for admitted requests but not yet holding a
token, as a share of the reserved pages, averaged over the engine steps
of the window (program counters ``reserved_total`` and ``pages_in_use``).
Reservations admit at worst case, so this is what admission holds back.
Moves ttft_p95_ms."""


def read(ctx):
    shares = [100.0 * (res - used) / res for _t, res, used in ctx["pool"]
              if res > 0]
    return sum(shares) / len(shares) if shares else None
