"""Device: share of the traced window in which no operation ran on the
chip (1 - busy / window, from the device trace).  Moves tpot_p95_ms."""
from benchmarks.onchip.reduce import idle_share


def read(ctx):
    return idle_share(ctx)
