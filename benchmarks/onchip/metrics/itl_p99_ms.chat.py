"""Engine/scheduler: 99th percentile of the gap between consecutive
tokens of a request, over every gap that ended in the window (host
clock).  A plain decode step or a step with an admission prefill wave in
it; kept off the verdict because its tail jumps between the two.  Moves
tpot_p95_ms."""
from benchmarks.onchip.reduce import pct


def read(ctx):
    return pct([g * 1e3 for _t, g in ctx["gaps"]], 99)
