"""Step model: median device time of one decode-step program (the
step model's jitted slot-batch step) in the traced window.  Moves
tpot_p95_ms."""
from benchmarks.onchip import trace
from benchmarks.onchip.reduce import DECODE_PROGRAMS, pct


def read(ctx):
    ev = ctx["events"]
    if ev is None:
        return None
    return pct([s * 1e3 for s in trace.module_seconds(ev, DECODE_PROGRAMS)],
               50)
