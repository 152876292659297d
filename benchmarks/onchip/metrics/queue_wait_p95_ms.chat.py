"""Engine/scheduler: 95th percentile, over requests due in the window, of
the time from when a request was due to the start of the engine step that
admitted it (host clock).  Moves ttft_p95_ms."""
from benchmarks.onchip.reduce import pct, queue_waits_ms


def read(ctx):
    return pct(queue_waits_ms(ctx), 95)
