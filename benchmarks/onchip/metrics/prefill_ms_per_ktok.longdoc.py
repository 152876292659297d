"""Step model: device milliseconds of the prefill programs (the chunked
prefill and the wave's cache write) per thousand prompt tokens prefilled
in the traced window.  Moves prompt_tok_s."""
from benchmarks.onchip.reduce import PREFILL_PROGRAMS, device_seconds


def read(ctx):
    tokens = sum(rows * p for _t, rows, p in ctx["prefills"])
    t = device_seconds(ctx, PREFILL_PROGRAMS)
    if tokens <= 0 or t <= 0:
        return None
    return t * 1e3 / (tokens / 1e3)
