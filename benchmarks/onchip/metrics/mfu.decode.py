"""Whole step: model FLOPs of the decode steps in the traced window
(flops.py, from the configuration's sizes and each step's live context)
over the device time of the decode-step programs times the chip's bf16
peak.  Bounds every decode kernel's gain.  Moves tpot_p95_ms."""
from benchmarks.onchip.reduce import DECODE_PROGRAMS, decode_flops, mfu


def read(ctx):
    return mfu(ctx, decode_flops(ctx), DECODE_PROGRAMS)
