"""Whole step: model FLOPs of the prompts prefilled in the traced window
(the configuration's own prefill_flops, or flops.py: every prompt token
through the stack, causal attention, the last token's logits) over the
device time of the prefill programs times the chip's bf16 peak.  Moves
prompt_tok_s."""
from benchmarks.onchip.reduce import PREFILL_PROGRAMS, mfu, prefill_flops


def read(ctx):
    return mfu(ctx, prefill_flops(ctx), PREFILL_PROGRAMS)
