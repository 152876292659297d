"""Kernels: the minGRU linear_scan's share of its roofline over the
traced window: the least time the chip needs to scan the prompt tokens
prefilled (costs/linear_scan.py) over the device time of the ops named
``linear_scan``.  Moves prompt_tok_s."""
from benchmarks.onchip.reduce import roofline_share


def read(ctx):
    s, chunk = ctx["sizes"], ctx["config"]["serve"]["prefill_chunk"]
    calls = [dict(tokens=rows * p, rows=rows * -(-p // chunk),
                  width=s["d_model"], n_layers=s["n_layers"])
             for _t, rows, p in ctx["prefills"]]
    return roofline_share(ctx, "linear_scan", calls)
