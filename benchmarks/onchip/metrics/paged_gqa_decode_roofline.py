"""Kernels: the paged GQA decode read's share of its roofline over the
traced window: the least time the chip needs for the live context of
every decode step (costs/paged_gqa_decode.py) over the device time of the
ops named ``paged_gqa_decode``.  Moves tpot_p95_ms."""
from benchmarks.onchip.reduce import roofline_share


def read(ctx):
    s = ctx["sizes"]
    calls = [dict(ctx_tokens=c, n_active=n, n_layers=s["n_layers"],
                  n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
                  head_dim=s["head_dim"])
             for _t, n, c in ctx["steps"] if n]
    return roofline_share(ctx, "paged_gqa_decode", calls)
