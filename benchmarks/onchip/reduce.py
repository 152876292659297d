"""Arithmetic shared by the per-layer readers in ``metrics/``.

A reader gets one context dict from the traced run (``driver.
layer_context``): the reduced device trace (``events``), the host-clock
windows, the decode steps and prefills the layer probes recorded inside
the traced window, the request records, inter-token gaps, page-pool
samples, the configuration's sizes, the chip's peaks, the loader of
kernel cost functions (``cost``) and the loader of a configuration's
reference module (``reference``), which may hold its own FLOP counts.
A reader returns a number, or None where it finds nothing to read; it
never returns 0 for a share of a peak.
"""
from __future__ import annotations

import math

import numpy as np

from benchmarks.onchip import flops as flops_mod
from benchmarks.onchip import trace as trace_mod

# device programs by the stable part of their names
DECODE_PROGRAMS = ("step_impl",)
PREFILL_PROGRAMS = ("jit_run", "prefill", "write_impl")


def pct(values, q):
    v = [x for x in values if not math.isnan(x)]
    return float(np.percentile(v, q)) if v else None


def queue_waits_ms(ctx):
    """Due time to the start of the step that admitted the request, for
    requests due in the window."""
    w0, w1 = ctx["window"]
    return [(r.step_start - r.due) * 1e3 for r in ctx["requests"]
            if w0 <= r.due < w1 and not math.isnan(r.step_start)]


def device_seconds(ctx, programs):
    ev = ctx["events"]
    return sum(trace_mod.module_seconds(ev, programs)) if ev else 0.0


def roofline_share(ctx, kernel, calls):
    """100 * (least time the chip could take for ``calls``, each a dict
    of the kernel cost function's arguments) / the kernel's device time.
    None where the trace holds no time for the kernel."""
    ev = ctx["events"]
    if ev is None or not calls:
        return None
    k_s = trace_mod.op_seconds(ev, kernel)
    if k_s <= 0:
        return None
    cost = ctx["cost"](kernel)
    pk = ctx["peaks"]
    least = 0.0
    for c in calls:
        f, b = cost(**c)
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / k_s


def _own(ctx, fn):
    """The configuration's own FLOP function ``fn`` from its
    ``references/<config>.py``, or None where it defines none."""
    return getattr(ctx["reference"](ctx["config"]["name"]), fn, None)


def decode_flops(ctx):
    """Model FLOPs of the decode steps: the configuration's
    ``decode_flops(sizes, n_active, ctx_tokens)``, or flops.py by mixer."""
    s = ctx["sizes"]
    own = _own(ctx, "decode_flops")
    step = own or (lambda s, n, c: flops_mod.decode_step(s, s["mixer"], n, c))
    return sum(step(s, n, c) for _t, n, c in ctx["steps"])


def prefill_flops(ctx):
    """Model FLOPs of the prefilled prompts: the configuration's
    ``prefill_flops(sizes, prompt_len)`` per prompt, or flops.py by
    mixer."""
    s = ctx["sizes"]
    own = _own(ctx, "prefill_flops")
    one = own or (lambda s, p: flops_mod.prefill(s, s["mixer"], p))
    return sum(rows * one(s, p) for _t, rows, p in ctx["prefills"])


def mfu(ctx, model_flops, programs):
    t = device_seconds(ctx, programs)
    if t <= 0 or model_flops <= 0:
        return None
    return 100.0 * model_flops / (t * ctx["peaks"]["bf16_flops"])


def idle_share(ctx):
    ev = ctx["events"]
    if ev is None or ev.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace_mod.busy_s(ev) / ev.window_s)
