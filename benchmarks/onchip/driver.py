"""Drive one cell through the program's normal serving path.

``get_config`` -> ``build_model`` -> ``repro.launch.serve.build_engine``
-> ``ServeEngine.submit`` / ``ServeEngine.step``.  The benchmark's own
loop submits each request when it is due (open loop) or when its
client's previous reply ends (closed loop), calls ``step()``, and takes
every time on its own clock after each call returns: a token is
delivered when the step that produced it returns.

Calls into each layer of the program are wrapped in
``jax.profiler.TraceAnnotation`` spans from here (``bench.*`` around the
benchmark's own work, ``layer.*`` around the engine's admission and the
step model's prefill, write and decode calls), and each layer call
records what it did (slots active and live context of a decode step,
prompt rows and length of a prefill) for the kernel cost functions.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import math
import sys
import time

import jax
import numpy as np

from benchmarks.onchip import spec as spec_mod
from benchmarks.onchip import check as check_mod
from benchmarks.onchip import trace as trace_mod
from benchmarks.onchip import traffic as traffic_mod
from benchmarks.onchip import weights as weights_mod

clock = time.perf_counter
span = jax.profiler.TraceAnnotation

# a traced run captures the window's last TRACE_S seconds
TRACE_S = 15.0


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# the system under test
# ----------------------------------------------------------------------
def _setting(cfg, key, value):
    """A value of the file's ``model``: a dict replaces fields of the
    sub-config ``key`` (``moe``, ``mamba``, ``mla``), anything else is the
    field's value."""
    if not isinstance(value, dict):
        return value
    sub = getattr(cfg, key)
    if not dataclasses.is_dataclass(sub):
        raise ValueError(f"{cfg.name} has no {key} settings to replace "
                         f"fields of (it is {sub!r})")
    return dataclasses.replace(sub, **value)


def build_model(config: dict):
    """The configuration as it runs, checked against the sizes its file
    states; a dotted size (``"moe.n_experts"``) names a field of a
    sub-config."""
    from repro.configs import get_config
    from repro.models import build_model as program_build_model
    base = get_config(config["arch"])
    cfg = dataclasses.replace(base, **{k: _setting(base, k, v)
                                       for k, v in config["model"].items()})
    for k, v in config["sizes"].items():
        if k == "mixer":
            continue
        got = functools.reduce(getattr, k.split("."), cfg)
        if got != v:
            raise ValueError(f"{config['name']}: the program's {k} is "
                             f"{got!r}, the file says {v!r}")
    return program_build_model(cfg)


def build_engine(model, params, config: dict):
    from repro.configs import ServeConfig
    from repro.launch.serve import build_engine as program_build_engine
    return program_build_engine(model, params, ServeConfig(**config["serve"]))


class Probe:
    """Wraps the engine's layer calls in host spans and records, for each
    decode step, the active slots and their live context, and for each
    prefill, its rows and prompt length."""

    def __init__(self, eng):
        self.steps = []        # (t, n_active, ctx_tokens)
        self.prefills = []     # (t, rows, prompt_len)
        self.eng = eng
        sm = eng.sm
        step, prefill, write = sm.step, sm.prefill, sm.write_slots
        admit, sample = eng.admit, sm.sample

        def traced_step(*a, **k):
            act = eng.active
            self.steps.append((clock(), int(act.sum()),
                               int((eng.pos[act] + 1).sum())))
            with span("layer.decode_step"):
                return step(*a, **k)

        def traced_prefill(params, xs, *a, **k):
            xs = np.asarray(xs)
            rows = 1 + sum(not np.array_equal(r, xs[-1]) for r in xs[:-1])
            self.prefills.append((clock(), rows, xs.shape[1]))
            with span("layer.prefill"):
                return prefill(params, xs, *a, **k)

        def traced_write(*a, **k):
            with span("layer.write_slots"):
                return write(*a, **k)

        def traced_sample(*a, **k):
            with span("layer.sample"):
                return sample(*a, **k)

        def traced_admit():
            with span("layer.admit"):
                return admit()

        sm.step, sm.prefill, sm.write_slots = (traced_step, traced_prefill,
                                               traced_write)
        sm.sample = traced_sample
        eng.admit = traced_admit


class LoopWatch:
    """Backend compiles, persistent-cache hits and the garbage
    collector's pauses while active."""

    def __init__(self):
        self.compiles = self.hits = self.full_gcs = 0
        self.gc_longest = 0.0
        self.on = False
        self._gc_t0 = None

    def _gc(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._gc_t0 = clock()
        elif self._gc_t0 is not None:
            self.gc_longest = max(self.gc_longest, clock() - self._gc_t0)
            self.full_gcs += info["generation"] == 2

    def _dur(self, event, secs, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._ev)
        gc.callbacks.remove(self._gc)


# ----------------------------------------------------------------------
# warm-up
# ----------------------------------------------------------------------
def warm_lengths(prompt_lens, granule: int) -> list:
    """The shortest prompt of each ``granule``-token bucket the mix holds:
    the engine's compiled shapes depend on a prompt only through its
    chunk width and (paged) its page count, both constant in a bucket."""
    best = {}
    for p in map(int, prompt_lens):
        b = -(-p // granule)
        best[b] = min(best.get(b, p), p)
    return sorted(best.values())


def warm_up(eng, lens, vocab, rng, all_lens):
    """Serve one two-token request per length in ``lens`` through
    submit/step: the prefill chunk shapes, the single-request wave, the
    page write of each bucket and one decode step.  Then pad a prompt of
    every length in ``all_lens`` to its chunk grid as the chunked prefill
    does (one small program per distinct length, which would otherwise
    compile in the window).  Waits until the engine is idle."""
    reqs = [eng.submit(rng.integers(0, vocab, n, dtype=np.int32),
                       max_new_tokens=2) for n in lens]
    while eng.waiting or eng.active.any():
        eng.step()
    if not all(r.finished and len(r.outputs) == 2 for r in reqs):
        raise RuntimeError("warm-up requests did not finish")
    import jax.numpy as jnp
    for n in sorted(set(map(int, all_lens))):
        c = eng.sm.chunk_for(n)
        if n % c:
            jnp.pad(jnp.zeros((1, n), jnp.int32),
                    ((0, 0), (0, c - n % c))).block_until_ready()


# ----------------------------------------------------------------------
# the measured loop
# ----------------------------------------------------------------------
@dataclasses.dataclass(eq=False)
class Rec:
    prompt: np.ndarray
    n_out: int
    due: float                      # when it was due (open) / submitted
    req: object = None
    step_start: float = math.nan    # start of the step that admitted it
    first: float = math.nan
    last: float = math.nan
    finish: float = math.nan
    seen: int = 0
    outputs: list = None


@dataclasses.dataclass
class Window:
    w0: float
    w1: float
    end: float = math.nan
    trace: tuple = None            # host (start, end) of the traced part
    gaps: list = dataclasses.field(default_factory=list)   # (t, gap)
    pool: list = dataclasses.field(default_factory=list)   # (t, res, used)
    late: list = dataclasses.field(default_factory=list)   # submit - due
    queue: list = dataclasses.field(default_factory=list)  # (t, waiting)
    events: object = None


def serve(eng, sched, traffic, seconds, trace_s):
    """Run the ramp and the window, tracing its last ``trace_s`` seconds
    (none where 0); returns (records, Window)."""
    loop = traffic["loop"]
    ramp = float(traffic["ramp_s"])
    recs = [Rec(p, int(n), float(d)) for p, n, d in
            zip(sched.prompts, sched.out_lens, sched.due)]
    live = []
    n_docs = len(recs)
    t0 = clock()
    win = Window(t0 + ramp, t0 + ramp + seconds)
    grace_end = win.w1 + float(traffic.get("grace_s", 0.0))
    # the trace covers the window's last trace_s seconds and stops once
    # the loop has ended, so that reading it back stalls no request
    trace_from = max(win.w0, win.w1 - trace_s)
    for r in recs:
        r.due += t0
    pool = eng.pool
    tracing = contextlib.ExitStack()
    traced = {}
    nxt = 0                       # next record to submit

    def submit(r):
        with span("bench.submit"):
            r.req = eng.submit(r.prompt, max_new_tokens=r.n_out)
        live.append(r)

    if loop == "closed":
        clients = int(eng.slots)
        for r in recs[:clients]:
            r.due = t0
            submit(r)
        nxt = clients

    def window_done(now):
        if now < win.w1:
            return False
        if loop == "closed" or now >= grace_end:
            return True
        return all(not math.isnan(r.first) for r in recs[:nxt]
                   if win.w0 <= r.due < win.w1)

    while True:
        now = clock()
        if trace_s and win.trace is None and now >= trace_from:
            tracing.enter_context(trace_mod.capture(traced))
            win.trace = (now, math.nan)
        if window_done(now):
            break
        if loop == "open":
            while nxt < len(recs) and recs[nxt].due <= now:
                win.late.append(now - recs[nxt].due)
                submit(recs[nxt])
                nxt += 1
        if not (eng.waiting or eng.active.any()):
            if loop == "closed" or nxt >= len(recs):
                raise RuntimeError("the engine ran out of work before "
                                   "the window closed")
            wake = min(recs[nxt].due, win.w1)
            if trace_s and win.trace is None:
                wake = min(wake, trace_from)
            with span("bench.idle"):
                time.sleep(max(0.0, wake - clock()))
            continue
        s0 = clock()
        with span("bench.step"):
            eng.step()
        t = clock()
        with span("bench.record"):
            if win.w0 <= t < win.w1:
                win.queue.append((t, len(eng.waiting)))
                if pool is not None:
                    win.pool.append((t, pool.reserved_total,
                                     pool.pages_in_use))
            done = []
            for r in live:
                n = len(r.req.outputs)
                if n > r.seen:
                    if r.seen == 0:
                        r.first, r.step_start = t, s0
                    elif win.w0 <= t < win.w1:
                        win.gaps.append((t, t - r.last))
                    r.seen, r.last = n, t
                if r.req.finished:
                    r.finish = t
                    done.append(r)
            for r in done:
                live.remove(r)
                r.outputs = [int(x) for x in r.req.outputs]
                r.req = None
                if loop == "closed" and t < win.w1:
                    if nxt >= len(recs):      # the pool of documents cycles
                        doc = recs[nxt % n_docs]
                        recs.append(Rec(doc.prompt, doc.n_out, t))
                    recs[nxt].due = t
                    submit(recs[nxt])
                    nxt += 1
    win.end = clock()
    if win.trace is not None:
        tracing.close()
        win.trace = (win.trace[0], win.end)
        win.events = traced["events"]
    for r in live:                # still running when the loop ended
        r.outputs = None
        r.req = None
    return recs[:nxt], win


# ----------------------------------------------------------------------
# end-to-end metrics
# ----------------------------------------------------------------------
def pct(values, q):
    """q-th percentile (linear interpolation); +inf entries are misses."""
    v = np.sort(np.asarray(values, float))
    if not len(v):
        return math.nan
    return float(np.percentile(v, q))


def end_to_end(recs, win, traffic, seconds):
    """-> (metrics, attempted, failed, notes)"""
    out, notes = {}, {}
    if traffic["loop"] == "open":
        due = [r for r in recs if win.w0 <= r.due < win.w1]
        ttft = [(r.first - r.due) * 1e3 if not math.isnan(r.first)
                else math.inf for r in due]
        missed = sum(math.isinf(x) for x in ttft)
        p95 = pct(ttft, 95)
        if math.isinf(p95):     # more than 5% missed: censor at loop end
            p95 = (win.end - min(r.due for r in due
                                 if math.isnan(r.first))) * 1e3
        out["ttft_p95_ms"] = p95
        fin = [r for r in recs if win.w0 <= r.finish < win.w1
               and r.n_out > 1]
        out["tpot_p95_ms"] = pct([(r.finish - r.first) * 1e3 / (r.n_out - 1)
                                  for r in fin], 95)
        q = [n for _t, n in win.queue]
        notes.update(due=len(due), finished=len(fin), missed=missed,
                     ttft_p50_ms=pct(ttft, 50),
                     queue_first=q[0] if q else 0,
                     queue_last=q[-1] if q else 0,
                     queue_max=max(q) if q else 0)
        return out, len(due), missed, notes
    served = [r for r in recs if win.w0 <= r.first < win.w1]
    out["prompt_tok_s"] = sum(len(r.prompt) for r in served) / seconds
    notes.update(prefilled=len(served),
                 finished=sum(win.w0 <= r.finish < win.w1 for r in recs))
    return out, len(served), 0, notes


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    vals = [s.get("peak_bytes_in_use") for s in stats]
    return max(vals) if all(v is not None for v in vals) else None


def layer_context(cell, win, probe, recs, device_kind, sizes):
    tw = win.trace
    inside = (lambda t: tw[0] <= t < tw[1]) if tw else (lambda t: False)
    return dict(
        events=win.events, window=(win.w0, win.w1), trace_window=tw,
        steps=[s for s in probe.steps if inside(s[0])],
        prefills=[p for p in probe.prefills if inside(p[0])],
        requests=recs, gaps=win.gaps, pool=win.pool, sizes=sizes,
        config=cell.config, traffic=cell.traffic,
        peaks=spec_mod.peaks(device_kind), cost=spec_mod.kernel_cost,
        reference=spec_mod.reference)


def run_cell(cell, seed, seconds, trace, *, t_start, devices,
             controls=(), engine_hook=None, check=True, dump_events="",
             control_as_program=""):
    """Set up, run the window, read the metrics, free the program, check
    the served tokens against the reference -> the result dict.  With
    ``control_as_program`` the named control's tokens are judged in the
    program's place (``check.check``)."""
    phases = {}
    tp = clock()
    config, traffic = cell.config, cell.traffic
    sizes = config["sizes"]
    model = build_model(config)
    rate = cell.cell.get("rate_per_s")
    sched = traffic_mod.schedule(traffic, seed, seconds, sizes["vocab"],
                                 rate)
    ref = spec_mod.reference(config["name"])
    params = weights_mod.make(model, seed, getattr(ref, "INIT", None),
                              where=f"INIT of {ref.__file__}")
    phases["weights_s"] = clock() - tp
    tp = clock()
    eng = build_engine(model, params, config)
    probe = Probe(eng)
    phases["engine_s"] = clock() - tp
    tp = clock()
    lens = warm_lengths([len(p) for p in sched.prompts],
                        int(config["warm_granule"]))
    warm_up(eng, lens, sizes["vocab"], traffic_mod.seed_rng(seed + 1),
            [len(p) for p in sched.prompts])
    probe.steps.clear()
    probe.prefills.clear()
    phases["warm_up_s"] = clock() - tp
    phases["warm_requests"] = len(lens)
    # what set-up leaves (programs, traces, weights) lives as long as the
    # server: freeze it, as a serving process does after warm-up, so that
    # a full collection in the window does not walk it
    gc.collect()
    gc.freeze()
    setup_s = clock() - t_start
    log(f"setup: {setup_s:.3f} s, phases "
        + ", ".join(f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                    for k, v in phases.items()))
    if engine_hook is not None:
        engine_hook(eng)

    with LoopWatch() as cc:
        cc.on = True
        recs, win = serve(eng, sched, traffic, seconds,
                          TRACE_S if trace else 0.0)
        cc.on = False
    late = np.asarray(win.late) * 1e3
    log(f"window: {seconds} s after a {traffic['ramp_s']} s ramp; "
        f"{len(recs)} requests submitted; generator late p50 "
        f"{pct(late, 50):.3f} ms, max {late.max() if len(late) else 0:.3f}"
        f" ms; compiles in the loop {cc.compiles}, persistent-cache hits "
        f"{cc.hits}; full collections {cc.full_gcs}, longest collector "
        f"pause {cc.gc_longest * 1e3:.3f} ms")
    metrics, attempted, failed, notes = end_to_end(recs, win, traffic,
                                                   seconds)
    log("served: " + ", ".join(f"{k} {v}" for k, v in notes.items()))
    notes["generator_late_max_ms"] = float(late.max()) if len(late) else 0.0
    notes["gc_pause_max_ms"] = cc.gc_longest * 1e3

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes(devices)}
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        ctx = layer_context(cell, win, probe, recs, dev.device_kind, sizes)
        out = {}
        for m in cell.per_layer:
            v = spec_mod.metric_reader(m["name"])(ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        ev = win.events
        if dump_events:
            ev.save(dump_events)
        device["busy_s"] = trace_mod.busy_s(ev)
        device["window_s"] = ev.window_s
        result["metrics"] = out
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(ev, 10),
            "idle_gaps": trace_mod.idle_by_host_span(ev)[:10]}
    else:
        metrics["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in metrics.items() if k in units}
    result["device"] = device
    result["setup_phases"] = phases
    result["served"] = notes
    result["compiles_in_window"] = cc.compiles

    # free the program's state before the reference runs on the chip (the
    # layer probes tie the engine into a cycle: collect it now)
    finished = [(r.prompt, r.outputs, r.n_out) for r in recs
                if r.outputs is not None]
    eng = probe = None
    del eng, probe
    gc.unfreeze()
    tg = clock()
    gc.collect()
    log(f"gc: a full collection over set-up's objects took "
        f"{(clock() - tg) * 1e3:.3f} ms after the window")
    if not check:
        return result
    checks, readings = check_mod.check(
        cell, params, finished, seed, controls=controls,
        control_as_program=control_as_program)
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    if readings:
        result["readings"] = readings
    result["checks"] = checks
    return result
