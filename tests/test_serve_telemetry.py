"""Observability: tracing, metrics, stats sink (README §Observability).

Two families of guarantees under test:

  * the PRIMITIVES work — bounded rate/percentile windows (eviction,
    empty-window, clock-misbehavior semantics), the metrics registry,
    the Chrome trace_event recorder and its validator, the injectable
    stats sink;
  * the ENGINE contracts hold with telemetry ON — a traced engine run
    (paged + speculative + forced preemption, the worst case) emits
    BITWISE the streams of an untraced run, keeps every jitted program
    at compile count 1, and its saved trace round-trips the Chrome JSON
    schema with a well-formed span tree (every B closed by a matching
    E, per-track monotonic timestamps).

Telemetry never touches jitted programs — every hook is host-side
around device calls — which is WHY the second family can hold.
"""
import dataclasses
import io
import json

import jax
import numpy as np
import pytest

from repro.common.trace import TraceRecorder, validate_chrome_trace
from repro.configs import SamplingParams, get_config
from repro.models import build_model
from repro.serve import (DecoderStepModel, DraftStepModel, PagedConfig,
                         ServeEngine, Telemetry)
from repro.serve.telemetry import (MetricsRegistry, PercentileWindow,
                                   RateWindow, StatsSink)


# -- bounded windows (the EngineStats rate-stream primitives) ------------
def test_rate_window_basic_rate():
    w = RateWindow(maxlen=8)
    # 3 events, 2s span, 5 units AFTER the anchor event -> 2.5/s (the
    # first event's units predate the window: excluded)
    w.push(10.0, 100)
    w.push(11.0, 2)
    w.push(12.0, 3)
    assert w.per_s() == pytest.approx(2.5)
    assert len(w) == 3


def test_rate_window_eviction_slides_the_anchor():
    w = RateWindow(maxlen=3)
    for i in range(10):                   # only the last 3 survive
        w.push(float(i), 1)
    assert len(w) == 3
    # window is [(7,1),(8,1),(9,1)]: 2 units over 2s
    assert w.per_s() == pytest.approx(1.0)


def test_rate_window_degenerate_is_zero():
    w = RateWindow()
    assert w.per_s() == 0.0               # empty
    w.push(5.0, 3)
    assert w.per_s() == 0.0               # single event: no span
    w.push(5.0, 4)
    assert w.per_s() == 0.0               # zero span
    w2 = RateWindow()
    w2.push(9.0, 1)
    w2.push(3.0, 7)                       # clock went BACKWARDS
    assert w2.per_s() == 0.0              # never inf / negative


def test_percentile_window_eviction_and_totals():
    w = PercentileWindow(maxlen=4)
    for v in range(10):
        w.push(float(v))
    assert len(w) == 4                    # window: 6,7,8,9
    assert w.n_total == 10                # lifetime count survives
    assert w.percentile(0) == pytest.approx(6.0)
    assert w.percentile(100) == pytest.approx(9.0)
    s = w.summary()
    assert s["count"] == 10 and s["max"] == pytest.approx(9.0)


def test_percentile_window_empty_is_zero():
    w = PercentileWindow()
    assert w.percentile(99) == 0.0
    assert w.percentiles((50, 99)) == (0.0, 0.0)
    assert w.summary() == {"count": 0, "p50": 0.0, "p99": 0.0,
                           "max": 0.0}


def test_metrics_registry():
    r = MetricsRegistry(reservoir=4)
    r.inc("a")
    r.inc("a", 4)
    r.gauge("g", 2.5)
    for v in range(10):
        r.observe("h", float(v))
    d = r.as_dict()
    assert d["counters"] == {"a": 5}
    assert d["gauges"] == {"g": 2.5}
    assert d["histograms"]["h"]["count"] == 10   # reservoir bounded at 4
    assert len(r.histograms["h"]) == 4


class _FakeStats:
    def __init__(self, n):
        self.n = n

    def line(self):
        return f"line {self.n}"


def test_stats_sink_stream_and_cadence():
    buf = io.StringIO()
    sink = StatsSink(stream=buf, every=3)
    for i in range(7):
        sink.emit(_FakeStats(i))
    out = buf.getvalue().splitlines()
    assert out == ["line 2", "line 5"]    # every 3rd call
    sink.emit(_FakeStats(99), force=True)
    assert buf.getvalue().splitlines()[-1] == "line 99"
    assert sink.n_lines == 3


# -- trace recorder + validator ------------------------------------------
def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    return clock


def test_trace_recorder_roundtrips_chrome_schema(tmp_path):
    tr = TraceRecorder(clock=_fake_clock())
    tr.thread_name(0, "engine")
    tr.begin("wave", 0, n=2)
    tr.instant("fork", 0, child=3)
    tr.counter("slots", 0, active=2, queue=1)
    tr.end(0, name="wave", tokens=2)
    tr.begin("queued", 5)
    tr.end(5)                             # unnamed E closes the top
    path = tmp_path / "t.json"
    tr.save(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    info = validate_chrome_trace(doc)
    assert info["spans"] == 2
    assert info["tracks"] == 2
    # span args land on both ends: B carries n, E carries tokens
    evs = {(e["ph"], e.get("name")): e for e in doc["traceEvents"]
           if e["ph"] in "BE"}
    assert evs[("B", "wave")]["args"] == {"n": 2}
    assert evs[("E", "wave")]["args"] == {"tokens": 2}


@pytest.mark.parametrize("events,err", [
    # unclosed span at end of trace
    ([{"ph": "B", "name": "x", "ts": 1, "pid": 0, "tid": 0}],
     "unclosed"),
    # E with no open span on the track
    ([{"ph": "E", "ts": 1, "pid": 0, "tid": 0}], "no open span"),
    # named E not matching the innermost open B
    ([{"ph": "B", "name": "a", "ts": 1, "pid": 0, "tid": 0},
      {"ph": "B", "name": "b", "ts": 2, "pid": 0, "tid": 0},
      {"ph": "E", "name": "a", "ts": 3, "pid": 0, "tid": 0}],
     "improper nesting"),
    # timestamps must be monotonic per track
    ([{"ph": "i", "name": "x", "ts": 5, "pid": 0, "tid": 0},
      {"ph": "i", "name": "y", "ts": 4, "pid": 0, "tid": 0}],
     "backwards"),
    # unknown phase letter
    ([{"ph": "Z", "name": "x", "ts": 1, "pid": 0, "tid": 0}],
     "phase"),
    # missing pid/tid
    ([{"ph": "i", "name": "x", "ts": 1}], "pid"),
])
def test_trace_validator_rejects_malformed(events, err):
    with pytest.raises(ValueError, match=err):
        validate_chrome_trace({"traceEvents": events})


def test_trace_validator_rejects_non_trace():
    with pytest.raises(ValueError):
        validate_chrome_trace([])
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})


# -- engine integration ---------------------------------------------------
@pytest.fixture(scope="module")
def lm():
    cfg = get_config("minimalist-lm-360m-smoke")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _submit_mixed(eng, cfg, n=4):
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(n):
        sp = (SamplingParams(temperature=0.9, top_k=8, seed=i)
              if i % 2 else None)
        reqs.append(eng.submit(rng.integers(0, cfg.vocab, size=3 + 2 * i),
                               max_new_tokens=3 + i, sampling=sp))
    return reqs


def test_engine_trace_smoke(lm, tmp_path):
    """Tier-1 smoke: a traced engine run saves valid Chrome JSON with a
    well-formed span tree and the expected span taxonomy."""
    cfg, model, params = lm
    tel = Telemetry(trace=True)
    sm = DecoderStepModel(model, max_len=32, prefill_chunk=8)
    eng = ServeEngine(sm, params, slots=2, telemetry=tel)
    reqs = _submit_mixed(eng, cfg)
    done = eng.run()
    assert len(done) == len(reqs)

    path = tmp_path / "trace.json"
    tel.save_trace(str(path))
    doc = json.loads(path.read_text())
    info = validate_chrome_trace(doc)     # raises on a malformed tree
    assert info["spans"] > 0
    # engine track + one track per request
    assert info["tracks"] == 1 + len(reqs)
    names = {e["name"] for e in doc["traceEvents"]
             if e["ph"] in ("B", "i")}
    assert {"admit", "prefill", "decode_wave",
            "queued", "running", "submit", "finish"} <= names
    # every request's lifecycle chain is closed: span count on a request
    # track == E count (validate_chrome_trace already checked pairing)
    m = eng.metrics()
    assert m["counters"]["requests_finished"] == len(reqs)
    assert m["jit"]["step_compiles"] == 1
    assert m["telemetry"]["counters"]["requests_submitted"] == len(reqs)
    assert m["telemetry"]["histograms"]["ttft_ms"]["count"] == len(reqs)


def test_metrics_without_telemetry(lm):
    """engine.metrics() is always available — counters/gauges/rates/jit
    need no Telemetry handle; the registry section appears only with
    one attached."""
    cfg, model, params = lm
    sm = DecoderStepModel(model, max_len=32, prefill_chunk=8)
    eng = ServeEngine(sm, params, slots=2)
    _submit_mixed(eng, cfg, n=2)
    eng.run()
    m = eng.metrics()
    assert set(m) == {"counters", "gauges", "rates", "jit"}
    assert m["counters"]["requests_finished"] == 2
    assert m["jit"]["step_compiles"] == 1
    assert 0.0 <= m["gauges"]["utilization"] <= 1.0


def test_stats_sink_drives_run(lm):
    """Telemetry(stats_stream=..., stats_every=N) replaces the old
    hardwired verbose print: same rendering, injectable stream and
    cadence."""
    cfg, model, params = lm
    buf = io.StringIO()
    tel = Telemetry(stats_stream=buf, stats_every=2)
    sm = DecoderStepModel(model, max_len=32, prefill_chunk=8)
    eng = ServeEngine(sm, params, slots=2, telemetry=tel)
    _submit_mixed(eng, cfg, n=3)
    eng.run()                             # no verbose flag needed
    lines = buf.getvalue().splitlines()
    assert lines and all(ln.startswith("[fifo") for ln in lines)
    assert tel.stats_sink.n_lines == len(lines)
    # every=2: one line per two steps driven by run()
    assert tel.stats_sink.n_calls > len(lines)


def test_deadline_miss_counter(lm):
    cfg, model, params = lm
    sm = DecoderStepModel(model, max_len=32, prefill_chunk=8)
    eng = ServeEngine(sm, params, slots=1)
    rng = np.random.default_rng(5)
    eng.submit(rng.integers(0, cfg.vocab, size=4), max_new_tokens=8,
               deadline=1)                # impossible: 8 tokens by step 1
    eng.submit(rng.integers(0, cfg.vocab, size=4), max_new_tokens=2)
    eng.run()
    assert eng.n_deadline_misses == 1
    assert eng.stats().deadline_misses == 1
    assert eng.metrics()["counters"]["deadline_misses"] == 1


# -- bitwise invariance + compile counts under tracing -------------------
@pytest.fixture(scope="module")
def spec_models():
    cfg = dataclasses.replace(get_config("smollm-360m-smoke"),
                              paged_impl="gather")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = get_config("minimalist-lm-360m-smoke")
    dmodel = build_model(dcfg)
    dparams = dmodel.init(jax.random.PRNGKey(1))
    return cfg, model, params, dmodel, dparams


LENS = [(7, 9), (13, 6), (5, 12)]
SPS = [None, dict(temperature=0.9, top_k=12, seed=3), None]


def _spec_engine(spec_models, telemetry):
    cfg, model, params, dmodel, dparams = spec_models
    sm = DecoderStepModel(model, max_len=64, prefill_chunk=8,
                          kv_layout="paged",
                          paged=PagedConfig(page_size=4))
    eng = ServeEngine(sm, params, slots=2, spec_k=3,
                      drafter=DraftStepModel(dmodel, spec_k=3),
                      drafter_params=dparams, telemetry=telemetry)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, size=p),
                       max_new_tokens=g,
                       sampling=SamplingParams(**sp) if sp else None)
            for (p, g), sp in zip(LENS, SPS)]
    return eng, sm, reqs


def _drive_with_preempt(eng, sm, reqs):
    """Two steps, force-evict every active slot, then drain."""
    eng.step()
    eng.step()
    victims = [int(s) for s in np.flatnonzero(eng.active)]
    assert victims
    for s in victims:
        eng._preempt(s)
    eng.run()
    assert eng.pool.pages_in_use == 0
    return [list(r.tokens) for r in reqs]


def test_traced_spec_preempt_bitwise_and_single_compile(spec_models,
                                                        tmp_path):
    """The acceptance worst case: paged + speculative + forced
    preemption with FULL tracing on emits bitwise the untraced streams,
    every jitted program compiles once, and the trace round-trips the
    Chrome schema with preempt/resume/spec spans present."""
    eng0, sm0, reqs0 = _spec_engine(spec_models, telemetry=None)
    ref = _drive_with_preempt(eng0, sm0, reqs0)

    tel = Telemetry(trace=True)
    eng, sm, reqs = _spec_engine(spec_models, telemetry=tel)
    got = _drive_with_preempt(eng, sm, reqs)
    assert got == ref                     # tracing changed NOTHING

    m = eng.metrics()
    assert m["jit"]["verify_compiles"] == 1
    assert m["jit"]["draft_propose_compiles"] == 1
    assert eng.n_preemptions == eng0.n_preemptions > 0
    assert m["counters"]["preemptions"] == eng.n_preemptions
    assert m["counters"]["drafts_accepted"] == eng0.n_drafts_accepted

    path = tmp_path / "spec_preempt_trace.json"
    tel.save_trace(str(path))
    doc = json.loads(path.read_text())
    info = validate_chrome_trace(doc)     # well-formed span tree
    assert info["tracks"] == 1 + len(reqs)
    names = {e["name"] for e in doc["traceEvents"]
             if e["ph"] in ("B", "i")}
    assert {"spec_wave", "propose", "verify", "preempt", "resume",
            "preempted", "running", "queued", "finish"} <= names
    # the preempted request's track carries the full lifecycle chain:
    # queued -> running -> preempted -> running (validator guarantees
    # every B on the track was closed)
    uid = next(r for r in reqs if r.n_preemptions).uid
    chain = [e["name"] for e in doc["traceEvents"]
             if e["tid"] == uid + 1 and e["ph"] == "B"]
    assert chain[:2] == ["queued", "running"]
    assert "preempted" in chain
    assert chain.index("preempted") < len(chain) - 1  # resumed after


def test_traced_plain_engine_bitwise(lm):
    """Dense / non-spec path: telemetry on vs off, identical streams
    and one compiled step."""
    cfg, model, params = lm

    def go(telemetry):
        sm = DecoderStepModel(model, max_len=32, prefill_chunk=8)
        eng = ServeEngine(sm, params, slots=2, telemetry=telemetry)
        reqs = _submit_mixed(eng, cfg)
        eng.run()
        assert sm._jit_step._cache_size() == 1
        return [list(r.tokens) for r in reqs]

    assert go(Telemetry(trace=True)) == go(None)


# -- engine spans: the step tree, the profiler sink, compile counting ----
STEP_ARGS = {"admitted", "prefill_tokens", "active_slots", "slots",
             "queue_depth", "pages_reserved", "pages_in_use",
             "preemptions", "compiles"}


def test_recorder_spans_on_the_host_clock():
    """spans() rebuilds the B/E tree of a track on the recorder's clock:
    t0 + ts, begin args updated by end args, parent indices; a span left
    open, or begun outside the bounds, is dropped and its children become
    tops."""
    tr = TraceRecorder(clock=_fake_clock())
    assert tr.t0 == pytest.approx(0.001)
    tr.begin("outer", 0, a=1)             # ts 1000 us
    tr.begin("inner", 0)
    tr.end(0, name="inner", n=2)
    tr.end(0, name="outer", b=3)
    tr.begin("other track", 4)
    tr.end(4)
    tr.begin("open", 0)
    tr.begin("child", 0)
    tr.end(0, name="child")
    sp = tr.spans()
    assert [s.name for s in sp] == ["outer", "inner", "child"]
    assert [s.parent for s in sp] == [-1, 0, -1]
    assert sp[0].args == {"a": 1, "b": 3}
    assert sp[1].args == {"n": 2}
    assert sp[0].start == pytest.approx(tr.t0 + 0.001)
    assert sp[0].end == pytest.approx(tr.t0 + 0.004)
    assert sp[1].start >= sp[0].start and sp[1].end <= sp[0].end
    assert [s.name for s in tr.spans(tid=4)] == ["other track"]
    # bounds on the begin: a child whose parent begun earlier is a top
    late = tr.spans(since=sp[1].start)
    assert [(s.name, s.parent) for s in late] == [("inner", -1),
                                                  ("child", -1)]
    assert [s.name for s in tr.spans(until=sp[1].start)] == ["outer"]


def _children(spans, i):
    return [s.name for s in spans if s.parent == i]


def _paged_lm():
    cfg = dataclasses.replace(get_config("smollm-360m-smoke"),
                              paged_impl="gather")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_step_span_tree(lm, layout, tmp_path):
    """Every step is one `step` span holding its admission and decode
    wave; the prefill and decode waves split into run/install/sync and
    prepare/dispatch/sync/book; the args say what the step did."""
    if layout == "dense":
        cfg, model, params = lm
        sm = DecoderStepModel(model, max_len=32, prefill_chunk=8)
    else:
        cfg, model, params = _paged_lm()
        sm = DecoderStepModel(model, max_len=32, prefill_chunk=8,
                              kv_layout="paged",
                              paged=PagedConfig(page_size=4))
    tel = Telemetry(trace=True)
    eng = ServeEngine(sm, params, slots=2, telemetry=tel)
    reqs = _submit_mixed(eng, cfg)
    eng.run()
    tel.close()
    validate_chrome_trace(tel.trace.to_json())
    sp = tel.trace.spans()
    tops = [i for i, s in enumerate(sp) if s.parent == -1]
    assert {sp[i].name for i in tops} == {"step"}
    for i in tops:
        assert STEP_ARGS <= set(sp[i].args)
        assert sp[i].args["slots"] == 2
        kids = _children(sp, i)
        assert kids[0] == "admit" and set(kids[1:]) <= {"decode_wave"}
    prefills = [i for i, s in enumerate(sp) if s.name == "prefill"]
    assert prefills
    for i in prefills:
        assert sp[sp[i].parent].name == "admit"
        assert _children(sp, i) == ["prefill.run", "prefill.install",
                                    "prefill.sync"]
        run = next(s for s in sp if s.parent == i)
        assert run.args["rows"] == sp[i].args["wave"]
        assert run.args["chunks"] == sp[i].args["chunks"]
    waves = [i for i, s in enumerate(sp) if s.name == "decode_wave"]
    assert len(waves) == eng.n_steps
    for i in waves:
        assert sp[sp[i].parent].name == "step"
        assert _children(sp, i) == ["decode.prepare", "decode.dispatch",
                                    "decode.sync", "decode.book"]
        assert sp[i].args["ctx_tokens"] >= sp[i].args["active_slots"] > 0
    # the step args sum to the run's totals
    steps = [sp[i].args for i in tops]
    assert sum(a["admitted"] for a in steps) == len(reqs)
    assert sum(a["prefill_tokens"] for a in steps) == \
        sum(len(r.prompt) for r in reqs)
    assert steps[-1]["active_slots"] == 0
    assert steps[-1]["preemptions"] == 0
    comp = [a["compiles"] for a in steps]
    assert comp[0] > 0 and comp == sorted(comp)
    if layout == "paged":
        assert max(a["pages_reserved"] for a in steps) > 0
        assert max(a["pages_in_use"] for a in steps) > 0
    m = eng.metrics()
    assert m["counters"]["requests_admitted"] == len(reqs)
    assert m["counters"]["prefill_tokens"] == \
        sum(len(r.prompt) for r in reqs)
    assert "itl_ms" not in m["telemetry"]["histograms"]
    assert m["telemetry"]["histograms"]["step_ms"]["count"] == eng.n_steps


def test_spec_step_span_tree(spec_models):
    """The propose/verify wave splits the same way, and every step span
    carries the preemptions so far."""
    tel = Telemetry(trace=True)
    eng, sm, reqs = _spec_engine(spec_models, telemetry=tel)
    _drive_with_preempt(eng, sm, reqs)
    tel.close()
    sp = tel.trace.spans()
    waves = [i for i, s in enumerate(sp) if s.name == "spec_wave"]
    assert waves
    for i in waves:
        assert _children(sp, i) == ["decode.prepare", "propose", "verify",
                                    "decode.sync", "decode.book"]
    steps = [s.args for s in sp if s.name == "step"]
    assert steps[0]["preemptions"] == 0
    assert steps[-1]["preemptions"] == eng.n_preemptions > 0


def _profiled_host_events(tmp_path, fn):
    """Run fn under a CPU profiler session -> the host events as
    (name, start_ns, end_ns)."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_engine_spans_land_in_the_profile(lm, tmp_path):
    """Under a profiler session the engine's spans are host events named
    serve.<name> that nest as the recorder's do."""
    cfg, model, params = lm
    tel = Telemetry(trace=True)
    eng = ServeEngine(DecoderStepModel(model, max_len=32, prefill_chunk=8),
                      params, slots=2, telemetry=tel)
    _submit_mixed(eng, cfg)
    evs = [e for e in _profiled_host_events(tmp_path, eng.run)
           if e[0].startswith("serve.")]
    tel.close()
    names = {e[0] for e in evs}
    assert {"serve.step", "serve.admit", "serve.prefill",
            "serve.prefill.run", "serve.prefill.install",
            "serve.prefill.sync", "serve.decode_wave",
            "serve.decode.prepare", "serve.decode.dispatch",
            "serve.decode.sync", "serve.decode.book"} <= names
    n_rec = sum(1 for s in tel.trace.spans())
    assert len(evs) == n_rec              # one profiler event per span

    def inside(e, outer):
        return any(o[0] == outer and o[1] <= e[1] and e[2] <= o[2]
                   for o in evs)

    for e in evs:
        if e[0].startswith("serve.decode."):
            assert inside(e, "serve.decode_wave")
        if e[0].startswith("serve.prefill."):
            assert inside(e, "serve.prefill")
        if e[0] in ("serve.prefill", "serve.admit"):
            assert inside(e, "serve.admit" if e[0] == "serve.prefill"
                          else "serve.step")
        if e[0] == "serve.decode_wave":
            assert inside(e, "serve.step")


def test_null_telemetry_leaves_no_profile_spans(lm, tmp_path):
    cfg, model, params = lm
    eng = ServeEngine(DecoderStepModel(model, max_len=32, prefill_chunk=8),
                      params, slots=2)
    _submit_mixed(eng, cfg)
    evs = _profiled_host_events(tmp_path, eng.run)
    assert evs                            # the session recorded the host
    assert not [e for e in evs if e[0].startswith("serve.")]


def test_telemetry_counts_every_compile():
    """Backend compiles outside any jitted wrapper (an eager pad of a new
    shape) count too, as `compiles` and a `compile` instant with the
    program's name and seconds; close() stops the count."""
    import jax.numpy as jnp
    tel = Telemetry(trace=True)
    jnp.pad(jnp.zeros((3, 1237), jnp.int32), ((0, 0), (0, 11)))
    n = tel.registry.counters.get("compiles", 0)
    assert n >= 1
    inst = [e for e in tel.trace.events
            if e["ph"] == "i" and e["name"] == "compile"]
    assert len(inst) == n
    assert all(e["args"]["seconds"] >= 0 and e["args"]["program"]
               for e in inst)
    tel.close()
    tel.close()                           # idempotent
    jnp.pad(jnp.zeros((3, 1239), jnp.int32), ((0, 0), (0, 13)))
    assert tel.registry.counters["compiles"] == n


def test_decode_wave_kv_walk_counter(monkeypatch):
    """`decode_wave` ends with `kv_blocks` (the blocks the paged GQA
    kernel walks that wave) and `kv_blocks_grid` (the blocks a walk of
    every page of every slot would take), summed over the layers that
    run the kernel; with telemetry off nothing is counted."""
    import jax.numpy as jnp

    from repro.kernels.paged_attention.paged_attention import (
        blocks_walked, pages_per_block)
    cfg = dataclasses.replace(get_config("smollm-360m-smoke"),
                              paged_impl="pallas")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # 1040 tokens of 16-token pages: 65 pages in blocks of 32, three
    # blocks for a full walk of one slot
    mk = lambda: DecoderStepModel(model, max_len=1040,  # noqa: E731
                                  prefill_chunk=64, kv_layout="paged",
                                  paged=PagedConfig(page_size=16))
    sm = mk()
    assert pages_per_block(16, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
                           65) == 32
    layers = cfg.n_layers
    # live tokens 1, 512, 513, 1040 and an inactive slot: 1+1+2+3 blocks
    pos = np.array([0, 511, 512, 1039, 700])
    active = np.array([True, True, True, True, False])
    assert blocks_walked(pos, active, length=1040, page_size=16,
                         ppb=32) == (7, 15)
    assert sm.kv_walk(pos, active) == (7 * layers, 15 * layers)

    tel = Telemetry(trace=True)
    eng = ServeEngine(sm, params, slots=2, telemetry=tel)
    rng = np.random.default_rng(5)
    for n in (5, 520):
        eng.submit(rng.integers(0, cfg.vocab, size=n), max_new_tokens=3)
    eng.run()
    tel.close()
    waves = [s.args for s in tel.trace.spans() if s.name == "decode_wave"]
    # both slots decode from positions 5 and 520 on: live 6 and 521
    # tokens, then 7 and 522 — one block and two each wave
    assert [(a["ctx_tokens"], a["kv_blocks"], a["kv_blocks_grid"])
            for a in waves] == [(6 + 521, 3 * layers, 6 * layers),
                                (7 + 522, 3 * layers, 6 * layers)]

    def no_count(*_a):
        raise AssertionError("kv_walk called with telemetry off")
    quiet = mk()
    monkeypatch.setattr(quiet, "kv_walk", no_count)
    eng = ServeEngine(quiet, params, slots=2)
    eng.submit(rng.integers(0, cfg.vocab, size=5), max_new_tokens=3)
    assert len(eng.run()) == 1
