#!/usr/bin/env python3
"""Bring-up check: serve the full-width models on a TPU through the normal
entry points (``get_config`` -> ``build_model`` -> ``launch.serve.
build_engine`` -> ``ServeEngine.submit/run``), in one process.

    python chip_smoke.py             # one chip: minGRU and GQA-paged phases
    python chip_smoke.py --chips 4   # four chips: minGRU under a 2x2 mesh only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # CPU, -smoke presets

Phases (each serves one seeded traffic shape twice and compares):

  * ``mingru``   — minimalist-lm-360m with ``scan_backend="pallas"`` (the
    compiled ``linear_scan`` kernel) against ``scan_backend="xla"``.
  * ``gqa_paged`` — smollm-360m on the paged KV pool with
    ``paged_impl="pallas"`` (the compiled ``paged_gqa_decode`` kernel)
    against the ``gather`` oracle.
  * ``mesh`` (``--chips 4`` only) — minimalist-lm-360m (default XLA scan)
    under a ``data=2 x model=2`` mesh against the same requests served
    with no mesh on one device.

Greedy tokens must agree up to the first near-tie: a divergence is accepted
only where the reference model's top-two logits at that position lie within
``TIE_RTOL`` of each other.  Every phase prints its compile count and time,
peak device memory and agreement figures.  The one-chip phases check that
the compiled step holds its kernel as a ``tpu_custom_call``; the mesh
phase checks that the decode step all-reduces across the mesh.  Any
failed check raises, so the script exits non-zero; off the TPU it exits 2
unless ``--rehearse`` is given.  The last line of standard output is one
JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import ServeConfig, get_config  # noqa: E402
from repro.kernels.dispatch import tpu_kernels  # noqa: E402
from repro.kernels.paged_attention.ops import paged_gqa_attention  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.launch.serve import build_engine  # noqa: E402
from repro.models import build_model  # noqa: E402

SEED = 0
# Prefill logits of two bf16 paths that differ only in rounding: the
# pallas and xla scans (both accumulate in fp32 and round h to bf16 once,
# so they differ by rare one-ulp flips carried through 32 layers), or a
# TP=2 reduction order against one device.  Bound: max|d logits| <=
# LOGIT_RTOL * max|reference logits|.
LOGIT_RTOL = 2e-2
# Paged decode read, kernel vs gather oracle on the live pool.  In
# interpret mode they agree to 2e-6 (tests/test_kernels_paged_attention);
# on the chip the oracle's fp32 P.V matmul runs at XLA's default (one bf16
# pass) precision, which rounds the probabilities to 2^-9 relative.  Bound:
# max|d out| <= PAGED_RTOL * max|oracle out|.
PAGED_RTOL = 1e-2
# A greedy divergence is a near-tie when the reference's top-1 and top-2
# logits differ by at most TIE_RTOL * max|logits| at that position.
TIE_RTOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Traffic:
    slots: int
    requests: int
    prompt: tuple      # inclusive (min, max) prompt length
    gen: int           # new tokens per request
    chunk: int         # prefill chunk
    max_len: int       # KV-cache length of the attention model
    suffix: str        # config-name suffix ("" = published widths)


FULL = Traffic(slots=16, requests=16, prompt=(256, 1024), gen=32, chunk=256,
               max_len=2048, suffix="")
REHEARSAL = Traffic(slots=4, requests=4, prompt=(8, 40), gen=8, chunk=16,
                    max_len=64, suffix="-smoke")


class CheckFailed(RuntimeError):
    """A bring-up check did not hold."""


def check(cond, msg):
    if not cond:
        raise CheckFailed(msg)


class CompileLog:
    """Counts XLA backend compiles and persistent-cache hits, with time."""

    def __init__(self):
        self.n = self.seconds = self.cache_hits = 0

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def line(self):
        return (f"{self.n} backend compiles in {self.seconds:.1f} s, "
                f"{self.cache_hits} persistent-cache hits")


class FirstCall:
    """Pass-through around a jitted program that keeps the abstract
    arguments of its first call, so that the program can be lowered and
    compiled again for its HLO text."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            self.args = jax.tree_util.tree_map(_abstract, args)
        return self.fn(*args)

    def __getattr__(self, name):          # _cache_size() and friends
        return getattr(self.fn, name)

    def hlo(self):
        check(self.args is not None, "program was never called")
        return self.fn.lower(*self.args).compile().as_text()


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    if isinstance(x, np.ndarray):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def check_kernel(rec: FirstCall, kernel, platform, tag):
    if platform != "tpu":
        print(f"[{tag}] HLO check skipped: {platform} runs Pallas in "
              "interpret mode")
        return
    found = tpu_kernels(rec.hlo())
    print(f"[{tag}] tpu_custom_call kernels in the compiled step: "
          f"{sorted(found)}")
    check(kernel in found, f"{kernel} is not a tpu_custom_call in the "
          "compiled step (interpret mode or fallback)")


def peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def make_prompts(traffic, vocab):
    rng = np.random.default_rng(SEED)
    lens = rng.integers(traffic.prompt[0], traffic.prompt[1] + 1,
                        traffic.requests)
    return [rng.integers(0, vocab, int(n), dtype=np.int64).astype(np.int32)
            for n in lens]


def engine(model, params, traffic, *, mesh=None, **serve_kw):
    return build_engine(model, params, ServeConfig(
        slots=traffic.slots, max_len=traffic.max_len,
        prefill_chunk=traffic.chunk, **serve_kw), mesh=mesh)


def record(eng, attr):
    """Install a :class:`FirstCall` over the step model's program."""
    rec = FirstCall(getattr(eng.sm, attr))
    setattr(eng.sm, attr, rec)
    return rec


def serve(eng, prompts, traffic):
    """Serve ``prompts`` greedily to completion -> token lists."""
    reqs = [eng.submit(p, max_new_tokens=traffic.gen) for p in prompts]
    eng.run()
    out = [list(map(int, r.outputs)) for r in reqs]
    check(all(len(o) == traffic.gen for o in out),
          f"a request emitted fewer than {traffic.gen} tokens")
    check(eng.metrics()["jit"]["step_compiles"] == 1,
          "the decode step compiled more than once")
    return out


def prefill_logits(eng, prompts, vocab):
    """Last-token prefill logits of each prompt through the engine's step
    model -> ((n, vocab) fp32, recorder of the prefill program)."""
    out, rec = [], None
    for p in prompts:
        last, _carry = eng.sm.prefill(eng.params, p[None])
        out.append(np.asarray(last[0, :vocab], np.float32))
        if rec is None:               # built lazily by the first prefill
            rec = record(eng, "_jit_prefill_fast")
    return np.stack(out), rec


def compare_logits(got, want, rtol, what, tag):
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    print(f"[{tag}] {what}: max|d| {err:.6g}, max|ref| {scale:.6g}, "
          f"relative {err / scale:.6g} (tolerance {rtol})")
    check(err <= rtol * scale, f"{what} disagree beyond {rtol}")


def compare_streams(ref_model, params, prompts, got, want, traffic, tag):
    """Greedy streams ``got`` vs reference ``want``: equal up to the first
    divergence, and each divergence sits on a reference near-tie (top-2
    margin <= TIE_RTOL * max|logit| of the reference model's forward over
    the agreed prefix)."""
    width = traffic.prompt[1] + traffic.gen      # one forward shape
    fwd = jax.jit(ref_model.__call__)
    vocab = ref_model.cfg.vocab
    first, n_div = None, 0
    for i, (p, g, w) in enumerate(zip(prompts, got, want)):
        j = next((k for k, (a, b) in enumerate(zip(g, w)) if a != b), None)
        if j is None:
            continue
        n_div += 1
        first = j if first is None else min(first, j)
        seq = np.zeros((1, width), np.int32)
        ctx = np.concatenate([p, np.asarray(w[:j], np.int32)])
        seq[0, :len(ctx)] = ctx
        logits = np.asarray(fwd(params, jnp.asarray(seq))[
            0, len(ctx) - 1, :vocab], np.float32)
        top2 = np.sort(logits)[-2:]
        margin = float(top2[1] - top2[0])
        bound = TIE_RTOL * float(np.abs(logits).max())
        print(f"[{tag}] request {i}: first divergence at generated token "
              f"{j} (got {g[j]}, reference {w[j]}); reference top-2 "
              f"margin {margin:.5g} vs near-tie bound {bound:.5g}")
        check(margin <= bound, f"request {i} diverges at token {j} where "
              f"the reference is not near a tie ({margin} > {bound})")
    n_tok = sum(len(w) for w in want)
    agree = sum(sum(a == b for a, b in zip(g, w))
                for g, w in zip(got, want))
    print(f"[{tag}] greedy tokens: {agree}/{n_tok} equal, "
          f"{n_div}/{len(want)} requests diverge, first divergence at "
          f"generated token {first}")


def phase_mingru(traffic, platform):
    tag = "mingru"
    cfg = get_config("minimalist-lm-360m" + traffic.suffix)
    model_k = build_model(dataclasses.replace(cfg, scan_backend="pallas"))
    model_x = build_model(dataclasses.replace(cfg, scan_backend="xla"))
    params = model_x.init(jax.random.PRNGKey(SEED))
    prompts = make_prompts(traffic, cfg.vocab)
    eng_k, eng_x = engine(model_k, params, traffic), \
        engine(model_x, params, traffic)
    lk, rec = prefill_logits(eng_k, prompts, cfg.vocab)
    lx, _ = prefill_logits(eng_x, prompts, cfg.vocab)
    compare_logits(lk, lx, LOGIT_RTOL, "prefill logits, pallas vs xla scan",
                   tag)
    compare_streams(model_x, params, prompts, serve(eng_k, prompts, traffic),
                    serve(eng_x, prompts, traffic), traffic, tag)
    check_kernel(rec, "linear_scan", platform, tag)


def _pools(state):
    """(k, v) pool pairs of every paged attention layer (scanned units
    stack their repeats on a leading axis)."""
    for sub in state.values():
        if isinstance(sub, dict) and "k" in sub and "v" in sub:
            k, v = sub["k"], sub["v"]
            if k.ndim == 4:
                yield k, v
            else:
                yield from zip(k, v)


def phase_gqa_paged(traffic, platform):
    tag = "gqa_paged"
    cfg = get_config("smollm-360m" + traffic.suffix)
    model_k = build_model(dataclasses.replace(cfg, paged_impl="pallas"))
    model_g = build_model(dataclasses.replace(cfg, paged_impl="gather"))
    params = model_g.init(jax.random.PRNGKey(SEED))
    prompts = make_prompts(traffic, cfg.vocab)
    paged = dict(kv_layout="paged", page_size=16)

    eng = engine(model_k, params, traffic, **paged)
    rec = record(eng, "_jit_step")
    reqs = [eng.submit(p, max_new_tokens=traffic.gen) for p in prompts]
    eng.step()           # admit every request and decode once
    # the decode read on the live pool, kernel vs oracle: every layer's
    # pages, one seeded random query per slot
    live = np.asarray(eng.st.active)
    check(live.all(), "a slot is idle after the first step")
    q = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (traffic.slots, cfg.n_heads, cfg.head_dim),
                          jnp.bfloat16)
    bt = jnp.asarray(eng.pool.block_tables, jnp.int32)
    pos = jnp.asarray(eng.st.pos, jnp.int32)
    outs = {b: np.stack([np.asarray(paged_gqa_attention(
        q, k, v, bt, pos, length=traffic.max_len, backend=b), np.float32)
        for k, v in _pools(eng.state)]) for b in ("pallas", "xla")}
    compare_logits(outs["pallas"], outs["xla"], PAGED_RTOL,
                   f"paged decode read on the live pool ({len(outs['xla'])} "
                   "layers), kernel vs gather", tag)
    eng.run()
    got = [list(map(int, r.outputs)) for r in reqs]
    check(all(len(g) == traffic.gen for g in got), "short streams")
    check(eng.metrics()["jit"]["step_compiles"] == 1,
          "the decode step compiled more than once")
    check(eng.pool.pages_in_use == 0, "the page pool did not drain")
    check_kernel(rec, "paged_gqa_decode", platform, tag)
    del eng, rec         # free the kernel engine's pool first
    want = serve(engine(model_g, params, traffic, **paged), prompts, traffic)
    compare_streams(model_g, params, prompts, got, want, traffic, tag)


def _devices_of(tree):
    return set().union(*(x.sharding.device_set
                         for x in jax.tree_util.tree_leaves(tree)))


def _split(tree):
    """True where some leaf is split across devices (not replicated)."""
    return any(x.sharding.shard_shape(x.shape) != x.shape
               for x in jax.tree_util.tree_leaves(tree))


def phase_mesh(traffic, platform):
    """minGRU under TP 2 x DP 2 against one device.  The config keeps its
    default XLA scan: the compiled Pallas kernels cannot be partitioned
    by the compiler (they would need a shard_map)."""
    tag = "mesh"
    cfg = get_config("minimalist-lm-360m" + traffic.suffix)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    prompts = make_prompts(traffic, cfg.vocab)
    eng_m = engine(model, params, traffic,
                   mesh=make_local_mesh(data=2, model=2))
    eng_1 = engine(model, params, traffic)
    rec = record(eng_m, "_jit_step")
    lm, _ = prefill_logits(eng_m, prompts, cfg.vocab)
    l1, _ = prefill_logits(eng_1, prompts, cfg.vocab)
    compare_logits(lm, l1, LOGIT_RTOL,
                   "prefill logits, 2x2 mesh vs one device", tag)
    got, want = serve(eng_m, prompts, traffic), serve(eng_1, prompts,
                                                      traffic)
    dev_p, dev_s = _devices_of(eng_m.params), _devices_of(eng_m.state)
    print(f"[{tag}] mesh engine: params on devices "
          f"{sorted(d.id for d in dev_p)}, state on devices "
          f"{sorted(d.id for d in dev_s)}")
    check(len(dev_p) == 4 and len(dev_s) == 4,
          "params and state must sit on four distinct devices")
    check(_split(eng_m.params), "no parameter is split over 'model' (TP)")
    check(_split(eng_m.state), "no state leaf is split over 'data' (DP)")
    one = jax.devices()[0]
    check(_devices_of(eng_1.params) == {one} and
          _devices_of(eng_1.state) == {one},
          "the no-mesh reference left device 0")
    hlo = rec.hlo()
    coll = sorted(c for c in ("all-reduce", "all-gather", "all-to-all",
                              "reduce-scatter", "collective-permute")
                  if f" {c}(" in hlo or f" {c}-start(" in hlo)
    print(f"[{tag}] collectives in the compiled mesh decode step: {coll}")
    check("all-reduce" in coll, "the TP decode step has no all-reduce")
    compare_streams(model, params, prompts, got, want, traffic, tag)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-mesh phase on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a non-TPU platform and use the -smoke "
                         "presets (CPU rehearsal; never a chip result)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: platform is {platform!r}, not 'tpu' (pass "
              "--rehearse for a CPU rehearsal)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    traffic = REHEARSAL if args.rehearse else FULL
    phases = ([phase_mesh] if args.chips == 4
              else [phase_mingru, phase_gqa_paged])
    used = devices[:args.chips]
    print(f"device: {platform} / {kind} x {len(used)}; traffic: {traffic}")
    for phase in phases:
        t0 = time.perf_counter()
        with CompileLog() as log:
            phase(traffic, platform)
        peak = peak_bytes(used)
        print(f"[{phase.__name__[6:]}] {log.line()}; wall "
              f"{time.perf_counter() - t0:.1f} s; peak_bytes_in_use "
              f"{'not reported' if peak is None else peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
