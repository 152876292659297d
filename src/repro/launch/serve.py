"""Serving driver: continuous-batching streaming decode (repro.serve).

    PYTHONPATH=src python -m repro.launch.serve --arch minimalist-lm-360m \
        --smoke --requests 16 --slots 4 --prompt-len 32 --gen 32

The engine admits requests of mixed prompt/generation lengths into a
fixed-capacity slot batch: prompts are consumed by the grid-padded
chunked prefill (one ``linear_scan`` per chunk for the O(1)-state mixers
— the paper's edge-inference property — and exactly one compiled chunk
shape across ragged prompt lengths), decode is ONE jitted slot-batch step
per token, and finished sequences retire the step they complete so their
slots go straight back into circulation.  ``--temperature/--top-k/--top-p``
turn on per-request sampling (counter-based PRNG: reproducible per
request, same compiled step as greedy).  ``--mesh DxM`` serves under a
local device mesh (TP params/caches over "model", DP slots over "data";
README §Sharded serving).  ``--kv-layout paged`` stores attention K/V in
a shared page pool with per-request block tables (``--page-size``,
``--num-pages``; README §Paged KV cache) — memory scales with live
tokens and admission defers when the pool is full.  ``--drafter ARCH
--spec-k K`` turns on speculative decoding: a pure-recurrent draft
model proposes ``k-1`` greedy tokens per wave and the target verifies
all ``k`` in one paged call (README §Speculative decoding; greedy
streams stay bitwise identical to plain decode).  ``--baseline`` runs
the old static-batch loop instead (kept as the benchmark reference).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs import SamplingParams, ServeConfig, get_config
from repro.launch.mesh import make_local_mesh, mesh_info
from repro.models import build_model
from repro.serve import DecoderStepModel, ServeEngine, Telemetry


def generate(model, params, prompts, *, max_len, gen_tokens):
    """Static-batch baseline: per-token prefill + lock-step greedy decode.

    prompts: (B, P) int32. Returns (B, gen_tokens) generated ids.  Every
    sequence occupies its batch row for the full P + gen_tokens steps —
    the reference the continuous-batching engine is benchmarked against.
    """
    B, P = prompts.shape
    cache = model.init_cache(B, max_len)

    @jax.jit
    def step(params, cache, tok, pos):
        logits, cache = model.decode_step(params, tok, cache, pos)
        return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), cache

    # prefill: feed prompt tokens, ignore logits
    tok = None
    for t in range(P):
        tok, cache = step(params, cache, prompts[:, t:t + 1], jnp.int32(t))
    out = []
    for t in range(gen_tokens):
        out.append(tok)
        tok, cache = step(params, cache, tok[:, None], jnp.int32(P + t))
    return jnp.stack(out, axis=1)


def build_engine(model, params, serve: ServeConfig = ServeConfig(),
                 mesh=None, telemetry=None):
    kw = {}
    if serve.kv_layout == "paged":
        from repro.serve import PagedConfig
        kw = dict(kv_layout="paged",
                  paged=PagedConfig(page_size=serve.page_size,
                                    num_pages=serve.num_pages))
    sm = DecoderStepModel(model, max_len=serve.max_len,
                          prefill_chunk=serve.prefill_chunk, **kw)
    if serve.drafter:
        from repro.serve import DraftStepModel
        dcfg = get_config(serve.drafter)
        dmodel = build_model(dcfg)
        dparams = dmodel.init(jax.random.PRNGKey(1))
        kw = dict(drafter=DraftStepModel(
                      dmodel, spec_k=serve.spec_k,
                      prefill_chunk=serve.prefill_chunk),
                  drafter_params=dparams, spec_k=serve.spec_k)
    else:
        kw = {}
    return ServeEngine(sm, params, slots=serve.slots, mesh=mesh,
                       prefix_cache=serve.prefix_cache,
                       policy=serve.policy, telemetry=telemetry, **kw)


def parse_mesh(spec: str):
    """'DxM' -> a local (data=D, model=M) mesh; '' -> None (no mesh)."""
    if not spec:
        return None
    try:
        d, m = (int(v) for v in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"--mesh expects DxM (e.g. 2x2), got {spec!r}")
    return make_local_mesh(model=m, data=d)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minimalist-lm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="mean prompt length; actual lengths vary ±50%%")
    ap.add_argument("--gen", type=int, default=32,
                    help="mean generation budget; actual budgets vary ±50%%")
    ap.add_argument("--prefill-chunk", type=int, default=256)
    ap.add_argument("--max-len", type=int, default=0,
                    help="attention cache length (default: fits the longest "
                         "request)")
    ap.add_argument("--scan-backend", default=None,
                    choices=[None, "seq", "xla", "pallas", "pallas_tpu"],
                    help="linear-scan backend for recurrent prefill")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "pooled", "per_request", "auto"],
                    help="MoE dispatch mode (MoE stacks only): 'auto' "
                         "(default) serves batch-invariantly — gather-GEMM "
                         "decode + per-request prefill; 'pooled' reverts "
                         "to the capacity-limited training dispatch, whose "
                         "routing depends on co-batched traffic")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter (0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass (1.0 disables)")
    ap.add_argument("--seed", type=int, default=0,
                    help="per-request PRNG seed base (request i uses "
                         "seed+i; decoding is reproducible per request)")
    ap.add_argument("--mesh", default="",
                    help="serve under a DxM local device mesh (e.g. 2x2 = "
                         "data 2 x model 2): params and caches TP-shard "
                         "over 'model' via the logical-axis rules, slots "
                         "DP-shard over 'data'; needs D*M local devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N fakes them on CPU)")
    ap.add_argument("--kv-layout", default="dense",
                    choices=["dense", "paged"],
                    help="attention KV-cache layout: 'dense' preallocates "
                         "(slots, max_len) rows per slot; 'paged' shares "
                         "a page pool with per-request block tables so "
                         "memory scales with live tokens (README §Paged "
                         "KV cache)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=["bf16", "int8"],
                    help="paged KV-pool storage dtype: 'int8' stores "
                         "symmetric per-page codes + float32 scales per "
                         "page per KV head — half the pool bytes, so "
                         "~2x the concurrent requests fit a fixed pool "
                         "(README §Paged KV cache)")
    ap.add_argument("--paged-impl", default=None,
                    choices=["gather", "pallas", "pallas_tpu"],
                    help="paged decode read: 'pallas' (default) = the "
                         "block-table kernel, interpret off-TPU / "
                         "compiled on TPU; 'gather' = dense-view oracle "
                         "(bitwise-dense, slower); 'pallas_tpu' = "
                         "compiled only")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool capacity; 0 auto-sizes to the dense "
                         "equivalent (slots x pages-per-max-len-request) "
                         "— set lower to actually cap memory (admission "
                         "defers when the pool is full)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged layout only: pin finished prompts' pages "
                         "so requests sharing a page-aligned prompt "
                         "prefix attach to them and prefill only the "
                         "tail (README §Prefix caching)")
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "priority", "sjf", "edf"],
                    help="admission/preemption policy: 'fifo' = strict "
                         "arrival order with defer-at-head; 'priority' "
                         "= per-request priority classes (may preempt "
                         "lower-priority running requests under the "
                         "paged layout); 'sjf' = shortest-prefill-first "
                         "with aging; 'edf' = earliest-deadline-first "
                         "(submit(deadline=...); may preempt later-"
                         "deadline running requests under the paged "
                         "layout) (README §Scheduling & preemption)")
    ap.add_argument("--drafter", default="",
                    help="speculative decoding: arch name of a pure "
                         "O(1)-state draft model (e.g. minimalist-lm-"
                         "360m-smoke) proposing greedy k-token waves "
                         "the target verifies in one paged call; needs "
                         "--kv-layout paged and a matching vocab "
                         "(README §Speculative decoding)")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="speculative verify width: tokens decided per "
                         "wave per slot (1 = off; needs --drafter)")
    ap.add_argument("--verbose", action="store_true",
                    help="print a per-step stats line (occupancy, "
                         "queue depth, pool pages, preemptions)")
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="record request-lifecycle + wave spans and save "
                         "them as Chrome trace_event JSON — open in "
                         "https://ui.perfetto.dev (README §Observability)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the engine metrics registry "
                         "(engine.metrics()) as JSON after the run")
    ap.add_argument("--fork", type=int, default=0,
                    help="fork the FIRST admitted request into N extra "
                         "copy-on-write streams after one decode step "
                         "(paged layout; demonstrates best-of-n page "
                         "sharing)")
    ap.add_argument("--baseline", action="store_true",
                    help="run the static-batch loop instead of the engine")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if min(args.requests, args.gen, args.prompt_len, args.slots) < 1:
        ap.error("--requests, --gen, --prompt-len and --slots must all "
                 "be >= 1")
    if args.mesh and args.baseline:
        ap.error("--mesh applies to the engine, not the static baseline")
    try:
        mesh = parse_mesh(args.mesh)
    except ValueError as e:
        ap.error(str(e))

    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    if args.scan_backend:
        cfg = dataclasses.replace(cfg, scan_backend=args.scan_backend)
    if args.kv_dtype or args.paged_impl:
        if args.kv_layout != "paged":
            ap.error("--kv-dtype / --paged-impl need --kv-layout paged")
        if args.kv_dtype:
            cfg = dataclasses.replace(cfg, kv_dtype=args.kv_dtype)
        if args.paged_impl:
            cfg = dataclasses.replace(cfg, paged_impl=args.paged_impl)
    if args.moe_dispatch:
        if cfg.moe is None:
            ap.error(f"--moe-dispatch given but {cfg.name} has no MoE "
                     "layers")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, dispatch=args.moe_dispatch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(1)
    lo = max(1, args.prompt_len // 2)
    plens = rng.integers(lo, args.prompt_len * 3 // 2 + 1, args.requests)
    glens = rng.integers(max(1, args.gen // 2),
                         args.gen * 3 // 2 + 1, args.requests)
    prompts = [rng.integers(0, cfg.vocab, size=p, dtype=np.int64)
               for p in plens]
    max_len = args.max_len or int(plens.max() + glens.max() + 1)

    if args.baseline:
        # static batch: pad every prompt to the longest, run the worst case
        P, G = int(plens.max()), int(glens.max())
        batch = np.stack([np.resize(p, P) for p in prompts])
        t0 = time.time()
        out = generate(model, params, jnp.asarray(batch, jnp.int32),
                       max_len=max_len, gen_tokens=G)
        out.block_until_ready()
        dt = time.time() - t0
        total = args.requests * (P + G)
        print(f"baseline: {out.shape} in {dt:.2f}s "
              f"({total/dt:.1f} tok/s incl. prefill + compile)")
        return out

    if args.prefix_cache and args.kv_layout != "paged":
        ap.error("--prefix-cache needs --kv-layout paged")
    if args.fork and args.kv_layout != "paged":
        ap.error("--fork needs --kv-layout paged")
    drafter_name = args.drafter and (
        args.drafter + ("-smoke" if args.smoke
                        and not args.drafter.endswith("-smoke") else ""))
    if drafter_name and args.kv_layout != "paged":
        ap.error("--drafter needs --kv-layout paged")
    if args.spec_k > 1 and not drafter_name:
        ap.error("--spec-k > 1 needs --drafter")
    telemetry = None
    if args.trace or args.metrics:
        telemetry = Telemetry(trace=bool(args.trace))
    eng = build_engine(model, params,
                       ServeConfig(slots=args.slots, max_len=max_len,
                                   prefill_chunk=args.prefill_chunk,
                                   kv_layout=args.kv_layout,
                                   page_size=args.page_size,
                                   num_pages=args.num_pages,
                                   prefix_cache=args.prefix_cache,
                                   policy=args.policy,
                                   spec_k=args.spec_k,
                                   drafter=drafter_name),
                       mesh=mesh, telemetry=telemetry)
    if eng.drafter is not None:
        print(f"speculative decoding: drafter {drafter_name}, "
              f"k={args.spec_k}")
    if eng.pool is not None:
        print(f"paged KV: {eng.pool.num_pages} pages x "
              f"{args.page_size} tokens, "
              f"<= {eng.pool.max_pages} pages/request"
              + (", prefix cache on" if eng.prefix_cache else ""))
    if mesh is not None:
        info = mesh_info(mesh)
        print(f"mesh: {info['axes']} (dp={info['dp']} tp={info['tp']}, "
              f"{info['n_devices']} devices)")
    t0 = time.time()
    first = None
    for i, (p, g) in enumerate(zip(prompts, glens)):
        sampling = None
        if args.temperature > 0:
            sampling = SamplingParams(temperature=args.temperature,
                                      top_k=args.top_k, top_p=args.top_p,
                                      seed=args.seed + i)
        r = eng.submit(p, max_new_tokens=int(g), sampling=sampling)
        first = first or r
    if args.fork:
        eng.step()                       # admit + one decode step
        room = int(args.slots - eng.active.sum())
        if first.finished or not room:
            print(f"fork skipped: request uid={first.uid} "
                  + ("already finished" if first.finished
                     else "no free slot (raise --slots above the "
                          "request count to demo forking)"))
        else:
            kids = eng.fork(first, min(args.fork, room))
            print(f"forked request uid={first.uid} into "
                  f"{len(kids)} COW streams")
    done = eng.run(verbose=args.verbose)
    dt = time.time() - t0
    total = int(plens.sum() + glens.sum())
    stats = eng.stats()
    print(f"engine: {len(done)} requests, {eng.n_emitted} tokens in "
          f"{dt:.2f}s ({total/dt:.1f} tok/s incl. prefill + compile), "
          f"slot utilization {stats.utilization:.2f}, "
          f"policy {stats.policy}, {stats.n_preemptions} preemption(s)")
    if eng.drafter is not None:
        print(f"spec decode: accept rate {stats.accept_rate:.2f}, "
              f"{eng.n_emitted / max(eng.n_steps, 1):.2f} "
              f"accepted tokens/step")
    if eng.prefix_cache is not None:
        pc = eng.prefix_cache
        print(f"prefix cache: {eng.n_prefix_hits} hits / "
              f"{pc.misses} misses, {eng.n_prefix_tokens} prompt tokens "
              f"skipped, {len(pc)} entries pinning "
              f"{pc.pinned_pages} pages, {pc.n_evicted} evicted")
    if eng.n_forks or eng.n_cow_copies:
        print(f"forks: {eng.n_forks}, COW page copies: "
              f"{eng.n_cow_copies}")
    if args.trace:
        eng.telemetry.save_trace(args.trace)
        print(f"trace: {len(eng.telemetry.trace)} events -> {args.trace} "
              "(open in https://ui.perfetto.dev)")
    if args.metrics:
        print("metrics:", json.dumps(eng.metrics(), indent=2,
                                     sort_keys=True))
    if telemetry is not None:
        telemetry.close()
    print("sample:", done[0].tokens[:16])
    return done


if __name__ == "__main__":
    main()
