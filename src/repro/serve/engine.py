"""Fixed-capacity continuous-batching engine (the EXECUTOR layer).

The serving stack is split into three layers with explicit seams:

  * STATE  — :mod:`repro.serve.state`: :class:`SlotTable` owns the
    waiting queue, the free-slot bitmask, per-slot position / budget /
    sampling-knob arrays and the page-pool interactions (release on
    free) behind small explicit mutators.
  * SCHEDULER — :mod:`repro.serve.scheduler`: a
    :class:`~repro.serve.scheduler.SchedulingPolicy` orders admission
    (``admit_order``) and may name a preemption victim
    (``select_victim``).  ``policy="fifo"`` (the default) reproduces the
    historical strict-FIFO defer-at-head admission byte for byte;
    ``"priority"`` / ``"sjf"`` reorder the queue deterministically (uid
    tie-break) and, under ``priority``, evict lower-priority running
    requests when a higher-priority arrival is blocked.
  * EXECUTOR — this module: the jitted step / write / prefill paths.
    The decode step stays ONE compiled program over the full slot batch
    whose shapes never change, under every policy — scheduling decisions
    are host-side list manipulation, invisible to jit.

Preemption (paged layout only): evicting a running request snapshots
its page chain + per-slot carry to host memory (``device_get`` of
exactly its pages via the block table), releases the pages back to the
pool, and re-queues it; re-admission re-reserves what the slot held at
eviction (recorded in the snapshot), re-seeds FRESH pages with the
snapshotted bytes and resumes mid-stream with no prefill.  Reads go through the block table and the sampling PRNG is
counter-based on (seed, uid, pos), so a preempted-then-resumed stream
is bitwise-equal to one that was never disturbed.

Request lifecycle::

    submit() -> WAITING -> [admit: chunked prefill -> state write] ->
    RUNNING (slot batch decode, inactive slots masked)
       -> retire -> FINISHED (tokens / stream outputs on the host)
       -> preempt -> WAITING (snapshot held) -> resume -> RUNNING

Two request flavors, selected by the StepModel:

  * autoregressive (DecoderLM): the prompt is prefilled in chunks at
    admission; emitted tokens feed back as the next input until
    ``max_new_tokens`` (or ``eos_id``) is reached.  Each request may
    carry :class:`~repro.configs.base.SamplingParams` — the knobs ride
    as per-slot arrays through the one jitted decode step (greedy and
    sampled traffic share a single compiled program), and the PRNG is
    counter-based (fold_in(seed, uid_lo, uid_hi, pos) — the FULL
    submission uid reaches the key as two 32-bit words) so a request's
    tokens are reproducible regardless of co-batched traffic.
  * streaming (MinimalistNetwork): input frames are fed one per step —
    the paper's edge case where samples arrive in real time — and every
    per-frame output is recorded; the request retires when its stream is
    exhausted.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.common import pow2ceil
from repro.configs.base import SamplingParams
from repro.serve.sampling import KNOB_DTYPES
from repro.serve.scheduler import make_policy
from repro.serve.spec import heterogeneous_k
from repro.serve.telemetry import (NULL_TELEMETRY, PercentileWindow,
                                   RateWindow, StatsSink)
# Request/_knob_values moved to serve.state with the layer split; they
# are re-exported here because engine.py was their public home
from repro.serve.state import Request, SlotTable, _knob_values  # noqa: F401

# jitted wrappers whose compile counts engine.metrics() reports — a
# StepModel/drafter may carry any subset (getattr skips the rest)
_JIT_PROGRAMS = ("_jit_step", "_jit_write", "_jit_prefill_fast",
                 "_jit_prefill_scan", "_jit_sample", "_jit_seed",
                 "_jit_verify", "_jit_copy_slot", "_jit_copy_pages")
_DRAFT_JIT_PROGRAMS = ("_jit_propose", "_jit_install")


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """One host-side snapshot of engine occupancy (``ServeEngine.stats()``).

    Replaces the bare ``utilization()`` readout: the load harness and
    ``run(verbose=True)`` record these per wave, and the pool fields are
    what a capacity planner actually needs (pages, not a ratio)."""

    policy: str
    n_steps: int
    slots: int
    active_slots: int
    queue_depth: int
    pages_in_use: int          # 0 when unpaged
    pages_free: int            # 0 when unpaged
    pages_reserved: int        # 0 when unpaged
    n_preemptions: int
    utilization: float         # decode tokens per slot-step paid
    # requests that finished after their submit(deadline=...) step count
    # elapsed on the engine's step clock (0 when no deadlines are set)
    deadline_misses: int = 0
    # rate stream (what an autoscaler actually acts on): windowed decode
    # throughput, submit->admission wait percentiles, and the speculative
    # draft-acceptance rate (0 when no drafter is configured)
    tokens_per_s: float = 0.0
    queue_wait_p50_ms: float = 0.0
    queue_wait_p99_ms: float = 0.0
    accept_rate: float = 0.0

    def line(self) -> str:
        """Compact single-line rendering for ``run(verbose=True)``."""
        return (f"[{self.policy} step {self.n_steps}] "
                f"slots {self.active_slots}/{self.slots} "
                f"queue {self.queue_depth} "
                f"pages {self.pages_in_use} used / {self.pages_free} "
                f"free / {self.pages_reserved} reserved "
                f"preempt {self.n_preemptions} "
                f"util {self.utilization:.2f} "
                f"tok/s {self.tokens_per_s:.0f} "
                f"qwait {self.queue_wait_p50_ms:.1f}/"
                f"{self.queue_wait_p99_ms:.1f}ms "
                f"accept {self.accept_rate:.2f}")


class ServeEngine:
    """Continuous-batching engine over any :class:`StepModel`.

    ``mesh=`` serves under a :class:`jax.sharding.Mesh`: the StepModel is
    bound to it (``bind_mesh``) so parameters TP-shard over "model" via
    the model's logical-axis rule tables, the slot-batch state DP-shards
    its slot axis over "data", and every host-side transfer (prompts,
    next tokens, sampling knobs) is device_put against the slot sharding
    — the decode step stays ONE compiled (now SPMD) program.  On a 1×1
    mesh this is bitwise identical to the no-mesh engine; the semantics
    (admission, retirement, per-request reproducibility) never change.

    ``policy=`` selects the admission/preemption policy: a name from
    :data:`repro.serve.scheduler.POLICIES` ("fifo" default, "priority",
    "sjf") or a :class:`~repro.serve.scheduler.SchedulingPolicy`
    instance.
    """

    def __init__(self, step_model, params, *, slots: int = 8, mesh=None,
                 prefix_cache: bool = False, policy="fifo",
                 drafter=None, drafter_params=None, spec_k: int = 1,
                 telemetry=None):
        self.sm = step_model
        self.slots = int(slots)
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        # observability handle (serve.telemetry): no-op by default, and
        # NEVER on the jitted path — every hook below runs host-side
        # around device calls, so tracing cannot move a bit or retrace
        self.telemetry = telemetry if telemetry is not None \
            else NULL_TELEMETRY
        self.policy = make_policy(policy)
        self.policy.telemetry = self.telemetry
        self.spec_k = int(spec_k)
        self.drafter = drafter
        self.draft_params = drafter_params
        if drafter is None:
            if self.spec_k != 1:
                raise ValueError(
                    f"spec_k={spec_k} needs a drafter (spec_k == 1 is "
                    "plain decode)")
        else:
            self._check_spec_compat(step_model, drafter, prefix_cache)
        if mesh is not None:
            step_model.bind_mesh(mesh, self.slots)
        self.mesh = step_model.mesh
        self.params = step_model.place_params(params)
        # paged KV layout: the engine owns the page allocator — block
        # tables, free list and per-slot chains live here on the host;
        # only the page POOLS are device state (inside self.state)
        self.pool = None
        if getattr(step_model, "kv_layout", "dense") == "paged":
            from repro.serve.paged import PagePool
            self.pool = PagePool(step_model.num_pages(self.slots),
                                 self.slots, step_model.max_pages)
            self.pool.telemetry = self.telemetry
        self.prefix_cache = None
        if prefix_cache:
            if self.pool is None:
                raise ValueError(
                    "prefix_cache=True needs kv_layout='paged'")
            step_model.check_prefix_cacheable()
            from repro.serve.paged import PrefixCache
            # window-bearing stacks overwrite ring slots during prefill,
            # so only end-of-prompt page state is cacheable (and the
            # tail must start exactly at the attach point)
            self.prefix_cache = PrefixCache(
                self.pool, step_model.paged.page_size,
                full_prompt_only=step_model._has_window)
            self.prefix_cache.telemetry = self.telemetry
        self.state = step_model.init_state(self.slots)
        self.st = SlotTable(self.slots, pool=self.pool,
                            pages_for_req=self._pages_for_req,
                            telemetry=self.telemetry)
        self._uid = 0
        # speculative decoding: the drafter's stacked-carry store, the
        # per-slot resume index into its K axis, and each slot's own
        # verify width (plain DATA through the fixed-K verify program)
        if self.drafter is not None:
            self.draft_store = self.drafter.init_store(self.slots)
            self.drafter.telemetry = self.telemetry
            self._draft_sel = np.zeros(self.slots, np.int32)
            self._req_k = np.ones(self.slots, np.int32)
        # telemetry
        self.n_steps = 0
        self.n_emitted = 0          # all tokens, incl. admission prefill
        self.n_admitted = 0         # requests placed in a slot (or resumed)
        self.n_prefill_tokens = 0   # prompt tokens prefilled (rows x length)
        self._n_decoded = 0         # tokens emitted by slot-batch steps
        self.n_prefix_hits = 0      # admissions that attached to cache
        self.n_prefix_tokens = 0    # prompt positions skipped by attaches
        self.n_cow_copies = 0       # device page copies (decode COW)
        self.n_forks = 0
        self.n_preemptions = 0      # victims evicted by the policy
        self.n_drafts_proposed = 0  # drafter tokens offered to verify
        self.n_drafts_accepted = 0  # ... that the target accepted
        self.n_deadline_misses = 0  # finished past deadline (step clock)
        # rate stream (EngineStats): bounded windows — (wall time, tokens
        # decoded) per step, and submit->admission waits in milliseconds
        self._rate = RateWindow(maxlen=256)
        self._queue_wait = PercentileWindow(maxlen=512)
        self._verbose_sink: Optional[StatsSink] = None

    def _check_spec_compat(self, step_model, drafter, prefix_cache):
        """Everything speculative decoding requires of the target, checked
        at CONSTRUCTION with specific errors (no request ever burns a uid
        against an engine that cannot verify it)."""
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if getattr(drafter, "k", None) != self.spec_k:
            raise ValueError(
                f"drafter was built for spec_k={getattr(drafter, 'k', None)}"
                f" but the engine asks {self.spec_k} — the stacked-carry "
                "store and the verify program share one K")
        if not getattr(step_model, "autoregressive", False):
            raise ValueError("speculative decoding applies to "
                             "autoregressive LM targets only")
        if getattr(step_model, "kv_layout", "dense") != "paged":
            raise ValueError(
                "speculative decoding needs kv_layout='paged': rejection "
                "rollback = not committing pages (the dense layout writes "
                "in-place during decode)")
        if prefix_cache:
            raise ValueError("speculative decoding and prefix_cache are "
                             "mutually exclusive (singleton admission "
                             "waves; lift when needed)")
        if step_model.model.cfg.kv_dtype != "bf16":
            raise ValueError(
                f"speculative verify does not support kv_dtype="
                f"{step_model.model.cfg.kv_dtype!r}: the k-token snapshot "
                "overlay reads raw pool rows (quantized pools would need "
                "an in-graph dequant overlay)")
        o1 = sorted(set(step_model._slot_axis) - step_model._pool_names)
        if o1:
            raise ValueError(
                f"speculative targets must be attention-only stacks: "
                f"layers {o1} carry O(1) mixer state whose carry cannot "
                "be rolled back to an accepted prefix")
        if drafter.vocab != step_model.vocab:
            raise ValueError(
                f"drafter vocab ({drafter.vocab}) != target vocab "
                f"({step_model.vocab}): draft token ids must BE target "
                "token ids")
        rings = getattr(step_model, "_ring_lens", [])
        if rings and self.spec_k > min(rings):
            raise ValueError(
                f"spec_k={self.spec_k} exceeds the shortest sliding-"
                f"window ring ({min(rings)}): two speculative tokens "
                "would alias one ring slot in the verify overlay")

    # -- back-compat views onto the SlotTable ---------------------------
    # (tests and user code address scheduling state through the engine;
    # the STATE layer owns it, these read straight through)
    @property
    def free_mask(self) -> int:
        return self.st.free_mask

    @property
    def waiting(self):
        return self.st.waiting

    @property
    def slot_req(self):
        return self.st.slot_req

    @property
    def pos(self):
        return self.st.pos

    @property
    def remaining(self):
        return self.st.remaining

    @property
    def active(self):
        return self.st.active

    @property
    def knobs(self):
        return self.st.knobs

    @property
    def finished(self):
        return self.st.finished

    @property
    def _cur(self):
        return self.st.cur

    @_cur.setter
    def _cur(self, v):
        self.st.cur = v

    def _pages_for_req(self, req: Request) -> int:
        """Worst-case reservation: prompt + full budget for a fresh
        request; for a preempted one, the ORIGINAL reservation its slot
        held at eviction (recorded in the snapshot).  The two differ for
        fork children: a child's ``max_new_tokens`` counts from the FORK
        POINT while its chain covers every position up to there, so the
        prompt+budget formula would under-reserve it and restore (or a
        later decode append) would die in ``pool.grow``.  Re-reserving
        exactly what the slot held keeps the guarantee that the live
        chain never exceeds the reservation, so restore cannot fail
        mid-resume."""
        if self.pool is None:
            return 0
        if req.snapshot is not None:
            return req.snapshot["reserve"]
        return self.sm.pages_for(len(req.prompt) + req.max_new_tokens)

    # ------------------------------------------------------------------
    # submission / admission
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 0,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None, *,
               priority: int = 0,
               deadline: Optional[float] = None,
               spec_k: Optional[int] = None) -> Request:
        prompt = np.asarray(prompt)
        # speculative width override: validated against the engine's
        # compiled width BEFORE the uid burns (like every other reject)
        if spec_k is not None:
            if isinstance(spec_k, bool) or not isinstance(
                    spec_k, (int, np.integer)):
                raise ValueError(f"spec_k must be an int, got {spec_k!r}")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if spec_k > self.spec_k:
                raise ValueError(
                    f"spec_k={spec_k} exceeds the engine's verify width "
                    f"({self.spec_k}) — per-request widths may only "
                    "shrink the compiled K, never grow it")
        # ndim first: len() of a 0-d array raises TypeError, and a bare
        # scalar submission deserves the same clean rejection as []
        if prompt.ndim < 1 or prompt.size < 1:
            raise ValueError("empty prompt")
        if sampling is None:
            sampling = SamplingParams()    # fresh instance per request
        else:
            sampling.validate()
            if not self.sm.autoregressive:
                raise ValueError(
                    "sampling only applies to autoregressive requests")
        if self.sm.autoregressive:
            if prompt.ndim != 1:
                raise ValueError(
                    f"LM requests need a 1-D token prompt, got shape "
                    f"{prompt.shape}")
            if max_new_tokens < 1:
                raise ValueError(
                    f"LM requests need max_new_tokens >= 1, got "
                    f"{max_new_tokens}")
            prompt = prompt.astype(np.int32)
            # attention-bearing stacks write K/V at absolute positions:
            # past max_len the scatter would silently clamp / wrap and the
            # stream would decode garbage mid-request — reject up front
            if getattr(self.sm, "positional", False):
                need = len(prompt) + max_new_tokens
                if need > self.sm.max_len:
                    raise ValueError(
                        f"prompt ({len(prompt)}) + max_new_tokens "
                        f"({max_new_tokens}) = {need} cache positions, "
                        f"but the engine was built with "
                        f"max_len={self.sm.max_len}")
                # paged note: this bound is also what makes page OOM
                # impossible past this point — PagedConfig.validate_for
                # guarantees the pool holds one max-length request, so
                # any request accepted here fits an empty pool and
                # admission only ever DEFERS (see admit())
        req = Request(self._uid, prompt, max_new_tokens, eos_id, sampling,
                      priority=priority, deadline=deadline, spec_k=spec_k)
        req.validate_scheduling()          # raises BEFORE the uid burns
        self._uid += 1
        req.submit_t = time.perf_counter()
        req.created_t = req.submit_t       # TTFT/e2e anchor (never reset)
        self.st.waiting.append(req)
        tel = self.telemetry
        if tel.enabled:
            tel.inc("requests_submitted")
            tel.gauge("queue_depth", self.st.queue_depth)
            tel.request_instant(req, "submit", prompt=len(prompt),
                                max_new_tokens=int(max_new_tokens),
                                priority=req.priority)
            tel.request_begin(req, "queued")
        return req

    def _wave_sampling(self, group, pad_len):
        """Per-request sampling knob arrays for an admission wave (padding
        rows replicate the last request; their draws are discarded).
        Built as numpy first so handing them to jit is a plain device put
        (a list literal would trace a tiny convert program per wave size)."""
        reqs = [r for r, _s in group]
        reqs += [reqs[-1]] * (pad_len - len(group))
        vals = [_knob_values(r) for r in reqs]
        return {k: np.asarray([v[k] for v in vals], KNOB_DTYPES[k])
                for k in KNOB_DTYPES}

    def _pad_slots(self, slots):
        """Pad an admission wave's slot list to a power of two with
        out-of-bounds indices — the scatter drops them, and jit compiles
        at most log2(slots) admission shapes per prompt-length bucket."""
        padded = np.full(pow2ceil(len(slots)), self.slots, np.int32)
        padded[:len(slots)] = slots
        return padded

    def admit(self):
        """Move waiting requests into free slots until no further
        progress is possible.  Looping matters: a slot freed MID-wave
        (eos or ``max_new_tokens==1`` on the wave's first sampled token
        retires it inside the prefill loop) refills in the SAME call
        instead of idling for a whole decode step.

        When admission stalls, the policy may name a running victim to
        PREEMPT (``select_victim``); its eviction frees a slot + pages
        and admission retries.  Termination: each pass either admits a
        request or shrinks the running set, and ``select_victim``
        returning None ends the round."""
        self.policy.begin_round(self.st)
        while True:
            if self._admit_once():
                continue
            victim = self.policy.select_victim(self.st)
            if victim is None:
                break
            self._preempt(victim)

    def _admit_once(self) -> bool:
        """One admission wave: same-length prompts prefill as one batched
        chunked call, their carries land in one scatter write, and the
        wave costs one host sync — admission overhead amortizes over the
        wave.  Returns True iff at least one request was admitted (or a
        preempted one resumed).

        The POLICY picks the wave: admission tries candidates in
        ``policy.admit_order`` and stops at the first it cannot place —
        under "fifo" that is exactly the historical strict-FIFO
        defer-at-head loop (no bypass by smaller requests behind the
        head; head-of-line blocking is the price of starvation-freedom).

        Paged KV: admission additionally RESERVES the request's
        worst-case page chain (prompt + full generation budget) — the
        FULL worst case even when a prefix attach or fork will share
        pages, so sharing is an opportunistic saving, never load-bearing
        capacity, and decode-time page appends / COW copies can never
        fail.  Requests that can never fit were already rejected at
        submit().

        Prefix caching runs SINGLETON waves (one request per wave, in
        policy order): each admission inserts its prompt's pages before
        the next request's cache lookup, so same-batch duplicates hit
        too."""
        st = self.st
        admitted = []
        resumed = False
        while st.waiting and st.free_mask:
            req = self.policy.admit_order(st.waiting, st)[0]
            if self.pool is not None and not self.pool.can_admit(
                    self._pages_for_req(req)):
                break                      # defer until pages free up
            st.pop_waiting(req)
            self.n_admitted += 1
            if req.submit_t is not None:
                wait_ms = (time.perf_counter() - req.submit_t) * 1000.0
                self._queue_wait.push(wait_ms)
                if self.telemetry.enabled:
                    self.telemetry.observe("queue_wait_ms", wait_ms)
            slot = st.alloc_slot()
            if self.pool is not None:
                self.pool.reserve(slot, self._pages_for_req(req))
            st.slot_req[slot] = req
            if req.snapshot is not None:
                self._resume(req, slot)    # no prefill: pages re-seed
                resumed = True
                continue
            st.active[slot] = True
            if self.telemetry.enabled:
                self.telemetry.request_begin(req, "running", slot=slot)
            admitted.append((req, slot))
            if st.cur is None:
                shape = (self.slots,) + tuple(req.prompt.shape[1:])
                st.cur = np.zeros(shape, req.prompt.dtype)
            if self.prefix_cache is not None:
                break                      # singleton waves (see above)
        if not admitted:
            return resumed
        if not self.sm.autoregressive:
            # streaming: blank state reset for the whole wave in one write
            slots = [s for _r, s in admitted]
            pad = self._pad_slots(slots)
            blank = self.sm.init_state(len(pad))
            self.state = self.sm.write_slots(self.state, blank, pad)
            for req, slot in admitted:
                st.pos[slot] = 0
                st.remaining[slot] = len(req.prompt)
                st.cur[slot] = req.prompt[0]
            return True
        groups: dict = {}
        for req, slot in admitted:
            groups.setdefault(len(req.prompt), []).append((req, slot))
        tel = self.telemetry
        for plen, group in groups.items():
            cw = self.sm.chunk_for(plen)
            t0 = time.perf_counter() if tel.enabled else 0.0
            with tel.span("prefill", plen=plen, wave=len(group),
                          chunk_w=cw, chunks=-(-plen // cw)) as sp:
                pages = None
                if self.prefix_cache is not None:
                    req0, slot0 = group[0]  # singleton wave (see above)
                    pages, attach = self.prefix_cache.match(
                        req0.prompt, cw)
                with tel.span("prefill.run") as run:
                    if pages is not None:
                        last, carry, start = self._attach_prefill(
                            req0, slot0, pages, attach)
                        sp.set(attached=attach)
                    else:
                        start = 0
                        if self.pool is not None:
                            for _r, s in group:
                                self.pool.grow(s, self.sm.pages_for(plen))
                        prompts = [r.prompt for r, _s in group]
                        prompts += [prompts[-1]] * (
                            len(self._pad_slots([s for _r, s in group]))
                            - len(group))
                        last, carry = self.sm.prefill(self.params,
                                                      np.stack(prompts))
                    self.n_prefill_tokens += len(group) * (plen - start)
                    if tel.enabled:
                        run.set(rows=len(group),
                                chunks=-(-(plen - start) // cw))
                self._install_wave(plen, group, last, carry)
            if tel.enabled:
                tel.observe("prefill_ms",
                            (time.perf_counter() - t0) * 1000.0)
        return True

    def _attach_prefill(self, req, slot, pages, attach):
        """Prefix-cache hit: share the resident pages into ``slot``,
        reconstruct the dense cache they hold, and prefill only the tail
        chunks from ``start`` — the attached stream is bitwise the stream
        a full prefill would have produced (same chunk grid, same bytes).
        Returns (last, carry, start)."""
        sm, plen = self.sm, len(req.prompt)
        self.pool.share(slot, pages)
        # gather BEFORE any detach below rewires the block-table row
        seed = sm.seed_cache(self.state,
                             self.pool.block_tables[slot:slot + 1])
        self.pool.grow(slot, sm.pages_for(plen))
        if sm._has_window:
            # ring pages diverge from the entry's frozen bytes the moment
            # the tail writes — detach them, with no device copy: the
            # wave write below rewrites every chain page for every leaf
            for i in range(len(pages)):
                self.pool.cow(slot, i, materialize=False)
            start = attach
        else:
            # global/MLA: the overlap recompute writes identical bytes,
            # so shared pages stay shared; recompute at least the last
            # token (its logits feed the first sampled token)
            cw = sm.chunk_for(plen)
            start = (min(attach, plen - 1) // cw) * cw
        last, carry = sm.prefill(self.params, req.prompt[None, :],
                                 cache0=seed, start=start)
        self.n_prefix_hits += 1
        self.n_prefix_tokens += start
        return last, carry, start

    def _install_wave(self, plen, group, last, carry):
        """Scatter a prefilled wave into its slots, pin its prompts in
        the prefix cache, and draw/book-keep the first sampled token."""
        st = self.st
        tel = self.telemetry
        slots = [s for _r, s in group]
        pad = self._pad_slots(slots)
        with tel.span("prefill.install"):
            if self.pool is None:
                self.state = self.sm.write_slots(self.state, carry, pad)
            else:
                # page-granular scatter: each wave row's dense prefill
                # cache lands in its chain's pages; padding rows get
                # all-out-of-bounds page ids so their writes drop
                pages = np.full((len(pad), self.pool.max_pages),
                                self.pool.num_pages, np.int32)
                pages[:len(group)] = self.pool.block_tables[slots]
                self.state = self.sm.write_slots(self.state, carry, pad,
                                                 pages=pages, plen=plen)
                if self.prefix_cache is not None:
                    # pin BEFORE an instant retire below releases the chain
                    for r, s in group:
                        self.prefix_cache.insert(
                            r.prompt, self.pool.block_tables[s],
                            self.sm.chunk_for(plen))
            if self.drafter is not None:
                # the drafter tracks the SAME stream: prefill its own
                # carry over the wave's prompts (same padded batch —
                # padding rows land at OOB slots and drop) and tile it
                # K-wide, resume index 0.  The target draws tok0 below;
                # the drafter will consume it as ``cur`` in the first
                # propose wave.
                prompts = [r.prompt for r, _s in group]
                prompts += [prompts[-1]] * (len(pad) - len(group))
                carry = self.drafter.prefill(self.draft_params,
                                             np.stack(prompts))
                self.draft_store = self.drafter.install(self.draft_store,
                                                        carry, pad)
            # the wave's first generated token sits at position plen —
            # its draw uses the same counter-based (seed, uid, pos) key
            # family as the decode loop, so it is reproducible under any
            # batching
            tok0 = self.sm.sample(last,
                                  self._wave_sampling(group, len(pad)),
                                  np.full(len(pad), plen, np.int32))
        with tel.span("prefill.sync"):
            tok0 = np.asarray(tok0)
        for i, (req, slot) in enumerate(group):
            t = int(tok0[i])
            req.outputs.append(t)
            self.n_emitted += 1
            self._first_token(req)
            st.pos[slot] = plen
            st.remaining[slot] = req.max_new_tokens - 1
            st.cur[slot] = t
            st.set_sampling(slot, req)
            if self.drafter is not None:
                self._draft_sel[slot] = 0
                self._req_k[slot] = (req.spec_k if req.spec_k is not None
                                     else self.spec_k)
            if st.remaining[slot] <= 0 or t == req.eos_id:
                self._retire(slot)

    # ------------------------------------------------------------------
    # preemption (policy-driven victim swap-out / swap-in)
    # ------------------------------------------------------------------
    def _preempt(self, slot: int):
        """Evict running ``slot``: device_get exactly its page chain +
        per-slot carry to host memory, release its pages/reservation and
        put the request back on the queue holding the snapshot.  Eager
        transfers only — the jitted step's compile count stays 1."""
        st = self.st
        req = st.slot_req[slot]
        if req is None or not st.active[slot]:
            raise ValueError(f"slot {slot} is not running (cannot "
                             "preempt)")
        if self.pool is None:
            raise ValueError("preemption needs kv_layout='paged' (page "
                             "swap is what makes eviction cheap)")
        n = int(self.pool.chain_len[slot])
        pages = self.pool.block_tables[slot, :n].copy()
        tel = self.telemetry
        with tel.span("preempt", uid=req.uid, slot=int(slot), pages=n):
            req.snapshot = {
                "n_pages": n,
                # the slot's reservation at eviction — re-admission
                # reserves exactly this (see _pages_for_req:
                # prompt+budget would under-size a fork child's chain)
                "reserve": self.pool.reserved_for(slot),
                "state": self.sm.snapshot_slot(self.state, slot, pages),
                "pos": int(st.pos[slot]),
                "remaining": int(st.remaining[slot]),
                "cur": np.copy(st.cur[slot]),
            }
            if self.drafter is not None:
                req.snapshot["draft"] = self.drafter.snapshot_slot(
                    self.draft_store, slot)
                req.snapshot["draft_sel"] = int(self._draft_sel[slot])
            req.submit_t = time.perf_counter()  # queue wait restarts here
            req.n_preemptions += 1
            self.n_preemptions += 1
            st.free_slot(slot)             # pages + reservation go back
        if tel.enabled:
            tel.inc("preemptions")
            tel.request_begin(req, "preempted", slot=int(slot), pages=n)
        # appendleft: a policy that keeps arrival order re-tries the
        # victim first; ordering policies re-sort anyway
        st.waiting.appendleft(req)

    def _resume(self, req: Request, slot: int):
        """Re-admit a preempted request (caller holds slot+reservation):
        grow a FRESH chain, re-seed its pages from the snapshot, restore
        the per-slot carry/counters — then decode continues mid-stream,
        bitwise where it left off.  No prefill, no first-token draw."""
        st = self.st
        snap = req.snapshot
        tel = self.telemetry
        with tel.span("resume", uid=req.uid, slot=int(slot),
                      pages=snap["n_pages"]):
            self.pool.grow(slot, snap["n_pages"])
            pages = self.pool.block_tables[slot, :snap["n_pages"]]
            self.state = self.sm.restore_slot(self.state, snap["state"],
                                              slot, pages)
            st.pos[slot] = snap["pos"]
            st.remaining[slot] = snap["remaining"]
            st.cur[slot] = snap["cur"]
            st.set_sampling(slot, req)
            st.active[slot] = True
            if self.drafter is not None:
                self.draft_store = self.drafter.restore_slot(
                    self.draft_store, snap["draft"], slot)
                self._draft_sel[slot] = snap["draft_sel"]
                self._req_k[slot] = (req.spec_k if req.spec_k is not None
                                     else self.spec_k)
        if tel.enabled:
            tel.inc("resumes")
            tel.request_begin(req, "running", slot=int(slot),
                              resumed=True)
        req.snapshot = None                # drop the host bytes

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _first_token(self, req: Request):
        """Book the request's first emitted token (TTFT anchor)."""
        if req.first_token_t is not None:
            return
        req.first_token_t = time.perf_counter()
        if self.telemetry.enabled and req.created_t is not None:
            self.telemetry.observe(
                "ttft_ms", (req.first_token_t - req.created_t) * 1000.0)

    def _retire(self, slot: int) -> Request:
        """Retire a finishing slot — the ONE finish path, so telemetry
        sees every completion (admission instant-retire, plain decode,
        spec waves)."""
        req = self.st.retire(slot)
        # deadline misses live on the engine's STEP clock — the unit
        # submit(deadline=...) is scored in by the load harness
        miss = req.deadline is not None and self.n_steps > req.deadline
        if miss:
            self.n_deadline_misses += 1
        tel = self.telemetry
        if tel.enabled:
            tel.inc("requests_finished")
            if miss:
                tel.inc("deadline_misses")
            tel.request_end(req, tokens=len(req.outputs),
                            preemptions=req.n_preemptions)
            tel.request_instant(req, "finish", tokens=len(req.outputs),
                                deadline_miss=miss)
            if req.created_t is not None and req.finish_t is not None:
                tel.observe("e2e_ms",
                            (req.finish_t - req.created_t) * 1000.0)
        return req

    def cancel(self, req: Request):
        """Abort a request: a waiting one leaves the queue (the pool is
        never touched — a queued request holds no slot, pages or
        reservation), a running one frees its slot (and, under the paged
        layout, its pages) before the next step.  Tokens already emitted
        stay on the request, which is marked finished+cancelled and
        never joins ``finished``."""
        if req.finished:
            return
        if not self.st.discard_waiting(req):
            for slot, r in enumerate(self.st.slot_req):
                if r is req:
                    self.st.free_slot(slot)
                    break
            else:
                raise ValueError("request is not known to this engine")
        req.snapshot = None                # a preempted wait drops bytes
        req.finished = True
        req.cancelled = True
        if self.telemetry.enabled:
            self.telemetry.inc("requests_cancelled")
            self.telemetry.request_end(req, cancelled=True)
            self.telemetry.request_instant(req, "cancel")

    def step(self):
        """Admit what fits, then run ONE slot-batched decode step (a
        propose/verify wave when a drafter is configured — up to
        ``spec_k`` tokens per slot for the same number of host syncs).

        All telemetry here is host-side wall clock + host counters
        around the device call — the jitted program and its inputs are
        byte-identical with telemetry on or off.  The ``step`` span
        covers the whole call; its end args say what the step admitted
        and prefilled and what it left running.  A step's host time is
        its duration less the ``*.sync`` spans inside it (the host
        waiting on the device)."""
        tel = self.telemetry
        n_admitted, n_prefill = self.n_admitted, self.n_prefill_tokens
        with tel.span("step") as sp:
            self._step()
            if tel.enabled:
                st, pool = self.st, self.pool
                sp.set(admitted=self.n_admitted - n_admitted,
                       prefill_tokens=self.n_prefill_tokens - n_prefill,
                       active_slots=st.n_active, slots=self.slots,
                       queue_depth=st.queue_depth,
                       pages_reserved=pool.reserved_total if pool else 0,
                       pages_in_use=pool.pages_in_use if pool else 0,
                       preemptions=self.n_preemptions,
                       compiles=tel.registry.counters.get("compiles", 0))

    def _step(self):
        tel = self.telemetry
        with tel.span("admit", queue_depth=self.st.queue_depth):
            self.admit()
        st = self.st
        if not st.active.any():
            return
        t0 = time.perf_counter()
        d0, a0 = self._n_decoded, self.n_drafts_accepted
        spec = self.drafter is not None
        args, walk = {}, None
        if tel.enabled:
            args = dict(active_slots=st.n_active, slots=self.slots,
                        ctx_tokens=int((st.pos[st.active] + 1).sum()),
                        queue_depth=st.queue_depth,
                        pages_in_use=(self.pool.pages_in_use
                                      if self.pool else 0))
            if not spec:
                walk = self.sm.kv_walk(st.pos, st.active)
        with tel.span("spec_wave" if spec else "decode_wave",
                      **args) as sp:
            if spec:
                self._spec_step()
                sp.set(accepted_drafts=self.n_drafts_accepted - a0)
            else:
                self._plain_step()
            sp.set(tokens=self._n_decoded - d0)
            if walk is not None:
                sp.set(kv_blocks=walk[0], kv_blocks_grid=walk[1])
        now = time.perf_counter()
        self._rate.push(now, self._n_decoded - d0)
        if tel.enabled:
            tel.observe("step_ms", (now - t0) * 1000.0)
            tel.inc("decode_waves")
            tel.inc("tokens_decoded", self._n_decoded - d0)
            tel.gauge("active_slots", st.n_active)
            tel.gauge("queue_depth", st.queue_depth)
            tel.counter("slots", active=st.n_active,
                        queue=st.queue_depth)
            if self.pool is not None:
                tel.gauge("pool_utilization",
                          self.pool.pages_in_use / self.pool.num_pages)
                tel.counter("pool", in_use=self.pool.pages_in_use,
                            free=len(self.pool._free),
                            reserved=self.pool.reserved_total)

    def _grow_pages(self, widths):
        """Before a wave that writes K/V at ``pos .. pos + width - 1`` of
        every active slot: grow each chain to cover it and detach shared
        pages the writes land in (copy-on-write).  The pages come out of
        the reservation made at admission, so growth cannot fail
        mid-stream; the device copies of the whole wave run as ONE
        jitted program."""
        st = self.st
        cow_src, cow_dst = [], []
        for slot in np.flatnonzero(st.active):
            p0, kk = int(st.pos[slot]), int(widths[slot])
            self.pool.grow(slot, self.sm.pages_for(p0 + kk))
            touched = set()
            for p in range(p0, p0 + kk):
                touched.update(self.sm.write_page_indices(p))
            for ci in sorted(touched):
                pair = self.pool.cow(slot, ci)
                if pair is not None:
                    cow_src.append(pair[0])
                    cow_dst.append(pair[1])
        if cow_src:
            self.state = self.sm.copy_pages(self.state, cow_src, cow_dst)
            self.n_cow_copies += len(cow_src)

    def _plain_step(self):
        """One slot-batched decode step (no drafter)."""
        st = self.st
        tel = self.telemetry
        with tel.span("decode.prepare"):
            bt = None
            if self.pool is not None:
                # allocate-on-decode-append: this step writes K/V at
                # pos[slot] of every active slot
                self._grow_pages(np.ones(self.slots, np.int32))
                bt = self.pool.block_tables
            active = jnp.asarray(st.active)
            pos = jnp.asarray(st.pos)
            x = jnp.asarray(st.cur)
            sampling = None
            if self.sm.autoregressive:
                sampling = {k: jnp.asarray(v) for k, v in st.knobs.items()}
            kw = {} if bt is None else {"bt": bt}
        with tel.span("decode.dispatch"):
            out, self.state = self.sm.step(self.params, x, self.state, pos,
                                           active, sampling, **kw)
        with tel.span("decode.sync"):
            emitted = np.asarray(out)
        self.n_steps += 1
        with tel.span("decode.book"):
            for slot in np.flatnonzero(st.active):
                req = st.slot_req[slot]
                req.outputs.append(emitted[slot].copy())
                self.n_emitted += 1
                self._n_decoded += 1
                self._first_token(req)
                st.pos[slot] += 1
                st.remaining[slot] -= 1
                if self.sm.autoregressive:
                    st.cur[slot] = emitted[slot]
                    done = (st.remaining[slot] <= 0
                            or emitted[slot] == req.eos_id)
                else:
                    done = st.remaining[slot] <= 0
                    if not done:
                        st.cur[slot] = req.prompt[st.pos[slot]]
                if done:
                    self._retire(slot)

    def _spec_step(self):
        """One propose/verify wave: the drafter rolls ``spec_k`` greedy
        steps per slot (one jitted program), the target scores all of
        them in one ``verify`` call that also commits exactly the
        accepted prefix's K/V, and the host loop advances each slot by
        its ``n_emit`` accepted+correction tokens.  Greedy slots advance
        bitwise along the target-only stream; sampled slots draw from
        provably the target's distribution (serve.sampling).  Exactly
        one compiled propose program and one compiled verify program
        serve every traffic mix — per-slot widths, positions and
        sampling knobs are data."""
        st = self.st
        tel = self.telemetry
        with tel.span("decode.prepare"):
            # per-slot verify widths: the request's own spec_k clamped by
            # the remaining budget, so commits never pass pos + remaining
            # (the reservation and the max_len bound stop exactly there)
            k_slot = heterogeneous_k(self._req_k, st.remaining,
                                     self.spec_k)
            # a wave writes K/V at pos .. pos+k_slot-1: grow/COW the
            # whole span up front (same guarantee as one step)
            self._grow_pages(k_slot)
            active = jnp.asarray(st.active)
            pos = jnp.asarray(st.pos)
            sampling = {k: jnp.asarray(v) for k, v in st.knobs.items()}
        with tel.span("propose", k=int(k_slot.max())):
            toks, self.draft_store = self.drafter.propose(
                self.draft_params, self.draft_store, self._draft_sel,
                np.asarray(st.cur), active)
        with tel.span("verify"):
            emitted, n_emit, self.state = self.sm.verify(
                self.params, toks, self.state, pos, active,
                k_slot, sampling, bt=self.pool.block_tables)
        with tel.span("decode.sync"):
            emitted = np.asarray(emitted)
            n_emit = np.asarray(n_emit)
        self.n_steps += 1
        with tel.span("decode.book"):
            for slot in np.flatnonzero(st.active):
                self._book_spec_slot(slot, int(n_emit[slot]),
                                     int(k_slot[slot]), emitted[slot])

    def _book_spec_slot(self, slot, n, k, emitted):
        """Advance ``slot`` by the ``n`` tokens its verify accepted (of
        ``k`` offered), stopping at an eos."""
        st = self.st
        req = st.slot_req[slot]
        self.n_drafts_proposed += k - 1
        self.n_drafts_accepted += n - 1
        done = False
        n_take = n
        for j in range(n):
            t = int(emitted[j])
            req.outputs.append(emitted[j].copy())
            self.n_emitted += 1
            self._n_decoded += 1
            self._first_token(req)
            if t == req.eos_id:
                # tokens past an eos are discarded — target-only decode
                # would never have produced them (their K/V commits die
                # with the freed pages)
                n_take = j + 1
                done = True
                break
        st.pos[slot] += n_take
        st.remaining[slot] -= n_take
        if st.remaining[slot] <= 0:
            done = True
        if done:
            self._retire(slot)
        else:
            st.cur[slot] = emitted[n_take - 1]
            # resume carry: the drafter state after consuming the stream
            # through pos-1 is the wave's (n_take-1)-th feed
            self._draft_sel[slot] = n_take - 1

    def fork(self, req: Request, n: int = 1, *,
             max_new_tokens: Optional[int] = None,
             sampling: Optional[SamplingParams] = None) -> List[Request]:
        """Split a RUNNING request into ``n`` additional streams that
        share its page chain copy-on-write — beam search and best-of-n
        pay the parent's prefill (and all pages decoded so far) once.

        Each child copies the parent's block-table row (``PagePool.share``
        increments every page's refcount), its recurrent non-pool state
        (one jitted ``copy_slot``), its emitted-so-far outputs, position
        and input token; a later decode write into a still-shared page
        detaches a private copy first (see :meth:`step`).  Children get
        a FRESH uid, so sampled children draw independent streams from
        the counter-based PRNG while greedy children reproduce the
        parent bitwise.

        ``max_new_tokens=None`` inherits the parent's remaining budget;
        an int gives each child that many tokens from the fork point.
        Children need a free slot and a full worst-case reservation NOW
        — fork raises rather than queueing (a queued fork would race the
        parent's ongoing decode)."""
        st = self.st
        if self.pool is None:
            raise ValueError("fork() needs kv_layout='paged' (page "
                             "sharing is what makes a fork O(1))")
        if not self.sm.autoregressive:
            raise ValueError("fork() applies to LM requests only")
        parent = next((s for s, r in enumerate(st.slot_req)
                       if r is req), None)
        if parent is None:
            raise ValueError(
                "fork parent must be RUNNING (admitted, not finished) — "
                "fork after admit()/step() has placed it in a slot")
        if sampling is not None:
            sampling.validate()
        children: List[Request] = []
        for _ in range(int(n)):
            pos = int(st.pos[parent])
            budget = (int(st.remaining[parent])
                      if max_new_tokens is None else int(max_new_tokens))
            if budget < 1:
                raise ValueError(f"fork needs a generation budget >= 1, "
                                 f"got {budget}")
            if pos + budget > self.sm.max_len:
                raise ValueError(
                    f"fork at position {pos} + {budget} new tokens "
                    f"exceeds max_len={self.sm.max_len}")
            if not st.free_mask:
                raise RuntimeError("no free slot to fork into")
            need = self.sm.pages_for(pos + budget)
            if not self.pool.can_admit(need):
                raise RuntimeError(
                    f"cannot fork: child needs a reservation of {need} "
                    f"pages but only {self.pool.available} are "
                    "unreserved (shared pages don't count — "
                    "reservations stay worst-case under sharing)")
            slot = st.alloc_slot()
            self.pool.reserve(slot, need)
            nchain = int(self.pool.chain_len[parent])
            self.pool.share(slot,
                            self.pool.block_tables[parent, :nchain])
            samp = (dataclasses.replace(sampling) if sampling is not None
                    else dataclasses.replace(req.sampling))
            child = Request(self._uid, req.prompt, budget, req.eos_id,
                            samp, priority=req.priority,
                            deadline=req.deadline, spec_k=req.spec_k)
            self._uid += 1
            child.outputs = list(req.outputs)
            st.slot_req[slot] = child
            st.active[slot] = True
            st.pos[slot] = st.pos[parent]
            st.remaining[slot] = budget
            st.cur[slot] = st.cur[parent]
            st.set_sampling(slot, child)
            self.state = self.sm.copy_slot(self.state, parent, slot)
            if self.drafter is not None:
                self.draft_store = self.drafter.copy_slot(
                    self.draft_store, parent, slot)
                self._draft_sel[slot] = self._draft_sel[parent]
                self._req_k[slot] = self._req_k[parent]
            self.n_forks += 1
            if self.telemetry.enabled:
                self.telemetry.inc("forks")
                self.telemetry.instant("fork", parent_uid=req.uid,
                                       child_uid=child.uid,
                                       slot=int(slot))
                self.telemetry.request_begin(child, "running",
                                             slot=int(slot), forked=True)
            children.append(child)
        return children

    def run(self, max_steps: Optional[int] = None, *,
            verbose: bool = False) -> List[Request]:
        """Drive until every submitted request finishes; returns them in
        completion order.  ``verbose=True`` prints a :meth:`stats` line
        after every step (occupancy, queue, pool pages, preemptions).

        Deadlock guard: a step with nothing active, nothing retired and
        a non-empty queue can never make progress (no running request
        will ever free the pages the queue's head is deferred on) — the
        old loop busy-spun forever; now it raises, naming the blocked
        request and the pool state."""
        st = self.st
        steps = 0
        # the stats line goes through a SINK, not a hardwired print:
        # Telemetry(stats_stream=..., stats_every=N) owns the stream and
        # cadence; verbose=True without one falls back to a per-step
        # stdout sink (the historical rendering, byte for byte)
        sink = self.telemetry.stats_sink
        if sink is None and verbose:
            if self._verbose_sink is None:
                self._verbose_sink = StatsSink()
            sink = self._verbose_sink
        while st.waiting or st.active.any():
            n_finished = len(st.finished)
            self.step()
            if sink is not None:
                sink.emit(self.stats())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
            if (st.waiting and not st.active.any()
                    and len(st.finished) == n_finished):
                # the blocked head is the POLICY's head — under
                # priority/sjf that need not be waiting[0]
                head = self.policy.admit_order(st.waiting, st)[0]
                need = self._pages_for_req(head)
                pool = ("no page pool" if self.pool is None else
                        f"pool: {self.pool.available} of "
                        f"{self.pool.num_pages} pages unreserved, "
                        f"{self.pool.pages_in_use} in use, "
                        f"reserved_total={self.pool.reserved_total}")
                raise RuntimeError(
                    f"engine stalled: request uid={head.uid} "
                    f"(prompt={len(head.prompt)} tokens, "
                    f"max_new_tokens={head.max_new_tokens}, needs "
                    f"{need} pages) cannot admit, no slot is active to "
                    f"free capacity, and {len(st.waiting)} request(s) "
                    f"wait behind it — {pool}")
        return st.finished

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------
    def stats(self) -> EngineStats:
        """Current occupancy snapshot (see :class:`EngineStats`)."""
        paid = self.n_steps * self.slots
        p50, p99 = self._queue_wait.percentiles((50, 99))
        return EngineStats(
            policy=self.policy.name,
            n_steps=self.n_steps,
            slots=self.slots,
            active_slots=self.st.n_active,
            queue_depth=self.st.queue_depth,
            pages_in_use=(self.pool.pages_in_use if self.pool else 0),
            pages_free=(len(self.pool._free) if self.pool else 0),
            pages_reserved=(self.pool.reserved_total if self.pool
                            else 0),
            n_preemptions=self.n_preemptions,
            utilization=self._n_decoded / paid if paid else 0.0,
            deadline_misses=self.n_deadline_misses,
            tokens_per_s=self._rate.per_s(),
            queue_wait_p50_ms=p50,
            queue_wait_p99_ms=p99,
            accept_rate=(self.n_drafts_accepted /
                         self.n_drafts_proposed
                         if self.n_drafts_proposed else 0.0))

    def _jit_programs(self) -> Dict[str, Any]:
        """The jitted wrappers this engine can observe compile counts
        on, by short name (``step``, ``verify``, ``draft_propose``, ...).
        Lazily-built wrappers (``_jit_prefill_fast`` before the first
        prefill) are skipped until they exist."""
        out = {}
        for attr in _JIT_PROGRAMS:
            fn = getattr(self.sm, attr, None)
            if fn is not None and hasattr(fn, "_cache_size"):
                out[attr[len("_jit_"):]] = fn
        if self.drafter is not None:
            for attr in _DRAFT_JIT_PROGRAMS:
                fn = getattr(self.drafter, attr, None)
                if fn is not None and hasattr(fn, "_cache_size"):
                    out["draft" + attr[len("_jit"):]] = fn
        return out

    def metrics(self) -> Dict[str, Any]:
        """Machine-readable engine metrics as a typed dict — the
        autoscaling-loop / dashboard readout.  Always available (the
        engine's own counters and the jit compile counts don't need a
        Telemetry handle); the ``telemetry`` section carries the
        registry's counters/gauges/histograms when one is attached.

        Sections: ``counters`` (monotonic ints), ``gauges`` (point-in-
        time floats), ``rates`` (windowed — what an autoscaler acts
        on), ``jit`` (``<program>_compiles`` per jitted wrapper — the
        compile-count-1 contract reads ``jit["step_compiles"]``)."""
        s = self.stats()
        m: Dict[str, Any] = {
            "counters": {
                "steps": self.n_steps,
                "tokens_emitted": self.n_emitted,
                "tokens_decoded": self._n_decoded,
                "requests_admitted": self.n_admitted,
                "prefill_tokens": self.n_prefill_tokens,
                "requests_finished": len(self.st.finished),
                "preemptions": self.n_preemptions,
                "forks": self.n_forks,
                "cow_copies": self.n_cow_copies,
                "prefix_hits": self.n_prefix_hits,
                "prefix_tokens_skipped": self.n_prefix_tokens,
                "drafts_proposed": self.n_drafts_proposed,
                "drafts_accepted": self.n_drafts_accepted,
                "deadline_misses": self.n_deadline_misses,
            },
            "gauges": {
                "slots": float(self.slots),
                "active_slots": float(s.active_slots),
                "queue_depth": float(s.queue_depth),
                "pages_in_use": float(s.pages_in_use),
                "pages_free": float(s.pages_free),
                "pages_reserved": float(s.pages_reserved),
                "pool_utilization": (
                    s.pages_in_use / self.pool.num_pages
                    if self.pool else 0.0),
                "utilization": s.utilization,
            },
            "rates": {
                "tokens_per_s": s.tokens_per_s,
                "queue_wait_p50_ms": s.queue_wait_p50_ms,
                "queue_wait_p99_ms": s.queue_wait_p99_ms,
                "accept_rate": s.accept_rate,
            },
            "jit": {f"{name}_compiles": fn._cache_size()
                    for name, fn in self._jit_programs().items()},
        }
        if self.telemetry.enabled:
            m["telemetry"] = self.telemetry.registry.as_dict()
        return m

    @property
    def utilization(self) -> float:
        """Decode-emitted tokens per slot-step actually paid for (tokens
        produced by admission prefill are excluded — they cost prefill
        FLOPs, not decode slot-steps)."""
        return self.stats().utilization
