"""The StepModel protocol: what the serving engine requires of a model.

A StepModel reduces any supported architecture to four operations over a
slot-batched recurrent state (every leaf carries the slot axis first):

  * ``init_state(batch)``                      — blank per-slot state
  * ``prefill(params, xs, pos0=0)``            — consume an admission
                                                 wave's prompts from a
                                                 fresh internal state
  * ``step(params, x, state, pos, active, sampling=None)``
                                               — one slot-batch decode
                                                 step (vector pos/active;
                                                 per-slot sampling knobs,
                                                 emitted value feeds back
                                                 for LMs)

LM adapters additionally expose ``sample(logits, sampling, pos)`` — the
admission-wave token draw (the engine samples the first generated token
from the prefill logits with the same counter-based keys the decode step
uses).  ``emit(out)`` survives as an optional greedy-argmax debugging
helper; the engine no longer calls it.

Two adapters are provided:

  * :class:`DecoderStepModel` — any ``models.transformer.DecoderLM``
    (minGRU / Mamba / attention / hybrid stacks).  Pure O(1)-state stacks
    take the direct batched ``decode_step`` with a dummy position (their
    mixers are position-free); attention-bearing stacks are vmapped over
    slots so each slot keeps its own absolute position in the KV cache.
  * :class:`MinimalistStepModel` — the paper's raw ``MinimalistNetwork``
    (frame streaming, e.g. per-sample sMNIST classification), optionally
    through the fused single-step Pallas kernel on exported 2 b codes.

Mesh serving: ``bind_mesh(mesh, slots)`` commits an adapter to a
``jax.sharding.Mesh`` — parameters TP-shard over "model" through the
model's own logical-axis rule tables, the slot-batch state DP-shards
its slot axis over "data" (``parallel.sharding.SERVE_CACHE_RULES``),
and per-call host arrays are ``device_put`` against the slot sharding,
so the decode step stays one compiled SPMD program.  See
:class:`ServeShardings` and README §Sharded serving.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import pow2ceil
from repro.configs.base import ATTN, ATTN_LOCAL, MLA
from repro.parallel import sharding as shd
from repro.serve.sampling import greedy_arrays, sample_tokens, verify_tokens


@dataclasses.dataclass(frozen=True)
class ServeShardings:
    """Every placement the serving engine needs, for one (mesh, model,
    slot count): parameters TP-shard over "model" via the model's own
    logical-axis rule tables, the slot-batch state DP-shards its slot
    axis over "data" (TP-shardable cache dims ride the serve cache
    rules), and per-slot decode arrays (tokens / positions / active /
    sampling knobs) shard like a batch.  ``replicated`` is the fully
    replicated placement for scalars and scatter indices."""

    mesh: Any
    params: Any       # NamedSharding pytree matching the param pytree
    state: Any        # NamedSharding pytree matching init_state(slots)
    slot: Any         # NamedSharding for (slots,)-leading arrays
    replicated: Any   # NamedSharding(mesh, P())


class StepModel:
    """Contract only; see module docstring."""

    #: LM generation (emit feeds back as the next input) vs frame streaming
    #: (inputs always come from the request's own sequence).
    autoregressive: bool = True

    #: bound by :meth:`bind_mesh`; ``None`` = classic single-device serving.
    mesh = None
    sharding: Optional[ServeShardings] = None
    _slot_shardings = None      # (dim0, rank) -> NamedSharding cache

    def shardings(self, mesh, slots, rules=None) -> ServeShardings:
        """Compute (without binding) the placements this model's serve
        arrays take on ``mesh`` with a ``slots``-wide slot batch."""
        raise NotImplementedError

    def bind_mesh(self, mesh, slots, rules=None) -> ServeShardings:
        """Commit this StepModel to ``mesh``: recompute shardings and
        rebuild the jitted programs so every compiled step runs SPMD
        (and donates the slot state).  One mesh per StepModel — the
        engine calls this at init when constructed with ``mesh=``."""
        raise NotImplementedError

    def place_params(self, params):
        """device_put ``params`` against the bound mesh (identity when
        unbound)."""
        if self.mesh is None:
            return params
        return jax.device_put(params, self.sharding.params)

    def put_slot(self, a):
        """device_put one per-slot/wave array (dim0 = slot axis) against
        the bound mesh (divisibility-gated DP; no-op when unbound).  The
        NamedSharding per (dim0, rank) is cached — this runs ~10x per
        decode step on the latency-critical host path."""
        if self.mesh is None:
            return a
        a = jnp.asarray(a)
        key = (a.shape[0] if a.ndim else None, a.ndim)
        if self._slot_shardings is None:
            self._slot_shardings = {}
        sh = self._slot_shardings.get(key)
        if sh is None:
            sh = NamedSharding(self.mesh, shd.dim0_dp_spec(a.shape,
                                                           self.mesh))
            self._slot_shardings[key] = sh
        return jax.device_put(a, sh)

    def init_state(self, batch):
        raise NotImplementedError

    def prefill(self, params, xs, pos0=0):
        """xs: (B, P, …) an admission wave's prompts (equal lengths) ->
        (last_out (B, …), carry state with batch B)."""
        raise NotImplementedError

    def step(self, params, x, state, pos, active, sampling=None):
        """ONE slot-batch decode step.  Returns (emitted, merged_state):
        the emitted value per slot (token id / output vector) and the
        state with inactive slots frozen — both produced inside a single
        jitted program so the hot path is one dispatch + one host sync.
        ``sampling`` is a dict of per-slot knob ARRAYS (see
        repro.serve.sampling) or None for all-greedy; either way the
        same program runs — knobs are data, not trace constants."""
        raise NotImplementedError

    def emit(self, out):
        """Optional: raw output -> recorded value (greedy debugging aid)."""
        raise NotImplementedError

    def kv_walk(self, pos, active):
        """(KV blocks the decode step's paged attention kernel walks,
        blocks a walk of every page of every slot would take) for host
        slot vectors ``pos`` / ``active``, or None where no layer runs
        such a kernel."""
        return None

    def write_slots(self, state, batch_state, slots):
        """Scatter a batched carry (batch axis aligned with ``slots``) into
        the slot batch.  Entries of ``slots`` >= capacity are padding and
        dropped (JAX scatter OOB-drop semantics) — admission waves pad the
        group batch to a power of two so jit shapes stay bounded."""
        raise NotImplementedError


def _axis_mask(active, leaf, axis=0):
    """Broadcast (slots,) bool over a leaf whose slot dim sits at ``axis``."""
    shape = [1] * leaf.ndim
    shape[axis] = active.shape[0]
    return active.reshape(shape)


def masked_update(state, new_state, active, axis=0):
    """Freeze inactive slots: new value where active, old where not."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(_axis_mask(active, n, axis), n, o),
        new_state, state)


# ---------------------------------------------------------------------------
# DecoderLM adapter
# ---------------------------------------------------------------------------

class DecoderStepModel(StepModel):
    """StepModel over a DecoderLM; state = the per-layer decode caches.

    ``kv_layout`` selects where attention caches live:

      * "dense" (default) — every slot owns (max_len, ...) cache rows;
        positional stacks decode via a per-slot vmap of ``decode_step``.
      * "paged" — attention caches are shared page pools plus per-slot
        block tables (``serve.paged``); decode runs the natively
        slot-batched ``decode_step_paged`` (a vmap cannot thread shared
        pool state), admission prefill still computes the dense wave
        cache and ``write_slots`` scatters it PAGE-granularly, and the
        engine allocates pages as positions cross page boundaries.  The
        default ``paged_impl="pallas"`` reads through the page-indirect
        kernel (pinned per-family tolerance vs the gather oracle);
        ``paged_impl="gather"`` keeps the decode math bitwise identical
        to the dense layout.  With ``kv_dtype="int8"`` pools store
        symmetric per-page codes + float32 scale leaves (``*_scale``):
        ``write_slots`` quantizes page rows on install, the in-graph
        decode write requantizes incrementally, and the scales ride the
        pool subtrees so page copies (COW) and sharding need no special
        cases.
    """

    autoregressive = True

    def __init__(self, model, *, max_len: int = 256,
                 prefill_chunk: int = 256, kv_layout: str = "dense",
                 paged=None):
        self.model = model
        self.max_len = int(max_len)
        self.prefill_chunk = int(prefill_chunk)
        self.vocab = model.cfg.vocab
        kinds = {s.kind for s in model.cfg.layer_specs()}
        # position-free stacks: every mixer carries O(1) state and ignores
        # absolute position -> one batched decode_step, never retraced.
        self.positional = bool(kinds & {ATTN, ATTN_LOCAL, MLA})
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {kv_layout!r}")
        self.kv_layout = kv_layout
        self.paged = None
        self._pool_names = frozenset()
        self._gqa_walks = {}
        if kv_layout == "paged":
            from repro.serve.paged import PagedConfig
            if not self.positional:
                raise ValueError(
                    f"{model.cfg.name}: kv_layout='paged' needs an "
                    "attention-bearing stack — pure O(1)-state stacks "
                    "have no KV cache to page (serve them dense)")
            # the longest in-cache span any layer keeps: global/MLA
            # layers span max_len; a pure sliding-window stack is bounded
            # by its ring, so its page chains (and the block-table width)
            # never exceed the window
            if kinds & {ATTN, MLA}:
                self._page_cap = self.max_len
            else:
                self._page_cap = min(model.cfg.sliding_window, self.max_len)
            self.paged = paged if paged is not None else PagedConfig()
            self.max_pages = self.pages_for(self.max_len)
            self.paged.validate_for(self.max_len, self.max_pages)
            self._pool_names = frozenset(model.paged_layer_names())
            # copy-on-write metadata: which in-chain page indices a
            # decode write at position p touches.  Global/MLA layers
            # write the absolute page p//ps; each sliding-window ring of
            # length L recycles page (p % L)//ps in place.
            ring = set()
            has_global = False
            for name, lyr, _m in model._all_layers():
                if name not in self._pool_names:
                    continue
                L = lyr.mixer.ring_length(self.max_len)
                if L < self.max_len:
                    ring.add(int(L))
                else:
                    has_global = True
            self._ring_lens = sorted(ring)
            self._has_global = has_global
            self._has_window = bool(ring)
            self._gqa_walks = self._gqa_kernel_walks()
        # in the model's native cache layout, scanned-unit leaves carry the
        # layer-repeat axis FIRST — their slot (batch) axis is 1, not 0.
        self._slot_axis = {name: (1 if mode == "scanned" else 0)
                           for name, _l, mode in model._all_layers()}
        # MoE stacks: the decode step routes through the capacity-free
        # gather-GEMM path and chunked prefill through per-request
        # grouping (models.moe, MoEConfig.dispatch="auto"), so routing —
        # and therefore the generated text — no longer depends on the
        # co-batched traffic or the prefill chunking.  Only an explicit
        # dispatch="pooled" opts back into batch-DEPENDENT serving (the
        # training semantics, capacity drops included) — that one still
        # warns, because there the old caveat remains true.
        self.moe_dispatch = (model.cfg.moe.dispatch
                             if any(s.moe for s in model.cfg.layer_specs())
                             else None)
        if self.moe_dispatch == "pooled":
            import warnings
            warnings.warn(
                f"{model.cfg.name}: dispatch='pooled' pools every token of "
                "a call into one capacity-limited dispatch — serving "
                "outputs will vary with concurrent traffic and prefill "
                "chunking (use 'auto' or 'per_request' for batch-invariant "
                "routing)", stacklevel=2)
        if self.kv_layout == "paged":
            self._jit_step = jax.jit(self._step_impl_paged)
            # the prompt length is a SHAPE (pages written per layer), so
            # it is static — one compiled write per (wave, plen) bucket,
            # exactly the prefill's own compile classes
            self._jit_write = jax.jit(self._write_impl_paged,
                                      static_argnums=(4,))
            # sharing machinery: fork slot-state copies, COW page copies,
            # prefix-attach cache seeding (all page-pool local — the page
            # axis is never sharded, so none of these need collectives)
            self._jit_copy_slot = jax.jit(self._copy_slot_impl)
            self._jit_copy_pages = jax.jit(self._copy_pages_impl)
            self._jit_seed = jax.jit(self._seed_impl)
            self._jit_verify = jax.jit(self._verify_impl_paged)
        else:
            self._jit_step = jax.jit(self._step_impl)
            self._jit_write = jax.jit(self._write_impl)
        self._jit_sample = jax.jit(self._sample_impl)
        self.emit = jax.jit(self._emit_impl)
        self._greedy = {}           # per-batch greedy sampling arrays
        # populated lazily by serve.prefill.chunked_prefill
        self._jit_prefill_fast = None
        self._jit_prefill_scan = None
        self._cache_templates = {}
        self._state_shardings = {}  # per-batch state placement (mesh only)

    # -- paged layout ----------------------------------------------------
    def pages_for(self, n: int) -> int:
        """Pages a request needs once it spans ``n`` positions (the max
        over layers: window rings cap at the ring length)."""
        ps = self.paged.page_size
        return -(-min(int(n), self._page_cap) // ps)

    def num_pages(self, slots: int) -> int:
        """Resolved pool capacity (0 in the config = dense-equivalent)."""
        return self.paged.resolve_num_pages(slots, self.max_pages)

    def write_page_indices(self, pos: int):
        """In-chain page indices a decode write at position ``pos``
        touches (the engine COWs these when they are shared): the
        absolute page for global/MLA layers, plus each sliding-window
        ring's recycled page."""
        ps = self.paged.page_size
        out = set()
        if self._has_global:
            out.add(int(pos) // ps)
        for L in self._ring_lens:
            out.add((int(pos) % L) // ps)
        return sorted(out)

    def _gqa_kernel_walks(self):
        """{(ring length, pages per block): layers} over the layers whose
        decode read is the paged GQA kernel (``paged_impl`` not
        "gather"); scanned units count once per repeat."""
        from repro.kernels.paged_attention.paged_attention import (
            pages_per_block)
        from repro.models.attention import GQAAttention
        walks = {}
        if self.model.cfg.paged_impl == "gather":
            return walks
        ps = self.paged.page_size
        for name, lyr, mode in self.model._all_layers():
            if (name not in self._pool_names
                    or not isinstance(lyr.mixer, GQAAttention)):
                continue
            L = lyr.mixer.ring_length(self.max_len)
            pool = lyr.mixer.paged_cache_spec(1, ps)["k"]
            key = (L, pages_per_block(ps, pool.shape[2], pool.shape[3],
                                      pool.dtype, -(-L // ps)))
            n = self.model.cfg.n_repeats if mode == "scanned" else 1
            walks[key] = walks.get(key, 0) + n
        return walks

    def kv_walk(self, pos, active):
        """Blocks walked and blocks of a full walk, summed over the
        decode step's paged GQA kernel calls (one per layer), from the
        same ``pages_per_block`` the kernel uses."""
        if not self._gqa_walks:
            return None
        from repro.kernels.paged_attention.paged_attention import (
            blocks_walked)
        walked = grid = 0
        for (L, ppb), n in self._gqa_walks.items():
            w, g = blocks_walked(pos, active, length=L,
                                 page_size=self.paged.page_size, ppb=ppb)
            walked, grid = walked + n * w, grid + n * g
        return walked, grid

    def check_prefix_cacheable(self):
        """Prefix caching reconstructs a request's WHOLE decode state
        from pages — reject stacks where that is impossible."""
        if self.kv_layout != "paged":
            raise ValueError("prefix caching needs kv_layout='paged'")
        o1 = sorted(set(self._slot_axis) - set(self._pool_names))
        if o1:
            raise ValueError(
                f"prefix caching needs an all-attention stack: layers "
                f"{o1} carry O(1) mixer state that does not live in "
                "pages, so an attached request could not reconstruct it")
        if self._page_cap < self.max_len:
            raise ValueError(
                "prefix caching needs page chains spanning max_len; a "
                f"pure sliding-window stack caps them at the ring "
                f"({self._page_cap} positions) and overwrites prompt "
                "pages in place")
        return True

    # -- page sharing (forks / prefix attaches) --------------------------
    def _copy_slot_impl(self, state, src, dst):
        """Duplicate the per-slot NON-pool leaves of ``src`` into ``dst``
        (fork: the page pools themselves are shared via block tables)."""
        out = {}
        for name, sub in state.items():
            if name in self._pool_names:
                out[name] = sub
                continue
            ax = self._slot_axis[name]

            def cp(s, ax=ax):
                row = jax.lax.dynamic_index_in_dim(s, src, ax,
                                                   keepdims=True)
                return jax.lax.dynamic_update_slice_in_dim(s, row, dst,
                                                           ax)

            out[name] = jax.tree_util.tree_map(cp, sub)
        return out

    def copy_slot(self, state, src: int, dst: int):
        """Fork: copy slot ``src``'s recurrent (non-pool) state into
        ``dst`` inside one jitted program (src/dst ride as traced
        scalars — one compile, any pair)."""
        src, dst = jnp.int32(src), jnp.int32(dst)
        if self.mesh is not None:
            src = jax.device_put(src, self.sharding.replicated)
            dst = jax.device_put(dst, self.sharding.replicated)
        return self._jit_copy_slot(state, src, dst)

    def _copy_pages_impl(self, state, src, dst):
        """Copy pool rows ``src[i] -> dst[i]`` in every page pool.
        Out-of-bounds ``dst`` padding drops (scatter semantics); the
        matching ``src`` padding reads clamp harmlessly."""
        out = {}
        for name, sub in state.items():
            if name not in self._pool_names:
                out[name] = sub
                continue
            ax = self._slot_axis[name]

            def cp(s, ax=ax):
                if ax == 0:
                    return s.at[dst].set(s[src])
                return s.at[:, dst].set(s[:, src])

            out[name] = jax.tree_util.tree_map(cp, sub)
        return out

    def copy_pages(self, state, src, dst):
        """Copy-on-write device copies: page ``src[i]`` -> ``dst[i]`` in
        every pool leaf.  Padded to a power of two (OOB dst indices
        drop) so jit compiles log2-many shapes; the page axis is never
        sharded, so under a mesh this stays collective-free."""
        import numpy as np
        n = pow2ceil(len(src))
        sp = np.zeros(n, np.int32)
        sp[:len(src)] = src
        dp = np.full(n, np.iinfo(np.int32).max, np.int32)
        dp[:len(dst)] = dst
        sp, dp = jnp.asarray(sp), jnp.asarray(dp)
        if self.mesh is not None:
            sp = jax.device_put(sp, self.sharding.replicated)
            dp = jax.device_put(dp, self.sharding.replicated)
        return self._jit_copy_pages(state, sp, dp)

    def _seed_impl(self, state, bt_row):
        """Native dense B=1 prefill cache gathered from ``bt_row``'s
        pages — the in-cache index mapping (absolute for global/MLA,
        ring for windows) is exactly ``gather_pages``'s, so the seeded
        cache is bitwise the dense cache the chain's writer produced
        (bf16 pools).  Int8 pools seed the DEQUANTIZED view (codes ×
        per-page scale): re-installing it quantizes back to bit-exact
        codes (see ``_write_impl_paged``)."""
        from repro.kernels.paged_attention.ref import (gather_dequant,
                                                       gather_pages)
        tmpl = self.model.cache_spec(1, self.max_len)
        out = {}
        for name, sub in state.items():
            ax = self._slot_axis[name]
            qkeys = ({k for k in sub if k + "_scale" in sub}
                     if isinstance(sub, dict) else set())
            if qkeys:
                nsub = {}
                for key in sorted(qkeys):
                    spec = tmpl[name][key]
                    Lv = spec.shape[ax + 1]
                    pool, sc = sub[key], sub[key + "_scale"]
                    if ax == 0:
                        nsub[key] = gather_dequant(pool, sc, bt_row, Lv,
                                                   spec.dtype)
                    else:
                        nsub[key] = jax.vmap(
                            lambda p, s, Lv=Lv: gather_dequant(
                                p, s, bt_row, Lv))(pool, sc).astype(
                                    spec.dtype)
                out[name] = nsub
                continue

            def g(pool, spec, ax=ax):
                Lv = spec.shape[ax + 1]
                if ax == 0:
                    return gather_pages(pool, bt_row,
                                        Lv).astype(spec.dtype)
                return jax.vmap(
                    lambda p: gather_pages(p, bt_row, Lv))(
                        pool).astype(spec.dtype)

            out[name] = jax.tree_util.tree_map(g, sub, tmpl[name])
        return out

    def seed_cache(self, state, bt_row):
        """Prefix attach: reconstruct the dense (B=1, native layout)
        cache held by ``bt_row``'s page chain, ready to resume
        ``prefill(cache0=..., start=...)`` from the attach point.
        Entries past the chain gather garbage — every read of them is
        position-masked or overwritten by the tail prefill."""
        bt = jnp.asarray(bt_row, jnp.int32)
        if self.mesh is not None:
            bt = jax.device_put(bt, self.sharding.replicated)
        cache = self._jit_seed(state, bt)
        if self.mesh is not None:
            cache = self.place_cache(cache)
        return cache

    # -- preemption (scheduler victim swap-out / swap-in) ----------------
    def snapshot_slot(self, state, slot, pages):
        """Host snapshot of everything slot ``slot`` owns: its chain's
        page rows (``pages`` = the physical ids, from the block table)
        out of every pool leaf, plus its per-slot row of every non-pool
        (O(1)-state) leaf — so hybrid recurrent/attention stacks swap
        out whole.  Eager ops + one ``device_get``: preemption is a
        rare host-paced event, so it buys no extra jitted program and
        the decode step's compile count stays 1.  Int8 pools snapshot
        codes AND ``<key>_scale`` rows (they ride the same subtree), so
        a restore reproduces the quantized bytes bit-exactly."""
        if self.kv_layout != "paged":
            raise ValueError("preemption snapshots need kv_layout="
                             "'paged' (page swap is what makes them "
                             "cheap)")
        pg = jnp.asarray(pages, jnp.int32)
        snap = {}
        for name, sub in state.items():
            ax = self._slot_axis[name]
            if name in self._pool_names:
                def take(s, ax=ax):
                    return jnp.take(s, pg, axis=ax)
            else:
                def take(s, ax=ax):
                    return jax.lax.index_in_dim(s, int(slot), axis=ax,
                                                keepdims=False)
            snap[name] = jax.tree_util.tree_map(take, sub)
        return jax.device_get(snap)

    def restore_slot(self, state, snap, slot, pages):
        """Inverse of :meth:`snapshot_slot`: install a host snapshot
        into ``slot`` under a FRESH page chain ``pages``.  The new ids
        need not match the snapshotted ones — every decode read goes
        through the block table, so the resumed stream sees identical
        bytes at identical positions and (with the counter-based PRNG
        keyed on (seed, uid, pos)) decodes bitwise-equal to a run that
        was never preempted."""
        if self.kv_layout != "paged":
            raise ValueError("preemption restores need kv_layout="
                             "'paged'")
        pg = jnp.asarray(pages, jnp.int32)
        slot = int(slot)
        out = {}
        for name, sub in state.items():
            ax = self._slot_axis[name]
            if name in self._pool_names:
                def put(s, v, ax=ax):
                    v = jnp.asarray(v, s.dtype)
                    if ax == 0:
                        return s.at[pg].set(v)
                    return s.at[:, pg].set(v)
            else:
                def put(s, v, ax=ax):
                    v = jnp.asarray(v, s.dtype)
                    if ax == 0:
                        return s.at[slot].set(v)
                    return s.at[:, slot].set(v)
            out[name] = jax.tree_util.tree_map(put, sub, snap[name])
        if self.mesh is not None:
            # eager scatters can drift placement — re-pin to the serve
            # cache shardings so the next jitted step sees the one
            # placement it was compiled for
            out = jax.device_put(
                out, self._state_sharding(self.mesh, self._bound_slots))
        return out

    # -- mesh placement --------------------------------------------------
    def state_spec(self, batch):
        """ShapeDtypeStruct tree of init_state(batch) (no allocation)."""
        if self.kv_layout == "paged":
            return self.model.paged_cache_spec(
                batch, self.max_len, self.num_pages(batch),
                self.paged.page_size)
        if not self.positional:
            return self.model.cache_spec(batch, self.max_len)
        unit = self.model.cache_spec(1, self.max_len)
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((batch,) + s.shape, s.dtype),
            unit)

    def state_axes(self):
        """Logical axes of init_state's layout.  Native model layout for
        O(1)-state stacks; positional DENSE stacks stack per-slot unit
        caches, so the slot axis is prepended as a leading "batch" (the
        unit's own singleton batch dim then loses the DP divisibility
        race and replicates, as it should).  The PAGED layout is native
        again: page pools carry ("pages", "page", ...) — the page axis is
        never sharded (same contract as kv_len) while kv_heads / latents
        TP-shard — and the O(1) leaves keep their slot batch."""
        if self.kv_layout == "paged":
            return self.model.paged_cache_axes()
        axes = self.model.cache_axes()
        if not self.positional:
            return axes
        return jax.tree_util.tree_map(
            lambda t: ("batch",) + tuple(t), axes,
            is_leaf=lambda x: isinstance(x, tuple))

    def _state_sharding(self, mesh, batch):
        key = (id(mesh), batch)
        if key not in self._state_shardings:
            spec = shd.serve_cache_specs(self.state_axes(),
                                         self.state_spec(batch), mesh)
            self._state_shardings[key] = shd.named_sharding_tree(spec,
                                                                 mesh)
        return self._state_shardings[key]

    def shardings(self, mesh, slots, rules=None) -> ServeShardings:
        p_shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        p_spec = shd.param_specs(self.model, p_shapes, mesh, rules)
        return ServeShardings(
            mesh=mesh,
            params=shd.named_sharding_tree(p_spec, mesh),
            state=self._state_sharding(mesh, int(slots)),
            slot=NamedSharding(mesh, shd.dim0_dp_spec((int(slots),), mesh)),
            replicated=NamedSharding(mesh, P()))

    def bind_mesh(self, mesh, slots, rules=None) -> ServeShardings:
        """Rebuild the jitted programs for SPMD serving on ``mesh``:

        * the decode step and the admission scatter pin their state
          output to the serve cache shardings (so the engine's carried
          state never drifts placement between steps — one compiled
          program, not a placement-chasing family) and DONATE the
          incoming state buffer;
        * per-call host arrays are device_put against the slot sharding
          by :meth:`step` / :meth:`sample` / :meth:`write_slots`;
        * prefill templates and compiled programs are dropped so
          serve.prefill rebuilds them placed.
        """
        slots = int(slots)
        if (self.mesh is mesh
                and getattr(self, "_bound_slots", None) == slots
                and getattr(self, "_bound_rules", None) == rules):
            return self.sharding
        self._state_shardings = {}
        self._slot_shardings = {}
        self.mesh = mesh
        self._bound_slots = slots
        self._bound_rules = rules
        self.sharding = self.shardings(mesh, slots, rules)
        if self.kv_layout == "paged":
            self._jit_step = jax.jit(
                self._step_impl_paged, donate_argnums=(2,),
                out_shardings=(self.sharding.slot, self.sharding.state))
            self._jit_write = jax.jit(
                self._write_impl_paged, static_argnums=(4,),
                donate_argnums=(0,), out_shardings=self.sharding.state)
            self._jit_copy_slot = jax.jit(
                self._copy_slot_impl, donate_argnums=(0,),
                out_shardings=self.sharding.state)
            self._jit_copy_pages = jax.jit(
                self._copy_pages_impl, donate_argnums=(0,),
                out_shardings=self.sharding.state)
            self._jit_seed = jax.jit(self._seed_impl)
            # emitted tokens are (slots, K): rank-2 slot-leading — the
            # spec only reads dim0 divisibility, so any K shares it
            slot2 = NamedSharding(mesh,
                                  shd.dim0_dp_spec((slots, 2), mesh))
            self._jit_verify = jax.jit(
                self._verify_impl_paged, donate_argnums=(2,),
                out_shardings=(slot2, self.sharding.slot,
                               self.sharding.state))
        else:
            self._jit_step = jax.jit(
                self._step_impl, donate_argnums=(2,),
                out_shardings=(self.sharding.slot, self.sharding.state))
            self._jit_write = jax.jit(self._write_impl,
                                      donate_argnums=(0,),
                                      out_shardings=self.sharding.state)
        self._jit_sample = jax.jit(self._sample_impl)
        self._greedy = {}
        self._jit_prefill_fast = None
        self._jit_prefill_scan = None
        self._cache_templates = {}
        return self.sharding

    def place_cache(self, cache):
        """Place a NATIVE-layout prefill cache (batch = wave size) against
        the serve cache rules (used by serve.prefill for its templates)."""
        if self.mesh is None:
            return cache
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), cache)
        spec = shd.serve_cache_specs(self.model.cache_axes(), shapes,
                                     self.mesh)
        return jax.device_put(cache,
                              shd.named_sharding_tree(spec, self.mesh))

    # -- state ----------------------------------------------------------
    def init_state(self, batch):
        state = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.state_spec(batch))
        if self.mesh is not None:
            state = jax.device_put(state,
                                   self._state_sharding(self.mesh, batch))
        return state

    # -- prefill (an admission wave of same-length prompts) -------------
    def chunk_for(self, plen: int) -> int:
        """The chunk width a ``plen``-token prompt prefills at — part of
        the prefix-cache key: attaching is bitwise only between requests
        sharing one chunk grid."""
        return min(self.prefill_chunk, pow2ceil(int(plen)))

    def prefill(self, params, xs, pos0=0, cache0=None, start=0):
        """xs: (B, P) int32 prompts.  Grid-padded chunking via
        serve.prefill, with the chunk capped at the next power of two of
        the prompt: a 10-token prompt pays a 16-wide chunk, not the full
        ``prefill_chunk`` — padding waste stays < 2x while the chunk
        program family stays log2-bounded (each width compiles once and
        serves every prompt length that buckets to it).

        ``cache0``/``start``: prefix-attach tail prefill — resume from a
        seeded cache (see :meth:`seed_cache`), consuming only the chunks
        from ``start`` (chunk-grid aligned) onward."""
        from repro.serve.prefill import chunked_prefill
        chunk = self.chunk_for(xs.shape[1])
        return chunked_prefill(self, params, xs, chunk=chunk, pos0=pos0,
                               cache0=cache0, start=start)

    # -- decode ---------------------------------------------------------
    def _step_impl(self, params, tok, state, pos, active, samp):
        if not self.positional:
            logits, new_state = self.model.decode_step(
                params, tok[:, None], state, jnp.int32(0))
            logits = logits[:, -1, :]
            merged = {}
            for name, sub in state.items():
                ax = self._slot_axis[name]
                merged[name] = masked_update(sub, new_state[name],
                                             active, axis=ax)
        else:
            vstep = jax.vmap(self.model.decode_step,
                             in_axes=(None, 0, 0, 0))
            logits, new_state = vstep(params, tok[:, None, None], state, pos)
            logits = logits[:, 0, -1, :]
            merged = masked_update(state, new_state, active)
        # the token produced from input position p lands at position p+1 —
        # the PRNG key folds in the GENERATED token's position, so the
        # admission-sampled first token (at pos = prompt length) and the
        # decode stream never collide on a counter value
        return self._sample_impl(logits, samp, pos + 1), merged

    def _step_impl_paged(self, params, tok, state, pos, active, samp, bt):
        """Natively slot-batched paged decode (no vmap: the page pools
        are shared state).  Pool leaves come back already frozen for
        inactive slots — their write was dropped in-layer — so only the
        per-slot O(1) leaves take the masked merge."""
        logits, new_state = self.model.decode_step_paged(
            params, tok[:, None], state, pos, bt, active, self.max_len)
        logits = logits[:, -1, :]
        merged = {}
        for name, sub in state.items():
            if name in self._pool_names:
                merged[name] = new_state[name]
            else:
                merged[name] = masked_update(sub, new_state[name], active,
                                             axis=self._slot_axis[name])
        return self._sample_impl(logits, samp, pos + 1), merged

    def step(self, params, tok, state, pos, active, sampling=None,
             bt=None):
        """tok: (slots,) int32; pos, active: (slots,); sampling: dict of
        per-slot knob arrays (None -> all-greedy arrays of the same
        dtypes, so greedy/sampled traffic share ONE compiled program);
        bt: (slots, max_pages) int32 block tables (paged layout only —
        plain DATA through the jitted step, like the sampling knobs).
        Under a bound mesh every host-side array is device_put against
        the slot sharding first, so each step dispatches the same
        compiled SPMD program (placement is part of the jit key)."""
        if sampling is None:
            n = int(tok.shape[0])
            if n not in self._greedy:
                g = greedy_arrays(n)
                if self.mesh is not None:
                    g = {k: self.put_slot(v) for k, v in g.items()}
                self._greedy[n] = g
            sampling = self._greedy[n]
        if self.mesh is not None:
            tok, pos, active = (self.put_slot(tok), self.put_slot(pos),
                                self.put_slot(active))
            sampling = {k: self.put_slot(v) for k, v in sampling.items()}
        if self.kv_layout == "paged":
            if bt is None:
                raise ValueError("paged kv_layout needs block tables "
                                 "(the engine passes pool.block_tables)")
            bt = jnp.asarray(bt, jnp.int32)
            if self.mesh is not None:
                bt = self.put_slot(bt)
            return self._jit_step(params, tok, state, pos, active,
                                  sampling, bt)
        return self._jit_step(params, tok, state, pos, active, sampling)

    # -- speculative verify (serve/spec.py + the engine drive this) ------
    def _verify_impl_paged(self, params, toks, state, pos, active, k_slot,
                           samp, bt):
        """ONE jitted program for the whole verify wave: score the K fed
        tokens against the untouched pools, run the rejection/residual
        verifier on the real-vocab fp32 logits, then commit exactly the
        accepted prefix's K/V — the pool never holds a speculative byte,
        so rollback is simply "don't advance pos"."""
        logits, blocks = self.model.verify_step_paged(
            params, toks, state, pos, bt, active, self.max_len)
        lg = logits[..., :self.vocab].astype(jnp.float32)
        emitted, n_emit = verify_tokens(
            lg, toks, k_slot, samp["seed"], samp["uid"], samp["uid_hi"],
            pos, samp["temperature"], samp["top_k"], samp["top_p"])
        n_emit = jnp.where(active, n_emit, 0)
        merged = self.model.commit_step_paged(
            state, blocks, pos, bt, n_emit, active, self.max_len)
        return emitted, n_emit, merged

    def verify(self, params, toks, state, pos, active, k_slot,
               sampling=None, bt=None):
        """k-token speculative verify.  ``toks``: (slots, K) int32 — per
        slot the CURRENT token (last emitted, not yet in cache) followed
        by K-1 greedy drafts, fed at positions ``pos .. pos+K-1``;
        ``k_slot``: (slots,) int32 per-slot verify widths (1..K — plain
        DATA, so heterogeneous widths share one compiled program).
        Returns ``(emitted (slots, K), n_emit (slots,), state)``:
        ``emitted[b, :n_emit[b]]`` are the tokens for stream positions
        ``pos[b]+1 ..``, their K/V already committed page-granularly
        (inactive slots report ``n_emit == 0`` and commit nothing).
        ``k_slot == 1`` everywhere is bitwise the plain :meth:`step`."""
        if self.kv_layout != "paged":
            raise ValueError("speculative verify needs kv_layout='paged' "
                             "(rollback = uncommitted pages)")
        if bt is None:
            raise ValueError("paged verify needs block tables "
                             "(the engine passes pool.block_tables)")
        toks = jnp.asarray(toks, jnp.int32)
        k_slot = jnp.asarray(k_slot, jnp.int32)
        bt = jnp.asarray(bt, jnp.int32)
        if sampling is None:
            n = int(toks.shape[0])
            if n not in self._greedy:
                g = greedy_arrays(n)
                if self.mesh is not None:
                    g = {k: self.put_slot(v) for k, v in g.items()}
                self._greedy[n] = g
            sampling = self._greedy[n]
        if self.mesh is not None:
            toks, pos, active = (self.put_slot(toks), self.put_slot(pos),
                                 self.put_slot(active))
            k_slot, bt = self.put_slot(k_slot), self.put_slot(bt)
            sampling = {k: self.put_slot(v) for k, v in sampling.items()}
        return self._jit_verify(params, toks, state, pos, active, k_slot,
                                sampling, bt)

    def _sample_impl(self, logits, samp, pos):
        """Per-row counter-keyed sampling over the REAL vocab; greedy rows
        (temperature <= 0) take the argmax path inside the same program.
        A runtime cond skips the whole stochastic pipeline (sorts, PRNG)
        when EVERY slot is greedy, so all-greedy traffic keeps the plain
        argmax hot path without a second compiled program."""
        lg = logits[..., :self.vocab].astype(jnp.float32)
        return jax.lax.cond(
            jnp.any(samp["temperature"] > 0.0),
            lambda: sample_tokens(lg, samp["seed"], samp["uid"],
                                  samp["uid_hi"], pos,
                                  samp["temperature"], samp["top_k"],
                                  samp["top_p"]),
            lambda: jnp.argmax(lg, -1).astype(jnp.int32))

    def sample(self, logits, sampling, pos):
        """Draw one token per row of ``logits`` (admission-wave shape)."""
        pos = jnp.asarray(pos, jnp.int32)
        if self.mesh is not None:
            sampling = {k: self.put_slot(v) for k, v in sampling.items()}
            pos = self.put_slot(pos)
        return self._jit_sample(logits, sampling, pos)

    def _emit_impl(self, logits):
        """Greedy over the REAL vocab (ignore Megatron padding columns).
        Kept as a debugging helper — the serving paths go through
        sample()/step(), whose greedy branch is this same argmax."""
        return jnp.argmax(logits[..., :self.vocab], -1).astype(jnp.int32)

    # -- slot writes ----------------------------------------------------
    def _write_impl(self, state, batch_state, slots):
        out = {}
        for name, sub in state.items():
            ax = self._slot_axis[name]

            def upd(s, v, ax=ax):
                if self.positional:
                    # stacked layout (slots, *unit): bring the cache batch
                    # axis to the front, re-insert its singleton, scatter.
                    v2 = jnp.expand_dims(jnp.moveaxis(v, ax, 0), 1 + ax)
                    return s.at[slots].set(v2.astype(s.dtype))
                if ax == 0:
                    return s.at[slots].set(v.astype(s.dtype))
                return s.at[:, slots].set(v.astype(s.dtype))

            out[name] = jax.tree_util.tree_map(upd, sub, batch_state[name])
        return out

    def _write_impl_paged(self, state, batch_state, slots, pages, plen):
        """Admission-wave install under the paged layout: O(1)-state
        leaves scatter at their slot ids (native layout), attention
        leaves scatter PAGE-granularly — the wave's dense prefill cache
        is resliced into (page,)-sized rows that land at the chain's page
        ids.  ``pages`` rows of padding wave entries are all out of
        bounds, so their writes drop exactly like padded slot ids."""
        ps = self.paged.page_size
        out = {}
        for name, sub in state.items():
            ax = self._slot_axis[name]
            if name in self._pool_names:
                def rows(v, ax=ax):
                    # v: dense wave cache; slot axis at ax, length at ax+1
                    # -> ((..., n, ps, ...) page rows, n)
                    Lv = v.shape[ax + 1]
                    n = -(-min(plen, Lv) // ps)
                    take = min(n * ps, Lv)
                    sl = [slice(None)] * v.ndim
                    sl[ax + 1] = slice(0, take)
                    v2 = v[tuple(sl)]
                    if take < n * ps:     # ring shorter than whole pages
                        padw = [(0, 0)] * v.ndim
                        padw[ax + 1] = (0, n * ps - take)
                        v2 = jnp.pad(v2, padw)
                    shape = v2.shape[:ax + 1] + (n, ps) + v2.shape[ax + 2:]
                    return v2.reshape(shape), n

                def scat(s, v2, n, ax=ax):
                    if ax == 0:
                        return s.at[pages[:, :n]].set(v2)
                    return s.at[:, pages[:, :n]].set(v2)

                # int8 pools carry float32 ``<key>_scale`` leaves the
                # dense wave cache does not have: quantize each data
                # leaf's page rows on install (symmetric absmax scale per
                # page per feature row) and scatter codes + scales.
                # Re-installing an unchanged page (prefix attaches
                # rewrite the whole chain) reproduces its codes
                # bit-exactly: a quantized page's max |code| is QMAX, so
                # the recomputed scale matches to float rounding.
                qkeys = ({k for k in sub if k + "_scale" in sub}
                         if isinstance(sub, dict) else set())
                if qkeys:
                    from repro.kernels.paged_attention import quant as kvq
                    nsub = {}
                    for key in sorted(qkeys):
                        v2, n = rows(batch_state[name][key])
                        sc = kvq.page_abs_scale(v2, page_axis=ax + 2)
                        codes = kvq.quantize(v2, sc, page_axis=ax + 2)
                        nsub[key] = scat(sub[key], codes, n)
                        nsub[key + "_scale"] = scat(sub[key + "_scale"],
                                                    sc, n)
                    out[name] = nsub
                    continue

                def updp(s, v, ax=ax):
                    v2, n = rows(v, ax=ax)
                    return scat(s, v2.astype(s.dtype), n, ax=ax)

                out[name] = jax.tree_util.tree_map(updp, sub,
                                                   batch_state[name])
            else:
                def upd(s, v, ax=ax):
                    if ax == 0:
                        return s.at[slots].set(v.astype(s.dtype))
                    return s.at[:, slots].set(v.astype(s.dtype))

                out[name] = jax.tree_util.tree_map(upd, sub,
                                                   batch_state[name])
        return out

    def write_slots(self, state, batch_state, slots, pages=None,
                    plen=None):
        """Install an admission wave's prefill carry into its slots.
        Paged layout: ``pages`` = the wave's block-table rows (padding
        rows all out of bounds) and ``plen`` = the wave's prompt length
        (static: it fixes how many pages each layer writes)."""
        slots = jnp.asarray(slots, jnp.int32)
        if self.mesh is not None:
            slots = jax.device_put(slots, self.sharding.replicated)
        if self.kv_layout == "paged":
            if pages is None or plen is None:
                raise ValueError("paged write_slots needs the wave's page "
                                 "rows and its prompt length")
            pages = jnp.asarray(pages, jnp.int32)
            if self.mesh is not None:
                pages = jax.device_put(pages, self.sharding.replicated)
            # the write program depends on plen only through per-leaf
            # PAGE counts, so round up to a page multiple before it
            # becomes the static jit key: prompt lengths that share page
            # buckets share one compiled write (identical program either
            # way — the page count ceil()s to the same value)
            ps = self.paged.page_size
            return self._jit_write(state, batch_state, slots, pages,
                                   -(-int(plen) // ps) * ps)
        return self._jit_write(state, batch_state, slots)


# ---------------------------------------------------------------------------
# MinimalistNetwork adapter (paper's edge-streaming case)
# ---------------------------------------------------------------------------

class MinimalistStepModel(StepModel):
    """Frame-streaming StepModel over ``core.mingru.MinimalistNetwork``.

    ``use_fused_kernel=True`` serves the exported hardware model through
    the fused single-step Pallas kernel (kernels.minimalist_block) — pass
    the *trained block params* as usual; the 2 b-code export
    (:func:`repro.kernels.minimalist_block.ops.from_block_params`) is
    cached per params object and redone (with a fresh jit trace, since
    the codes are baked in as constants) whenever a different params
    pytree is passed.
    """

    autoregressive = False

    def __init__(self, net, *, scan_backend=None, use_fused_kernel=False,
                 kernel_backend="pallas"):
        self.net = net
        self.scan_backend = scan_backend
        self.use_fused_kernel = use_fused_kernel
        self.kernel_backend = kernel_backend
        self._exported = None
        self._export_src = None
        self._jit_step = jax.jit(self._step_impl)
        self._jit_write = jax.jit(self._write_impl)

    # -- mesh placement --------------------------------------------------
    # Frame streaming serves DP-only: slots (and their O(1) states) shard
    # over "data"; the paper-scale analog blocks are far too small to pay
    # TP collectives, so params replicate.
    def shardings(self, mesh, slots, rules=None) -> ServeShardings:
        del rules
        repl = NamedSharding(mesh, P())
        state_shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.net.initial_state(int(slots)))
        return ServeShardings(
            mesh=mesh, params=repl,
            state=shd.named_sharding_tree(
                shd.slot_specs(state_shapes, mesh), mesh),
            slot=NamedSharding(mesh, shd.dim0_dp_spec((int(slots),), mesh)),
            replicated=repl)

    def bind_mesh(self, mesh, slots, rules=None) -> ServeShardings:
        del rules                        # DP-only: no rule table in play
        slots = int(slots)
        if self.mesh is mesh and getattr(self, "_bound_slots", None) == slots:
            return self.sharding
        self._slot_shardings = {}
        self.mesh = mesh
        self._bound_slots = slots
        self.sharding = self.shardings(mesh, slots)
        self._jit_step = jax.jit(self._step_impl, donate_argnums=(2,),
                                 out_shardings=(self.sharding.slot,
                                                self.sharding.state))
        self._jit_write = jax.jit(self._write_impl, donate_argnums=(0,),
                                  out_shardings=self.sharding.state)
        return self.sharding

    def _export(self, params):
        """(Re)export 2 b codes when a different params object arrives.
        The codes enter the fused step as jit CONSTANTS, so the step jit
        is rebuilt alongside them — otherwise stale weights would serve
        silently after a checkpoint reload or QAT phase change."""
        if self._exported is None or self._export_src is not params:
            from repro.kernels.minimalist_block import ops as mb_ops
            self._exported = [mb_ops.from_block_params(params[b.name])
                              for b in self.net.blocks]
            self._export_src = params
            if self.mesh is not None:     # keep the bound-mesh jit options
                self._jit_step = jax.jit(
                    self._step_impl, donate_argnums=(2,),
                    out_shardings=(self.sharding.slot, self.sharding.state))
            else:
                self._jit_step = jax.jit(self._step_impl)
        return self._exported

    def init_state(self, batch):
        state = self.net.initial_state(batch)
        if self.mesh is not None:
            shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
            state = jax.device_put(state, shd.named_sharding_tree(
                shd.slot_specs(shapes, self.mesh), self.mesh))
        return state

    def _raw_step(self, params, x, state):
        if self.use_fused_kernel:
            from repro.kernels.minimalist_block import ops as mb_ops
            out, new_states = x, []
            for i, exp in enumerate(self._exported):
                y, h = mb_ops.minimalist_step(
                    out, *exp, state[i], backend=self.kernel_backend)
                new_states.append(h)
                # readout layer: the analog h is the result (no comparator)
                out = h if i == len(self._exported) - 1 else y
            return out, new_states
        return self.net.step(params, x, state)

    def _step_impl(self, params, x, state, pos, active):
        del pos
        out, new_state = self._raw_step(params, x, state)
        return out, masked_update(state, new_state, active)

    def step(self, params, x, state, pos, active, sampling=None):
        """x: (slots, d_in) frames; pos unused (position-free); sampling
        ignored — frame streaming emits analog outputs, not tokens."""
        del sampling
        if self.use_fused_kernel:
            self._export(params)        # host-side, once; jit sees constants
        if self.mesh is not None:
            x, pos, active = (self.put_slot(x), self.put_slot(pos),
                              self.put_slot(active))
        return self._jit_step(params, x, state, pos, active)

    def emit(self, out):
        return out

    def _write_impl(self, state, batch_state, slots):
        return jax.tree_util.tree_map(
            lambda s, v: s.at[slots].set(v.astype(s.dtype)),
            state, batch_state)

    def write_slots(self, state, batch_state, slots):
        slots = jnp.asarray(slots, jnp.int32)
        if self.mesh is not None:
            slots = jax.device_put(slots, self.sharding.replicated)
            batch_state = jax.tree_util.tree_map(self.put_slot, batch_state)
        return self._jit_write(state, batch_state, slots)
