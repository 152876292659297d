"""Attention blocks: GQA (global + sliding window) and DeepSeek MLA.

All variants support the three execution regimes of the assignment:
  * train/prefill  — full-sequence causal attention
  * decode         — single new token against a KV cache
    (GQA: ring-buffer cache for local layers; MLA: compressed latent cache
    with the weight-absorption trick, which is what makes MLA's small cache
    pay off at decode time)
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MLAConfig
from repro.kernels.paged_attention import quant as kvq
from repro.kernels.paged_attention.ref import (gather_dequant, gather_pages,
                                               paged_positions)
from repro.models.module import Module, RMSNorm, fan_in_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def causal_mask(q_pos, k_pos, window: int | None = None):
    """(…, Sq, Sk) additive mask: causal, optionally banded (sliding)."""
    m = q_pos[..., :, None] >= k_pos[..., None, :]
    if window is not None:
        m &= (q_pos[..., :, None] - k_pos[..., None, :]) < window
    return jnp.where(m, 0.0, NEG_INF)


def _paged_write_rows(pool, page, row, x):
    """pool (P, page, KV, hd) with x (B, KV, hd) written at entry ``row``
    of ``page`` per slot, through the pool's (P, page, KV*hd) view."""
    P, ps = pool.shape[:2]
    flat = pool.reshape(P, ps, -1).at[page, row].set(
        x.reshape(x.shape[0], -1).astype(pool.dtype))
    return flat.reshape(pool.shape)


def _paged_write_q8(pool, scale, wpage, in_page, fresh, tok):
    """Quantized page-granular decode write (kv_dtype="int8"): read the
    slot's live page row, grow its scale monotonically to admit the new
    token, rescale the existing codes, scatter the token's codes, write
    the row and scale back.

    In the steady state the scale is unchanged, the rescale ratio is
    exactly 1.0, and round(c * 1.0) == c — repeated decode writes never
    perturb stored codes.  ``fresh`` marks writes that START a new page:
    the previous tenant's scale is reset to 0 there, which also zeroes
    its stale codes through the rescale.  (A sliding-window ring recycles
    pages in place, so its pages are fresh only on the first lap and the
    scale grows monotonically over the window's history — conservative,
    never wrong.)

    pool: (P, ps, *feat, d) int8; scale: (P, *feat) f32; wpage: (B,)
    page ids (out of bounds for inactive slots — reads clamp, writes
    drop, which IS the frozen-slot merge); in_page: (B,) in-page index;
    tok: (B, *feat, d) this step's values."""
    B = tok.shape[0]
    rows = pool[wpage]                            # (B, ps, *feat, d)
    old_s = scale[wpage]                          # (B, *feat)
    f = fresh.reshape((B,) + (1,) * (old_s.ndim - 1))
    old_s = jnp.where(f, 0.0, old_s)
    tok_s = jnp.max(jnp.abs(tok.astype(jnp.float32)), axis=-1) / kvq.QMAX
    new_s = jnp.maximum(jnp.maximum(old_s, tok_s), kvq.MIN_SCALE)
    rows = kvq.rescale_codes(rows, old_s, new_s)
    code = jnp.clip(jnp.round(tok.astype(jnp.float32) / new_s[..., None]),
                    -kvq.QMAX, kvq.QMAX).astype(jnp.int8)
    rows = rows.at[jnp.arange(B), in_page].set(code)
    return pool.at[wpage].set(rows), scale.at[wpage].set(new_s)


def _sdpa(q, k, v, mask):
    """q: (B,S,H,hd), k/v: (B,L,KV,hd) with H = KV*G. mask: (B,1,S,L)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgd,blkd->bkgsl", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd) + mask[:, :, None, :, :]
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgsl,blkd->bskgd", w, v)
    return out.reshape(B, S, H, hd)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

class GQAAttention(Module):
    def __init__(self, cfg: ModelConfig, *, local: bool = False, name="attn",
                 dtype=jnp.float32):
        self.cfg = cfg
        self.local = local
        self.window = cfg.sliding_window if local else None
        self.name = name
        self.dtype = dtype

    def init(self, key):
        c = self.cfg
        ks = jax.random.split(key, 4)
        shp = dict(dtype=self.dtype)
        d, H, KV, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
        mk = lambda k, s: fan_in_init(k, s, self.dtype, fan_in=s[0])
        return {
            "wq": mk(ks[0], (d, H * hd)).reshape(d, H, hd),
            "wk": mk(ks[1], (d, KV * hd)).reshape(d, KV, hd),
            "wv": mk(ks[2], (d, KV * hd)).reshape(d, KV, hd),
            "wo": fan_in_init(ks[3], (H * hd, d), self.dtype).reshape(H, hd, d),
        }

    def axes(self):
        return {"wq": ("embed", "heads", "head_dim"),
                "wk": ("embed", "kv_heads", "head_dim"),
                "wv": ("embed", "kv_heads", "head_dim"),
                "wo": ("heads", "head_dim", "embed")}

    def _qkv(self, params, x, positions):
        c = self.cfg
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)
        return q, k, v

    def __call__(self, params, x, positions=None):
        """Full-sequence causal attention. x: (B, S, D)."""
        B, S, _ = x.shape
        impl = self.cfg.attention_impl
        if positions is None:
            positions = jnp.arange(S)
        q, k, v = self._qkv(params, x, positions)
        if impl == "stub":
            # dry-run stand-in: O(S·d) op with grads to q/k/v; the real
            # kernel's cost is added analytically by launch.dryrun
            out = q + (k.mean(1, keepdims=True) + v.mean(1, keepdims=True)
                       ).mean(2, keepdims=True)
        elif impl == "flash":
            from repro.kernels.flash_attention.ops import flash_attention
            out = flash_attention(
                jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1), True, self.window, "pallas")
            out = jnp.moveaxis(out, 1, 2)
        else:
            pos = jnp.broadcast_to(positions, (B, S)) \
                if positions.ndim == 1 else positions
            mask = causal_mask(pos, pos, self.window)[:, None]
            out = _sdpa(q, k, v, mask)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))

    # --- decode ---
    def init_cache(self, batch, length, dtype=jnp.bfloat16):
        c = self.cfg
        L = min(length, self.window) if self.window else length
        return {
            "k": jnp.zeros((batch, L, c.n_kv_heads, c.head_dim), dtype),
            "v": jnp.zeros((batch, L, c.n_kv_heads, c.head_dim), dtype),
        }

    def cache_spec(self, batch, length, dtype=jnp.bfloat16):
        c = self.cfg
        L = min(length, self.window) if self.window else length
        s = jax.ShapeDtypeStruct((batch, L, c.n_kv_heads, c.head_dim), dtype)
        return {"k": s, "v": s}

    def cache_axes(self):
        a = ("batch", "kv_len", "kv_heads", "head_dim")
        return {"k": a, "v": a}

    def can_prefill(self):
        return True

    def prefill(self, params, x, cache, pos0, length=None):
        """Chunk prefill.  Tokens at in-chunk index >= ``length`` are grid
        padding: masked out of attention and never written to the cache
        (``length=None`` means the whole chunk is valid).

        Global: scatter K/V at absolute positions [pos0, pos0+length) and
        attend causally against the whole cache.  Sliding-window: attend
        each query against (ring snapshot ++ in-chunk K/V), then perform a
        wrap-aware masked ring scatter of the last min(L, length) valid
        tokens — exactly one writer per ring slot, so chunk writes that
        cross the ring boundary neither clobber live entries nor skip
        slots (the ROADMAP wrap bug)."""
        B, S, _ = x.shape
        if length is None:
            length = jnp.int32(S)
        positions = pos0 + jnp.arange(S)
        q, k, v = self._qkv(params, x,
                            jnp.broadcast_to(positions, (B, S)))
        L = cache["k"].shape[1]
        i = jnp.arange(S)
        valid = i < length
        if not self.local:
            # index L is out of bounds -> the scatter drops padding writes
            idx = jnp.where(valid & (positions < L), positions, L)
            ck = cache["k"].at[:, idx].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[:, idx].set(v.astype(cache["v"].dtype))
            k_pos = jnp.arange(L)
            mask = jnp.where(k_pos[None, :] <= positions[:, None], 0.0,
                             NEG_INF)[None, None]        # (1, 1, S, L)
            mask = jnp.broadcast_to(mask, (B, 1, S, L))
            out = _sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), mask)
        else:
            # the ring retains only L positions, so the effective window
            # matches the scanned decode path: min(window, L)
            W = min(self.window, L)
            # ring snapshot: entry j holds the latest position <= pos0-1
            # congruent to j (mod L); queries may not see entries this
            # chunk is about to overwrite, hence snapshot-then-write
            j = jnp.arange(L)
            ring_pos = (pos0 - 1) - ((pos0 - 1 - j) % L)
            ring_m = ((ring_pos >= 0) & (pos0 >= 1))[None, :] \
                & (positions[:, None] - ring_pos[None, :] < W)
            in_m = ((positions[None, :] <= positions[:, None])
                    & (positions[:, None] - positions[None, :] < W)
                    & valid[None, :])
            mask = jnp.where(jnp.concatenate([ring_m, in_m], axis=1),
                             0.0, NEG_INF)               # (S, L+S)
            mask = jnp.broadcast_to(mask[None, None], (B, 1, S, L + S))
            kk = jnp.concatenate([cache["k"].astype(q.dtype), k], axis=1)
            vv = jnp.concatenate([cache["v"].astype(q.dtype), v], axis=1)
            out = _sdpa(q, kk, vv, mask)
            # ring write: of the valid tokens, only the last L survive a
            # wrap — dropping the aliased older ones keeps one writer per
            # slot (duplicate-index scatter order is unspecified)
            wmask = valid & (i >= length - L)
            idx = jnp.where(wmask, (pos0 + i) % L, L)
            ck = cache["k"].at[:, idx].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[:, idx].set(v.astype(cache["v"].dtype))
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        return y, {"k": ck, "v": cv}

    # --- paged decode (shared page pool + per-request block tables) ---
    def paged_cache_spec(self, num_pages, page_size, dtype=jnp.bfloat16):
        c = self.cfg
        if c.kv_dtype == "int8":
            s = jax.ShapeDtypeStruct(
                (num_pages, page_size, c.n_kv_heads, c.head_dim), jnp.int8)
            sc = jax.ShapeDtypeStruct((num_pages, c.n_kv_heads),
                                      jnp.float32)
            return {"k": s, "v": s, "k_scale": sc, "v_scale": sc}
        s = jax.ShapeDtypeStruct(
            (num_pages, page_size, c.n_kv_heads, c.head_dim), dtype)
        return {"k": s, "v": s}

    def paged_cache_axes(self):
        a = ("pages", "page", "kv_heads", "head_dim")
        if self.cfg.kv_dtype == "int8":
            sc = ("pages", "kv_heads")
            return {"k": a, "v": a, "k_scale": sc, "v_scale": sc}
        return {"k": a, "v": a}

    def ring_length(self, length):
        """Dense in-cache length this layer emulates at engine max_len
        ``length`` (the sliding-window ring retains only the window)."""
        return min(length, self.window) if self.window else length

    def decode_paged(self, params, x, cache, pos, bt, active, length):
        """One slot-batched decode step against the page pool.

        x: (B, 1, D); pos/active: (B,); bt: (B, max_pages) page ids;
        cache: {"k","v"} pools (P, page, KV, hd); ``length`` = the
        engine's max_len.  The current token's K/V is scattered into the
        slot's live page (inactive slots write out of bounds — dropped,
        which IS the frozen-slot merge for pool state), then attention
        reads the chain back.  The default "gather" impl reconstructs the
        dense in-cache view and runs EXACTLY the dense ``decode`` math —
        entry j of the view equals dense cache entry j bitwise wherever
        the causal/window mask can see it, so paged == dense bitwise
        (bf16 pools).  "pallas" (the default) / "pallas_tpu" route the
        read through the page-indirect kernel instead (fp32 online
        softmax over each slot's live pages; inactive slots walk none
        and read zeros; no dense view is built).  With kv_dtype="int8" the
        write quantizes into the slot's live page (``_paged_write_q8``)
        and the read dequantizes per page — in-register in the kernel,
        via the scale gather on the oracle path."""
        B = x.shape[0]
        q, k, v = self._qkv(params, x, pos[:, None])
        Pp, ps = cache["k"].shape[0], cache["k"].shape[1]
        L = self.ring_length(length)
        slot = (pos % L) if self.window else pos          # in-cache index
        wpage = jnp.where(active, bt[jnp.arange(B), slot // ps], Pp)
        q8 = self.cfg.kv_dtype == "int8"
        if q8:
            # a page is brand-new only when the write lands on its first
            # entry at an unwrapped position (ring laps recycle in place)
            fresh = ((slot % ps) == 0) & (pos == slot)
            ck, cks = _paged_write_q8(cache["k"], cache["k_scale"],
                                      wpage, slot % ps, fresh, k[:, 0])
            cv, cvs = _paged_write_q8(cache["v"], cache["v_scale"],
                                      wpage, slot % ps, fresh, v[:, 0])
            new = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        else:
            cks = cvs = None
            # the write goes through the (P, page, KV*hd) view the
            # kernel reads, so the pool is laid out once for both
            ck, cv = (_paged_write_rows(cache[n], wpage, slot % ps, x[:, 0])
                      for n, x in (("k", k), ("v", v)))
            new = {"k": ck, "v": cv}
        impl = self.cfg.paged_impl
        if impl != "gather":
            from repro.kernels.paged_attention.ops import paged_gqa_attention
            out = paged_gqa_attention(
                q[:, 0], ck, cv, bt, pos, length=L, window=self.window,
                backend=impl, k_scale=cks, v_scale=cvs,
                active=active)[:, None]
        else:
            if q8:
                kd = gather_dequant(ck, cks, bt, L, q.dtype)
                vd = gather_dequant(cv, cvs, bt, L, q.dtype)
            else:
                kd = gather_pages(ck, bt, L).astype(q.dtype)
                vd = gather_pages(cv, bt, L).astype(q.dtype)
            _k_pos, valid = paged_positions(pos, L, self.window)
            mask = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
            out = _sdpa(q, kd, vd, mask)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        return y, new

    # --- speculative k-token verify (read-only; commit is separate) ---
    def verify_paged(self, params, x, cache, pos, bt, active, length):
        """Score K speculative tokens against the page pool WITHOUT
        writing it.  x: (B, K, D) holds the current token plus K-1
        drafts at positions ``pos .. pos+K-1``.

        Each query i attends through a per-query dense view: the
        gathered pool snapshot with the in-flight K/V of tokens m <= i
        overlaid at their native in-cache indices — exactly the view a
        sequential ``decode_paged`` at ``pos+i`` would read (the write-
        then-gather order, the ring eviction of entries more than L back,
        and the position mask all match by construction), and each query
        runs the identical S=1 ``_sdpa`` program, so greedy verify logits
        are bitwise the sequential gather-path logits.  Requires
        K <= ring length (the engine validates ``spec_k`` against it).

        Returns ``(y (B, K, D), block)`` where ``block`` holds the
        cache-dtype K/V of all K tokens for a later ``commit_paged`` of
        however many the verifier accepts — the pool never holds a
        speculative byte, so rollback is simply not committing."""
        if self.cfg.kv_dtype == "int8":
            raise NotImplementedError(
                "speculative verify requires bf16 pools (int8 page "
                "rescale is not replayable per accepted prefix)")
        B, K, _ = x.shape
        positions = pos[:, None] + jnp.arange(K)[None, :]
        q, k, v = self._qkv(params, x, positions)
        L = self.ring_length(length)
        slot = (positions % L) if self.window else positions   # (B, K)
        kd0 = gather_pages(cache["k"], bt, L)      # snapshot, pool dtype
        vd0 = gather_pages(cache["v"], bt, L)
        kc = k.astype(cache["k"].dtype)
        vc = v.astype(cache["v"].dtype)
        rows = jnp.arange(B)[:, None]
        outs = []
        for i in range(K):
            ki = kd0.at[rows, slot[:, :i + 1]].set(
                kc[:, :i + 1]).astype(q.dtype)
            vi = vd0.at[rows, slot[:, :i + 1]].set(
                vc[:, :i + 1]).astype(q.dtype)
            _k_pos, valid = paged_positions(pos + i, L, self.window)
            mask = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
            outs.append(_sdpa(q[:, i:i + 1], ki, vi, mask))
        out = jnp.concatenate(outs, axis=1)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        return y, {"k": kc, "v": vc}

    def commit_paged(self, cache, block, pos, bt, n_commit, active,
                     length):
        """Scatter the first ``n_commit[b]`` verified tokens of
        ``block`` (from :meth:`verify_paged`) into the pool at positions
        ``pos[b] .. pos[b]+n_commit[b]-1``.  Rejected/invalid entries
        write out of bounds — dropped, like every frozen-slot write."""
        B, K = block["k"].shape[:2]
        Pp, ps = cache["k"].shape[0], cache["k"].shape[1]
        L = self.ring_length(length)
        j = jnp.arange(K)
        p = pos[:, None] + j[None, :]
        slot = (p % L) if self.window else p
        ok = (j[None, :] < n_commit[:, None]) & active[:, None]
        wpage = jnp.where(ok, bt[jnp.arange(B)[:, None], slot // ps], Pp)
        return {"k": cache["k"].at[wpage, slot % ps].set(block["k"]),
                "v": cache["v"].at[wpage, slot % ps].set(block["v"])}

    def decode(self, params, x, cache, pos):
        """One-step decode. x: (B, 1, D); pos: scalar current position."""
        B = x.shape[0]
        q, k, v = self._qkv(params, x, jnp.full((B, 1), pos))
        L = cache["k"].shape[1]
        slot = (pos % L) if self.window else pos
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        # key positions: ring buffer for local, linear for global
        idx = jnp.arange(L)
        if self.window:
            # entry i holds position: the largest p ≤ pos with p % L == i
            k_pos = pos - ((pos - idx) % L)
        else:
            k_pos = idx
        valid = (k_pos <= pos) & (k_pos >= 0)
        if self.window:
            valid &= (pos - k_pos) < self.window
        mask = jnp.where(valid, 0.0, NEG_INF)[None, None, None, :]
        mask = jnp.broadcast_to(mask, (B, 1, 1, L))
        out = _sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), mask)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# DeepSeek Multi-head Latent Attention
# ---------------------------------------------------------------------------

class MLAAttention(Module):
    def __init__(self, cfg: ModelConfig, name="mla", dtype=jnp.float32):
        assert cfg.mla is not None
        self.cfg = cfg
        self.m: MLAConfig = cfg.mla
        self.name = name
        self.dtype = dtype

    def init(self, key):
        c, m = self.cfg, self.m
        d, H = c.d_model, c.n_heads
        qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
        ks = jax.random.split(key, 6)
        mk = lambda k, s, f: fan_in_init(k, s, self.dtype, fan_in=f)
        return {
            "w_dq": mk(ks[0], (d, m.q_lora_rank), d),
            "w_uq": mk(ks[1], (m.q_lora_rank, H, qk_head), m.q_lora_rank),
            "w_dkv": mk(ks[2], (d, m.kv_lora_rank), d),
            "w_kr": mk(ks[3], (d, m.qk_rope_head_dim), d),
            "w_ukv": mk(ks[4], (m.kv_lora_rank, H,
                                m.qk_nope_head_dim + m.v_head_dim),
                        m.kv_lora_rank),
            "wo": mk(ks[5], (H, m.v_head_dim, d), H * m.v_head_dim),
            "q_norm": jnp.ones((m.q_lora_rank,), self.dtype),
            "kv_norm": jnp.ones((m.kv_lora_rank,), self.dtype),
        }

    def axes(self):
        return {"w_dq": ("embed", "q_lora"),
                "w_uq": ("q_lora", "heads", "head_dim"),
                "w_dkv": ("embed", "kv_lora"),
                "w_kr": ("embed", "head_dim"),
                "w_ukv": ("kv_lora", "heads", "head_dim"),
                "wo": ("heads", "head_dim", "embed"),
                "q_norm": ("q_lora",), "kv_norm": ("kv_lora",)}

    @staticmethod
    def _rms(x, scale, eps=1e-6):
        v = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x.astype(jnp.float32) * jax.lax.rsqrt(v + eps)
                * scale.astype(jnp.float32)).astype(x.dtype)

    def _latents(self, params, x, positions):
        c, m = self.cfg, self.m
        cq = self._rms(x @ params["w_dq"].astype(x.dtype), params["q_norm"])
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"].astype(x.dtype))
        q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
        q_rope = apply_rope(q_rope, positions, c.rope_theta)
        ckv = self._rms(x @ params["w_dkv"].astype(x.dtype), params["kv_norm"])
        k_rope = (x @ params["w_kr"].astype(x.dtype))[:, :, None, :]  # 1 shared head
        k_rope = apply_rope(k_rope, positions, c.rope_theta)[:, :, 0, :]
        return q_nope, q_rope, ckv, k_rope

    def __call__(self, params, x, positions=None):
        c, m = self.cfg, self.m
        B, S, _ = x.shape
        if positions is None:
            positions = jnp.arange(S)
        q_nope, q_rope, ckv, k_rope = self._latents(params, x, positions)
        kv = jnp.einsum("bsr,rhk->bshk", ckv, params["w_ukv"].astype(x.dtype))
        k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
        if self.cfg.attention_impl == "stub":
            # dry-run stand-in (see GQAAttention.__call__)
            out = (v + q_nope.mean(-1, keepdims=True)
                   + q_rope.mean(-1, keepdims=True)
                   + k_nope.mean(-1, keepdims=True)
                   + k_rope.mean(-1, keepdims=True)[:, :, None, :])
        else:
            scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
            pos = jnp.broadcast_to(positions, (B, S)) \
                if positions.ndim == 1 else positions
            mask = causal_mask(pos, pos)[:, None]
            scores = (jnp.einsum("bshk,blhk->bhsl", q_nope, k_nope)
                      + jnp.einsum("bshk,blk->bhsl", q_rope, k_rope))
            scores = scores.astype(jnp.float32) * scale + mask
            w = jax.nn.softmax(scores, -1).astype(x.dtype)
            out = jnp.einsum("bhsl,blhk->bshk", w, v)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))

    # --- decode with compressed latent cache + weight absorption ---
    def cache_spec(self, batch, length, dtype=jnp.bfloat16):
        m = self.m
        return {
            "ckv": jax.ShapeDtypeStruct((batch, length, m.kv_lora_rank), dtype),
            "krope": jax.ShapeDtypeStruct((batch, length, m.qk_rope_head_dim), dtype),
        }

    def cache_axes(self):
        return {"ckv": ("batch", "kv_len", "kv_lora"),
                "krope": ("batch", "kv_len", "head_dim")}

    def init_cache(self, batch, length, dtype=jnp.bfloat16):
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.cache_spec(batch, length, dtype))

    def can_prefill(self):
        return True

    def prefill(self, params, x, cache, pos0, length=None):
        """Chunk prefill with the compressed latent cache: scatter the
        chunk's latents at absolute positions [pos0, pos0+length), then run
        the same weight-absorbed attention as ``decode`` for all S queries
        at once (identical math, batched over the chunk).  Tokens at
        in-chunk index >= ``length`` are grid padding — never written, and
        causally masked for every valid query."""
        c, m = self.cfg, self.m
        B, S, _ = x.shape
        if length is None:
            length = jnp.int32(S)
        positions = pos0 + jnp.arange(S)
        q_nope, q_rope, ckv, k_rope = self._latents(
            params, x, jnp.broadcast_to(positions, (B, S)))
        L = cache["ckv"].shape[1]
        i = jnp.arange(S)
        # index L is out of bounds -> the scatter drops padding writes
        idx = jnp.where((i < length) & (positions < L), positions, L)
        cc = cache["ckv"].at[:, idx].set(ckv.astype(cache["ckv"].dtype))
        cr = cache["krope"].at[:, idx].set(
            k_rope.astype(cache["krope"].dtype))
        w_uk = params["w_ukv"][:, :, :m.qk_nope_head_dim].astype(x.dtype)
        w_uv = params["w_ukv"][:, :, m.qk_nope_head_dim:].astype(x.dtype)
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)
        scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
        scores = (jnp.einsum("bshr,blr->bhsl", q_abs, cc.astype(x.dtype))
                  + jnp.einsum("bshk,blk->bhsl", q_rope,
                               cr.astype(x.dtype)))
        mask = jnp.where(jnp.arange(L)[None, :] <= positions[:, None],
                         0.0, NEG_INF)[None, None]       # (1, 1, S, L)
        w = jax.nn.softmax(scores.astype(jnp.float32) * scale + mask,
                           -1).astype(x.dtype)
        o_latent = jnp.einsum("bhsl,blr->bshr", w, cc.astype(x.dtype))
        out = jnp.einsum("bshr,rhk->bshk", o_latent, w_uv)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        return y, {"ckv": cc, "krope": cr}

    # --- paged decode over latent pages ---
    def paged_cache_spec(self, num_pages, page_size, dtype=jnp.bfloat16):
        m = self.m
        if self.cfg.kv_dtype == "int8":
            dtype = jnp.int8
        spec = {
            "ckv": jax.ShapeDtypeStruct(
                (num_pages, page_size, m.kv_lora_rank), dtype),
            "krope": jax.ShapeDtypeStruct(
                (num_pages, page_size, m.qk_rope_head_dim), dtype),
        }
        if self.cfg.kv_dtype == "int8":
            sc = jax.ShapeDtypeStruct((num_pages,), jnp.float32)
            spec.update(ckv_scale=sc, krope_scale=sc)
        return spec

    def paged_cache_axes(self):
        a = {"ckv": ("pages", "page", "kv_lora"),
             "krope": ("pages", "page", "head_dim")}
        if self.cfg.kv_dtype == "int8":
            a.update(ckv_scale=("pages",), krope_scale=("pages",))
        return a

    def ring_length(self, length):
        return length

    def decode_paged(self, params, x, cache, pos, bt, active, length):
        """Slot-batched weight-absorbed decode against latent page pools
        (see GQAAttention.decode_paged for the contract).  The compressed
        (ckv, k_rope) latents page exactly like K/V — this is what makes
        MLA's small cache pay off twice at serve time: fewer bytes per
        position AND pages allocated only for live positions."""
        c, m = self.cfg, self.m
        B = x.shape[0]
        q_nope, q_rope, ckv, k_rope = self._latents(params, x, pos[:, None])
        Pp, ps = cache["ckv"].shape[0], cache["ckv"].shape[1]
        wpage = jnp.where(active, bt[jnp.arange(B), pos // ps], Pp)
        q8 = self.cfg.kv_dtype == "int8"
        if q8:
            fresh = (pos % ps) == 0          # latent pages index globally
            cc, ccs = _paged_write_q8(cache["ckv"], cache["ckv_scale"],
                                      wpage, pos % ps, fresh, ckv[:, 0])
            cr, crs = _paged_write_q8(cache["krope"],
                                      cache["krope_scale"], wpage,
                                      pos % ps, fresh, k_rope[:, 0])
            new = {"ckv": cc, "krope": cr, "ckv_scale": ccs,
                   "krope_scale": crs}
        else:
            ccs = crs = None
            cc = cache["ckv"].at[wpage, pos % ps].set(
                ckv[:, 0].astype(cache["ckv"].dtype))
            cr = cache["krope"].at[wpage, pos % ps].set(
                k_rope[:, 0].astype(cache["krope"].dtype))
            new = {"ckv": cc, "krope": cr}
        w_uk = params["w_ukv"][:, :, :m.qk_nope_head_dim].astype(x.dtype)
        w_uv = params["w_ukv"][:, :, m.qk_nope_head_dim:].astype(x.dtype)
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)
        scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
        impl = self.cfg.paged_impl
        if impl != "gather":
            from repro.kernels.paged_attention.ops import paged_mla_attention
            o_latent = paged_mla_attention(
                q_abs[:, 0], q_rope[:, 0], cc, cr, bt, pos, length=length,
                scale=scale, backend=impl, ckv_scale=ccs,
                krope_scale=crs)[:, None]
            o_latent = o_latent.astype(x.dtype)
        else:
            if q8:
                ccd = gather_dequant(cc, ccs, bt, length, x.dtype)
                crd = gather_dequant(cr, crs, bt, length, x.dtype)
            else:
                ccd = gather_pages(cc, bt, length).astype(x.dtype)
                crd = gather_pages(cr, bt, length).astype(x.dtype)
            scores = (jnp.einsum("bshr,blr->bhsl", q_abs, ccd)
                      + jnp.einsum("bshk,blk->bhsl", q_rope, crd))
            _k_pos, valid = paged_positions(pos, length, None)
            mask = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
            w = jax.nn.softmax(scores.astype(jnp.float32) * scale + mask,
                               -1).astype(x.dtype)
            o_latent = jnp.einsum("bhsl,blr->bshr", w, ccd)
        out = jnp.einsum("bshr,rhk->bshk", o_latent, w_uv)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        return y, new

    # --- speculative k-token verify (read-only; commit is separate) ---
    def verify_paged(self, params, x, cache, pos, bt, active, length):
        """MLA k-token verify over latent pages — the same per-query
        overlaid-snapshot construction as GQAAttention.verify_paged, on
        the compressed (ckv, k_rope) latents (see that docstring for the
        bitwise contract)."""
        if self.cfg.kv_dtype == "int8":
            raise NotImplementedError(
                "speculative verify requires bf16 pools (int8 page "
                "rescale is not replayable per accepted prefix)")
        c, m = self.cfg, self.m
        B, K, _ = x.shape
        positions = pos[:, None] + jnp.arange(K)[None, :]
        q_nope, q_rope, ckv, k_rope = self._latents(params, x, positions)
        ccd0 = gather_pages(cache["ckv"], bt, length)
        crd0 = gather_pages(cache["krope"], bt, length)
        cc_b = ckv.astype(cache["ckv"].dtype)
        cr_b = k_rope.astype(cache["krope"].dtype)
        rows = jnp.arange(B)[:, None]
        w_uk = params["w_ukv"][:, :, :m.qk_nope_head_dim].astype(x.dtype)
        w_uv = params["w_ukv"][:, :, m.qk_nope_head_dim:].astype(x.dtype)
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)
        scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
        outs = []
        for i in range(K):
            ccd = ccd0.at[rows, positions[:, :i + 1]].set(
                cc_b[:, :i + 1]).astype(x.dtype)
            crd = crd0.at[rows, positions[:, :i + 1]].set(
                cr_b[:, :i + 1]).astype(x.dtype)
            scores = (jnp.einsum("bshr,blr->bhsl", q_abs[:, i:i + 1], ccd)
                      + jnp.einsum("bshk,blk->bhsl", q_rope[:, i:i + 1],
                                   crd))
            _k_pos, valid = paged_positions(pos + i, length, None)
            mask = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
            w = jax.nn.softmax(scores.astype(jnp.float32) * scale + mask,
                               -1).astype(x.dtype)
            outs.append(jnp.einsum("bhsl,blr->bshr", w, ccd))
        o_latent = jnp.concatenate(outs, axis=1)
        out = jnp.einsum("bshr,rhk->bshk", o_latent, w_uv)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        return y, {"ckv": cc_b, "krope": cr_b}

    def commit_paged(self, cache, block, pos, bt, n_commit, active,
                     length):
        """Commit the first ``n_commit[b]`` verified latents (see
        GQAAttention.commit_paged)."""
        B, K = block["ckv"].shape[:2]
        Pp, ps = cache["ckv"].shape[0], cache["ckv"].shape[1]
        j = jnp.arange(K)
        p = pos[:, None] + j[None, :]
        ok = (j[None, :] < n_commit[:, None]) & active[:, None]
        wpage = jnp.where(ok, bt[jnp.arange(B)[:, None], p // ps], Pp)
        return {"ckv": cache["ckv"].at[wpage, p % ps].set(block["ckv"]),
                "krope": cache["krope"].at[wpage, p % ps].set(
                    block["krope"])}

    def decode(self, params, x, cache, pos):
        c, m = self.cfg, self.m
        B = x.shape[0]
        q_nope, q_rope, ckv, k_rope = self._latents(
            params, x, jnp.full((B, 1), pos))
        cc = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), pos, axis=1)
        cr = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope.astype(cache["krope"].dtype), pos, axis=1)
        # absorb W^{UK} into the query:  q_abs = q_nope @ W^{UK}ᵀ  (per head)
        w_uk = params["w_ukv"][:, :, :m.qk_nope_head_dim].astype(x.dtype)
        w_uv = params["w_ukv"][:, :, m.qk_nope_head_dim:].astype(x.dtype)
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)
        L = cc.shape[1]
        scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
        scores = (jnp.einsum("bshr,blr->bhsl", q_abs, cc.astype(x.dtype))
                  + jnp.einsum("bshk,blk->bhsl", q_rope, cr.astype(x.dtype)))
        mask = jnp.where(jnp.arange(L) <= pos, 0.0, NEG_INF)[None, None, None]
        w = jax.nn.softmax(scores.astype(jnp.float32) * scale + mask,
                           -1).astype(x.dtype)
        o_latent = jnp.einsum("bhsl,blr->bshr", w, cc.astype(x.dtype))
        out = jnp.einsum("bshr,rhk->bshk", o_latent, w_uv)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        return y, {"ckv": cc, "krope": cr}
