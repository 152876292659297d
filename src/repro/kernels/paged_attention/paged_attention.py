"""Pallas TPU paged-attention decode kernels (block-table page gather).

Why this kernel exists: the serving engine's paged KV cache stores K/V in
a shared page pool ``(num_pages, page_size, ...)`` with per-request page
chains.  The XLA reference path materializes a dense ``(B, L, ...)`` view
of every request's chain each step — O(B·L·d) transient HBM traffic and
memory that defeats the point of paging.  These kernels read K/V pages
directly through the block table instead: the page ids are
SCALAR-PREFETCHED (``pltpu.PrefetchScalarGridSpec``) so BlockSpec index
maps DMA exactly the pages a request owns, with the online-softmax state
(m, l, acc) resident in VMEM.  Nothing dense is ever materialized.

GQA: the block walk.  The grid is (B, n_blocks), the block axis innermost
and sequential: grid cell (b, blk) takes block ``blk`` of slot b's
chain, ``ppb`` pages (``ppb * page_size`` tokens, every KV head of each
page).  Each page of a block is an input of its own, a
(1, page, KV*hd) BlockSpec over the pool viewed as (P, page, KV*hd),
whose index map reads the page id from a scalar-prefetched walk table
(``_walk_pages``); the pipeline copies the next cell's pages while the
current block computes.  The walk ends at each slot's live length

  * global:        min(pos + 1, length) tokens (in-cache index j <= pos)
  * wrapped ring:  length tokens (every ring entry is live once the
                   window has wrapped; before that, pos + 1)
  * inactive slot: nothing (``active`` false, where it is passed)

Past it, the walk table repeats the last live page (within the last live
block) or the last live block (for a dead block, and for a slot with
nothing to walk); the pipeline does not copy an input whose page id is
unchanged from the grid step before, so no page past a live length is
ever copied, and a dead cell does no math.  The score mask ``j < live``
keeps the repeated rows (and the unwritten tail of the last live page)
out of the softmax; every token below the live length is attendable — a
ring entry below it holds a position within the window — so that is the
whole mask.

The math runs over all KV heads at once: q comes in block-diagonal,
``(H, KV*hd)`` with head h = (kv, g) nonzero only on group kv's columns,
so one ``q·Kᵀ`` dot over the block's ``(T, KV*hd)`` rows scores every
head, and one ``P·V`` dot accumulates ``(H, KV*hd)`` of which the
wrapper keeps each head's own group.  Scores, probabilities and the
online-softmax state are float32; bf16 K enters the MXU as bf16 with
float32 accumulation (products of bf16 values are exact in float32).

``ppb`` follows the shapes (``pages_per_block``): about
``TOKENS_PER_BLOCK`` tokens, fewer where the double-buffered K and V
pages would pass ``VMEM_PAGE_BUDGET``, never more than a chain's pages.
The engine's walk counter (``blocks_walked``) calls the same function.

MLA runs one page per innermost grid step of a (B, n_pages) grid with a
rank-space score sum (q_abs·ckvᵀ + q_rope·kropeᵀ) and a latent-space
output (w·ckv); pages with no attendable entry (``p*page_size > pos``)
are skipped via ``pl.when``, which also keeps its online softmax sound.

The ``_q8`` variants read int8 pools with per-page float32 scales
(GQA: one per page per KV head; MLA: one per page — see
``paged_attention.quant``).  The scales ride in as a (1, 1, KV) or
(1, 1, 1) block through the same page id as the page they describe, and
the dequant (codes * scale) happens in-register right before the dots —
HBM streams half the KV bytes and nothing dequantized is ever written
back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
# the GQA walk's block, in tokens, and the VMEM its page buffers may take
# (K and V, both halves of the double buffer)
TOKENS_PER_BLOCK = 512
VMEM_PAGE_BUDGET = 4 << 20


def _round_up(x, m):
    return -(-x // m) * m


def pages_per_block(page_size, n_kv, head_dim, dtype, n_pages):
    """Pages one step of the GQA decode walk copies and computes.

    About ``TOKENS_PER_BLOCK`` tokens, fewer where the double-buffered K
    and V page buffers would pass ``VMEM_PAGE_BUDGET`` (a page takes
    ``page_size`` rows of ``n_kv * head_dim`` lanes in VMEM, padded to
    whole (sublane, 128-lane) tiles of ``dtype``), and never more than
    the ``n_pages`` of a slot's chain."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * 4 // itemsize
    page_bytes = (_round_up(page_size, sublanes)
                  * _round_up(n_kv * head_dim, 128) * itemsize)
    ppb = min(TOKENS_PER_BLOCK // page_size,
              VMEM_PAGE_BUDGET // (4 * page_bytes))
    return max(1, min(ppb, n_pages))


def live_tokens(pos, length, active=None):
    """Tokens of each slot's chain the GQA walk covers (numpy or jnp):
    positions 0..pos of a global cache, every entry of a wrapped ring,
    none for an inactive slot."""
    xp = jnp if isinstance(pos, jax.Array) else np
    n = xp.minimum(pos + 1, length)
    return n if active is None else xp.where(active, n, 0)


def blocks_walked(pos, active, *, length, page_size, ppb):
    """(blocks the GQA walk takes, blocks a walk of every page of every
    slot would take) for one kernel call, on the host: pos/active are the
    (B,) slot vectors of the decode step."""
    tokens = ppb * page_size
    n = live_tokens(np.asarray(pos), length, np.asarray(active))
    n_blocks = -(-(-(-length // page_size)) // ppb)
    return int((-(-n // tokens)).sum()), len(n) * n_blocks


def _page_mask(pos, p, ps, length):
    """(1, ps) additive mask for page ``p``'s entries vs query at ``pos``."""
    j = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    ok = (j < length) & (j <= pos)
    return jnp.where(ok, 0.0, NEG_INF)


def _online_update(s, v, acc, m_s, l_s):
    """One online-softmax accumulation step.  s: (R, ps) fp32 scores,
    v: (ps, D) fp32 values; scratch acc (R, D), m_s/l_s (R, 1)."""
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + p.sum(-1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new


def _lane_scales(sc, head_dim, width):
    """(..., KV) per-(page, KV-head) scales -> (..., width): each head's
    scale over its own ``head_dim`` lanes (lanes past KV*hd read 0)."""
    shape = sc.shape[:-1] + (width,)
    head = jax.lax.broadcasted_iota(jnp.int32, shape, sc.ndim - 1) // head_dim
    out = jnp.zeros(shape, jnp.float32)
    for kv in range(sc.shape[-1]):
        out = jnp.where(head == kv, sc[..., kv:kv + 1], out)
    return out


def _gqa_kernel(live_ref, pages_ref, q_ref, *refs, ps, ppb, n_blocks,
                head_dim, scale, q8):
    """Grid cell (b, blk): block ``blk`` of slot b's walk.

    ``refs``: the block's page inputs — ``ppb`` K pages, ``ppb`` V pages
    (1, page, KV*hd), and for int8 pools ``ppb`` K and V scale rows
    (1, 1, KV) — then the output (1, H, KV*hd) and the (acc, m, l)
    scratch.  ``live_ref`` (B,) holds each slot's live tokens;
    ``pages_ref`` is the walk table the index maps read."""
    n_in = ppb * (4 if q8 else 2)
    pages, (o_ref, acc, m_s, l_s) = refs[:n_in], refs[n_in:]
    b, blk = pl.program_id(0), pl.program_id(1)
    live = live_ref[b]
    tokens = ppb * ps

    @pl.when(blk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(blk * tokens < live)
    def _():
        kp, vp = pages[:ppb], pages[ppb:2 * ppb]
        if q8:
            ksp, vsp = pages[2 * ppb:3 * ppb], pages[3 * ppb:]
            width = kp[0].shape[-1]
            k = jnp.concatenate(
                [r[0].astype(jnp.float32)
                 * _lane_scales(sr[0], head_dim, width)
                 for r, sr in zip(kp, ksp)], 0)
            v = jnp.concatenate(
                [r[0].astype(jnp.float32)
                 * _lane_scales(sr[0], head_dim, width)
                 for r, sr in zip(vp, vsp)], 0)
        else:
            k = jnp.concatenate([r[0] for r in kp], 0)
            v = jnp.concatenate([r[0] for r in vp], 0).astype(jnp.float32)
        q = q_ref[0]
        ct = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(q.astype(ct), k.astype(ct),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        j = blk * tokens + jax.lax.broadcasted_iota(jnp.int32, (1, tokens),
                                                     1)
        _online_update(jnp.where(j < live, s * scale, NEG_INF), v, acc,
                       m_s, l_s)

    @pl.when(blk == n_blocks - 1)
    def _():
        o_ref[0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


def _walk_pages(live, block_tables, *, ps, ppb, n_blocks, num_pages):
    """(B * n_blocks * ppb,) page ids: the page the i-th page input of
    grid cell (b, blk) copies.  Live pages as the chain has them; a page
    slot past the live length repeats the slot's last live page, a dead
    block the slot's last live block, and a slot with nothing to walk the
    last live block of the slot before it (or the first live block after
    it) — an input whose page id is unchanged from the grid step before
    is not copied again, so nothing past a live length is ever copied."""
    B = live.shape[0]
    is_live = live > 0
    ar = jnp.arange(B, dtype=jnp.int32)
    prv = jax.lax.cummax(jnp.where(is_live, ar, -1))
    nxt = jax.lax.cummin(jnp.where(is_live, ar, B), reverse=True)
    src = jnp.where(is_live, ar,
                    jnp.where(prv >= 0, prv, jnp.where(nxt < B, nxt, 0)))
    last_page = jnp.maximum(-(-live[src] // ps) - 1, 0)
    last_blk = last_page // ppb
    first = jnp.where(is_live | (prv < 0), 0, last_blk)
    blk = jnp.clip(jnp.arange(n_blocks)[None, :], first[:, None],
                   jnp.where(is_live, last_blk, first)[:, None])
    p = jnp.minimum(blk[:, :, None] * ppb + jnp.arange(ppb),
                    last_page[:, None, None])
    pages = block_tables[src[:, None, None], p]
    return jnp.clip(pages, 0, num_pages - 1).reshape(-1)


def _gqa_call(q, pool_k, pool_v, scales, block_tables, pos, active, *,
              length, interpret):
    """The GQA pallas_call; ``scales`` is () for bf16 pools or the
    (k_scale, v_scale) pair, each (P, KV) float32, for int8 pools."""
    B, H, hd = q.shape
    P, ps, KV, _ = pool_k.shape
    G, C = H // KV, KV * hd
    n_pages = -(-length // ps)
    ppb = pages_per_block(ps, KV, hd, pool_k.dtype, n_pages)
    n_blocks = -(-n_pages // ppb)
    q8 = bool(scales)
    live = live_tokens(pos.astype(jnp.int32), length, active)
    walk = _walk_pages(live, block_tables[:, :n_pages].astype(jnp.int32),
                       ps=ps, ppb=ppb, n_blocks=n_blocks, num_pages=P)
    # block-diagonal q: head (kv, g) keeps its values on group kv's
    # columns and zeros elsewhere, so one dot scores every head
    eye = jnp.eye(KV, dtype=q.dtype)
    q_bd = (q.reshape(B, KV, G, 1, hd) * eye[None, :, None, :, None]
            ).reshape(B, H, C)

    def page_map(i):
        return lambda b, blk, live_r, walk_r: (
            walk_r[(b * n_blocks + blk) * ppb + i], 0, 0)

    pools = [pool_k.reshape(P, ps, C), pool_v.reshape(P, ps, C)]
    pools += [sc.astype(jnp.float32).reshape(P, 1, KV) for sc in scales]
    operands, specs = [], []
    for x in pools:
        for i in range(ppb):
            operands.append(x)
            specs.append(pl.BlockSpec((1,) + x.shape[1:], page_map(i)))
    row_map = lambda b, blk, *_: (b, 0, 0)
    kern = functools.partial(_gqa_kernel, ps=ps, ppb=ppb,
                             n_blocks=n_blocks, head_dim=hd,
                             scale=1.0 / (hd ** 0.5), q8=q8)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blocks),
        in_specs=[pl.BlockSpec((1, H, C), row_map)] + specs,
        out_specs=pl.BlockSpec((1, H, C), row_map),
        scratch_shapes=[pltpu.VMEM((H, C), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_gqa_decode_q8" if q8 else "paged_gqa_decode",
    )(live, walk, q_bd, *operands)
    # each head's own group of columns
    out = out.reshape(B, KV, G, KV, hd)
    diag = jnp.arange(KV)
    return jnp.moveaxis(out[:, diag, :, diag], 0, 1).reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("length", "interpret"))
def paged_gqa_fwd(q, pool_k, pool_v, block_tables, pos, active=None, *,
                  length, interpret=True):
    """q: (B, H, hd); pool_k/v: (P, page, KV, hd); block_tables:
    (B, >=ceil(length/page)) int32; pos: (B,) int32; active: (B,) bool or
    None (every slot live) -> (B, H, hd), zeros for inactive slots.
    ``length`` is the cache length, a sliding window's ring length for a
    ring, which needs nothing more: the live tokens of a ring are all
    attendable (module docstring)."""
    return _gqa_call(q, pool_k, pool_v, (), block_tables, pos, active,
                     length=length, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("length", "interpret"))
def paged_gqa_fwd_q8(q, pool_k, pool_v, k_scale, v_scale, block_tables,
                     pos, active=None, *, length, interpret=True):
    """Int8 pools + per-(page, kv-head) float32 scales.

    q: (B, H, hd); pool_k/v: (P, page, KV, hd) int8; k/v_scale: (P, KV)
    float32 -> (B, H, hd) in q.dtype."""
    return _gqa_call(q, pool_k, pool_v, (k_scale, v_scale), block_tables,
                     pos, active, length=length, interpret=interpret)


def _mla_kernel(pos_ref, bt_ref, qa_ref, qr_ref, ckv_ref, kr_ref, o_ref,
                acc, m_s, l_s, *, ps, n_pages, length, scale):
    b, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    pos = pos_ref[b]

    @pl.when((p * ps <= pos) & (p * ps < length))
    def _():
        qa = qa_ref[0].astype(jnp.float32)    # (H, r)
        qr = qr_ref[0].astype(jnp.float32)    # (H, dr)
        ckv = ckv_ref[0].astype(jnp.float32)  # (ps, r)
        kr = kr_ref[0].astype(jnp.float32)    # (ps, dr)
        s = (jax.lax.dot_general(qa, ckv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32))
        s = s * scale + _page_mask(pos, p, ps, length)
        _online_update(s, ckv, acc, m_s, l_s)

    @pl.when(p == n_pages - 1)
    def _():
        o_ref[0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("length", "scale", "interpret"))
def paged_mla_fwd(q_abs, q_rope, pool_ckv, pool_krope, block_tables, pos,
                  *, length, scale, interpret=True):
    """q_abs: (B, H, r); q_rope: (B, H, dr); pool_ckv: (P, page, r);
    pool_krope: (P, page, dr) -> latent output (B, H, r)."""
    B, H, r = q_abs.shape
    _P, ps, _ = pool_ckv.shape
    dr = q_rope.shape[-1]
    n_pages = -(-length // ps)
    bt = block_tables[:, :n_pages].astype(jnp.int32)
    kern = functools.partial(_mla_kernel, ps=ps, n_pages=n_pages,
                             length=length, scale=scale)
    page_map = lambda b, p, pos_ref, bt_ref: (bt_ref[b, p], 0, 0)
    q_map = lambda b, p, pos_ref, bt_ref: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, r), q_map),
            pl.BlockSpec((1, H, dr), q_map),
            pl.BlockSpec((1, ps, r), page_map),
            pl.BlockSpec((1, ps, dr), page_map),
        ],
        out_specs=pl.BlockSpec((1, H, r), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, r), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_mla_decode",
    )(pos.astype(jnp.int32), bt, q_abs, q_rope, pool_ckv, pool_krope)


def _mla_kernel_q8(pos_ref, bt_ref, qa_ref, qr_ref, ckv_ref, kr_ref,
                   cs_ref, rs_ref, o_ref, acc, m_s, l_s, *, ps, n_pages,
                   length, scale):
    b, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    pos = pos_ref[b]

    @pl.when((p * ps <= pos) & (p * ps < length))
    def _():
        qa = qa_ref[0].astype(jnp.float32)                     # (H, r)
        qr = qr_ref[0].astype(jnp.float32)                     # (H, dr)
        ckv = ckv_ref[0].astype(jnp.float32) * cs_ref[0]       # (ps, r)
        kr = kr_ref[0].astype(jnp.float32) * rs_ref[0]         # (ps, dr)
        s = (jax.lax.dot_general(qa, ckv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32))
        s = s * scale + _page_mask(pos, p, ps, length)
        _online_update(s, ckv, acc, m_s, l_s)

    @pl.when(p == n_pages - 1)
    def _():
        o_ref[0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("length", "scale", "interpret"))
def paged_mla_fwd_q8(q_abs, q_rope, pool_ckv, pool_krope, ckv_scale,
                     krope_scale, block_tables, pos, *, length, scale,
                     interpret=True):
    """Int8 latent pools + per-page float32 scales.

    pool_ckv: (P, page, r) int8; pool_krope: (P, page, dr) int8;
    ckv/krope_scale: (P,) float32 -> latent output (B, H, r)."""
    B, H, r = q_abs.shape
    _P, ps, _ = pool_ckv.shape
    dr = q_rope.shape[-1]
    n_pages = -(-length // ps)
    bt = block_tables[:, :n_pages].astype(jnp.int32)
    kern = functools.partial(_mla_kernel_q8, ps=ps, n_pages=n_pages,
                             length=length, scale=scale)
    page_map = lambda b, p, pos_ref, bt_ref: (bt_ref[b, p], 0, 0)
    sc_map = lambda b, p, pos_ref, bt_ref: (bt_ref[b, p], 0, 0)
    q_map = lambda b, p, pos_ref, bt_ref: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, r), q_map),
            pl.BlockSpec((1, H, dr), q_map),
            pl.BlockSpec((1, ps, r), page_map),
            pl.BlockSpec((1, ps, dr), page_map),
            # scales as (P, 1, 1): a (1, 1) block of (P, 1) is refused
            pl.BlockSpec((1, 1, 1), sc_map),
            pl.BlockSpec((1, 1, 1), sc_map),
        ],
        out_specs=pl.BlockSpec((1, H, r), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, r), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_mla_decode_q8",
    )(pos.astype(jnp.int32), bt, q_abs, q_rope, pool_ckv, pool_krope,
      ckv_scale.astype(jnp.float32).reshape(-1, 1, 1),
      krope_scale.astype(jnp.float32).reshape(-1, 1, 1))
