"""Pallas TPU paged-attention decode kernels (block-table page gather).

Why this kernel exists: the serving engine's paged KV cache stores K/V in
a shared page pool ``(num_pages, page_size, ...)`` with per-request page
chains.  The XLA reference path materializes a dense ``(B, L, ...)`` view
of every request's chain each step — O(B·L·d) transient HBM traffic and
memory that defeats the point of paging.  This kernel reads K/V pages
directly through the block table instead: the page id is SCALAR-PREFETCHED
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index map DMAs exactly
the pages a request owns, one page per innermost grid step, with the
online-softmax state (m, l, acc) resident in VMEM.  Nothing dense is ever
materialized; HBM traffic is the live pages + q/out.

Grid (GQA): (B, n_pages) with the page axis innermost and sequential;
each step loads pool block ``block_tables[b, p]`` whole — every KV head of
the page, since the TPU only takes a one-head block when KV divides into
sublane tiles — and loops over the heads in-register.  Masking
reconstructs the absolute position of every in-page entry:

  * global:       k_pos = j            (in-cache index == position)
  * window ring:  k_pos = pos - ((pos - j) % length)   [length <= window]

Pages with no attendable entry (``p*page_size > pos``) are skipped via
``pl.when`` — that gate is also what keeps the online softmax sound (a
fully-masked tile would poison the running max).  MLA runs the same
schedule over latent pages with a rank-space score sum
(q_abs·ckvᵀ + q_rope·kropeᵀ) and a latent-space output (w·ckv).

The ``_q8`` variants read int8 pools with per-page float32 scales
(GQA: one per page per KV head; MLA: one per page — see
``paged_attention.quant``).  The scales ride in as a (1, 1, KV) or
(1, 1, 1) block through the same block-table index map as the page they
describe, and the dequant (codes * scale) happens in-register right
before the q·Kᵀ and P·V dots — HBM streams half the KV bytes and nothing
dequantized is ever written back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _page_mask(pos, p, ps, length, window):
    """(1, ps) additive mask for page ``p``'s entries vs query at ``pos``."""
    j = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    if window is None:
        k_pos = j
    else:
        k_pos = pos - ((pos - j) % length)
    ok = (j < length) & (k_pos >= 0) & (k_pos <= pos)
    if window is not None:
        ok &= (pos - k_pos) < window
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


def _gqa_kernel(pos_ref, bt_ref, q_ref, k_ref, v_ref, *refs, ps, n_pages,
                length, window, scale):
    """One (request, page) grid cell over every KV head of the page.

    The page block is (1, ps, KV, hd): its last two dims are whole axes,
    which the TPU accepts for any KV and hd, where a one-head block
    (1, ps, 1, hd) is refused unless KV divides into sublane tiles.
    ``refs`` is (out, acc, m, l), led by the (1, 1, KV) k/v scale blocks
    for int8 pools."""
    *scales, o_ref, acc, m_s, l_s = refs
    b, p = pl.program_id(0), pl.program_id(1)
    n_kv = k_ref.shape[2]

    @pl.when(p == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    pos = pos_ref[b]
    # skip pages with no attendable entry: the first page always has one
    # (ring: position pos % length aliases into the live prefix; global:
    # every j <= pos), so the gate only drops unwritten chain tails
    @pl.when((p * ps <= pos) & (p * ps < length))
    def _():
        mask = _page_mask(pos, p, ps, length, window)
        for kv in range(n_kv):
            q = q_ref[0, kv].astype(jnp.float32)        # (G, hd)
            k = k_ref[0, :, kv, :].astype(jnp.float32)  # (ps, hd)
            v = v_ref[0, :, kv, :].astype(jnp.float32)
            if scales:
                k = k * scales[0][0, :, kv:kv + 1]
                v = v * scales[1][0, :, kv:kv + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            _online_update(s * scale + mask, v, acc.at[kv], m_s.at[kv],
                           l_s.at[kv])

    @pl.when(p == n_pages - 1)
    def _():
        o_ref[0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


def _gqa_call(q, pool_k, pool_v, scales, block_tables, pos, *, length,
              window, interpret):
    """The GQA pallas_call; ``scales`` is () for bf16 pools or the
    (k_scale, v_scale) pair, each (P, KV) float32, for int8 pools."""
    B, H, hd = q.shape
    P, ps, KV, _ = pool_k.shape
    G = H // KV
    n_pages = -(-length // ps)
    bt = block_tables[:, :n_pages].astype(jnp.int32)
    qg = q.reshape(B, KV, G, hd)
    kern = functools.partial(_gqa_kernel, ps=ps, n_pages=n_pages,
                             length=length, window=window,
                             scale=1.0 / (hd ** 0.5))
    page_map = lambda b, p, pos_ref, bt_ref: (bt_ref[b, p], 0, 0, 0)
    sc_map = lambda b, p, pos_ref, bt_ref: (bt_ref[b, p], 0, 0)
    q_map = lambda b, p, pos_ref, bt_ref: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[pl.BlockSpec((1, KV, G, hd), q_map),
                  pl.BlockSpec((1, ps, KV, hd), page_map),
                  pl.BlockSpec((1, ps, KV, hd), page_map)]
                 + [pl.BlockSpec((1, 1, KV), sc_map)] * len(scales),
        out_specs=pl.BlockSpec((1, KV, G, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_gqa_decode_q8" if scales else "paged_gqa_decode",
    )(pos.astype(jnp.int32), bt, qg, pool_k, pool_v,
      *(sc.astype(jnp.float32).reshape(P, 1, KV) for sc in scales))
    return out.reshape(B, H, hd)


@functools.partial(jax.jit,
                   static_argnames=("length", "window", "interpret"))
def paged_gqa_fwd(q, pool_k, pool_v, block_tables, pos, *, length,
                  window=None, interpret=True):
    """q: (B, H, hd); pool_k/v: (P, page, KV, hd); block_tables:
    (B, >=ceil(length/page)) int32; pos: (B,) int32 -> (B, H, hd)."""
    return _gqa_call(q, pool_k, pool_v, (), block_tables, pos,
                     length=length, window=window, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("length", "window", "interpret"))
def paged_gqa_fwd_q8(q, pool_k, pool_v, k_scale, v_scale, block_tables,
                     pos, *, length, window=None, interpret=True):
    """Int8 pools + per-(page, kv-head) float32 scales.

    q: (B, H, hd); pool_k/v: (P, page, KV, hd) int8; k/v_scale: (P, KV)
    float32 -> (B, H, hd) in q.dtype."""
    return _gqa_call(q, pool_k, pool_v, (k_scale, v_scale), block_tables,
                     pos, length=length, window=window, interpret=interpret)


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
        s = s * scale + _page_mask(pos, p, ps, length, None)
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
        s = s * scale + _page_mask(pos, p, ps, length, None)
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
