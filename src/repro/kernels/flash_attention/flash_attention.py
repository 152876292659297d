"""Pallas TPU flash attention (FlashAttention-2 schedule), fwd + bwd.

Why this kernel exists (DESIGN.md §Perf, hillclimb cell A): the naive
attention path materializes the (S, S) score matrix in HBM — at train_4k it
is ~8 GB/layer/device for even a small model and dominates the memory
roofline term by >10×.  The flash schedule keeps score tiles resident in
VMEM (online softmax), so HBM traffic is O(S·d) instead of O(S²).

Forward: grid (B, H, S/bq, S/bk) with the KV axis innermost and sequential;
running (m, l, acc) live in VMEM scratch; out + logsumexp written at the
last KV block.  Causal and sliding-window masks are applied in-kernel; with
causality, KV blocks entirely above the diagonal are skipped via pl.when.

Backward (FlashAttention-2 style, two passes sharing one kernel body each):
  * dKdV kernel: grid (B, H, S/bk, S/bq) — for a fixed KV tile, iterate Q
    tiles, recompute p = exp(qkᵀ·scale − L), accumulate dv += pᵀ·do and
    dk += dsᵀ·q with ds = p ∘ (do·vᵀ − D), D = rowsum(do ∘ o).
  * dQ kernel: grid (B, H, S/bq, S/bk) — for a fixed Q tile, iterate KV
    tiles, accumulate dq += ds·k.
Residuals saved from fwd: out and L = m + log(l) (one fp32 per row).

GQA is handled by index maps (kv_head = q_head // group) — K/V are never
expanded in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _mask(qi, ki, bq, bk, *, causal, window):
    """Additive mask for a (bq, bk) tile at block coords (qi, ki)."""
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= qpos >= kpos
    if window is not None:
        ok &= (qpos - kpos) < window
    return jnp.where(ok, 0.0, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                bq, bk, n_k, scale, causal, window):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # skip fully-masked KV tiles (strictly above the diagonal)
    run = True
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, (qi * bq) - (ki * bk + bk - 1) < window) \
            if not isinstance(run, bool) else \
            ((qi * bq) - (ki * bk + bk - 1) < window)

    @pl.when(run)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + _mask(qi, ki, bq, bk, causal=causal, window=window)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + p.sum(-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ki == n_k - 1)
    def _():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_s[...] + jnp.log(l))[:, 0].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "window",
                                             "group", "interpret"))
def flash_fwd(q, k, v, *, bq=128, bk=128, causal=True, window=None,
              group=1, interpret=True):
    """q: (B, H, S, D); k, v: (B, H//group, S, D) -> (out, lse)."""
    B, H, S, D = q.shape
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_q, n_k = S // bq, S // bk
    grid = (B, H, n_q, n_k)
    kern = functools.partial(_fwd_kernel, bq=bq, bk=bk, n_k=n_k,
                             scale=1.0 / math.sqrt(D), causal=causal,
                             window=window)
    kv_map = lambda b, h, qi, ki: (b, h // group, ki, 0)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                bq, bk, n_q, scale, causal, window):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)
    if window is not None:
        cond = (qi * bq) - (ki * bk + bk - 1) < window
        run = jnp.logical_and(run, cond) if not isinstance(run, bool) else cond

    @pl.when(run)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)     # (bq, D)
        lse = lse_ref[0, 0].astype(jnp.float32)   # (bq,)
        delta = delta_ref[0, 0].astype(jnp.float32)  # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + _mask(qi, ki, bq, bk, causal=causal, window=window)
        p = jnp.exp(s - lse[:, None])             # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, bq, bk, n_k, scale, causal, window):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)
    if window is not None:
        cond = (qi * bq) - (ki * bk + bk - 1) < window
        run = jnp.logical_and(run, cond) if not isinstance(run, bool) else cond

    @pl.when(run)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)
        delta = delta_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + _mask(qi, ki, bq, bk, causal=causal, window=window)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "window",
                                             "group", "interpret"))
def flash_bwd(q, k, v, o, lse, do, *, bq=128, bk=128, causal=True,
              window=None, group=1, interpret=True):
    """Returns (dq, dk, dv); dk/dv are per-(q-)head (caller reduces groups)."""
    B, H, S, D = q.shape
    n_q, n_k = S // bq, S // bk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    scale = 1.0 / math.sqrt(D)
    kv_map4 = lambda b, h, x, y: (b, h // group, y, 0)  # noqa: E731

    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, n_q=n_q, scale=scale,
                          causal=causal, window=window),
        grid=(B, H, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, ki, qi: (b, h, qi)),
            pl.BlockSpec((1, 1, bq), lambda b, h, ki, qi: (b, h, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, n_k=n_k, scale=scale,
                          causal=causal, window=window),
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_map4),
            pl.BlockSpec((1, 1, bk, D), kv_map4),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, h, qi)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)
    return dq, dkv[0], dkv[1]
