"""Pallas TPU kernel: fused Mamba-1 selective scan, fwd + bwd.

Hillclimb cell C (falcon-mamba train_4k, EXPERIMENTS.md §Perf): the XLA
path materializes a = exp(dt⊗A), b = (dt·x)⊗B and the state trajectory h —
three (B, T, d_inner, n) tensors ≈ 34 TB/device/step at train_4k.  This
kernel computes the discretization AND the y = Σ_n h∘C contraction inside
VMEM; HBM sees only the O(B·T·d_inner) inputs/outputs — the TPU-native
version of Mamba's fused CUDA scan (hardware adaptation per DESIGN.md §3).

Forward: grid (B, di/dblk, T/tblk), time chunks sequential, carry h
(dblk, n) in VMEM scratch; emits y and the chunk-entry states
(B, n_chunks, di, n) as bwd residuals.

Backward: same grid with the time axis *reversed* by index maps; per chunk
it (1) recomputes h locally from the saved chunk-entry state, storing the
trajectory in a (tblk, dblk, n) VMEM scratch, then (2) runs the reverse
recurrence λ_t = dh_t ∘ a_t with all parameter/input gradients computed on
the fly.  dA/dB/dC partial sums are emitted per (batch, di-block) and
reduced in ops.py (avoids cross-grid-cell write races).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, y_ref, hout_ref,
                h_s, *, tblk):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_s[...] = jnp.zeros_like(h_s)

    # save chunk-entry state (bwd residual)
    hout_ref[0, 0] = h_s[...].astype(hout_ref.dtype)

    A = a_ref[...].astype(jnp.float32)              # (dblk, n)
    dt = dt_ref[0].astype(jnp.float32)              # (tblk, dblk)
    x = x_ref[0].astype(jnp.float32)
    Bm = b_ref[0].astype(jnp.float32)               # (tblk, n)
    Cm = c_ref[0].astype(jnp.float32)

    def step(t, h):
        a_t = jnp.exp(dt[t][:, None] * A)           # (dblk, n)
        h = a_t * h + (dt[t] * x[t])[:, None] * Bm[t][None, :]
        y_ref[0, t, :] = (h * Cm[t][None, :]).sum(-1).astype(y_ref.dtype)
        return h

    h_s[...] = jax.lax.fori_loop(0, tblk, step, h_s[...])


def _bwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, dy_ref,
                ddt_ref, dx_ref, db_ref, dc_ref, da_ref,
                lam_s, htraj_s, da_s, *, tblk, n_t):
    ti = pl.program_id(2)   # reversed by index maps: ti=0 is the LAST chunk

    @pl.when(ti == 0)
    def _():
        lam_s[...] = jnp.zeros_like(lam_s)
        da_s[...] = jnp.zeros_like(da_s)

    A = a_ref[...].astype(jnp.float32)              # (dblk, n)
    dt = dt_ref[0].astype(jnp.float32)              # (tblk, dblk)
    x = x_ref[0].astype(jnp.float32)
    Bm = b_ref[0].astype(jnp.float32)               # (tblk, n)
    Cm = c_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)              # (tblk, dblk)
    h_entry = h0_ref[0, 0].astype(jnp.float32)      # (dblk, n)

    # (1) local forward recompute, storing the in-chunk trajectory
    def fstep(t, h):
        a_t = jnp.exp(dt[t][:, None] * A)
        h = a_t * h + (dt[t] * x[t])[:, None] * Bm[t][None, :]
        htraj_s[t] = h
        return h

    jax.lax.fori_loop(0, tblk, fstep, h_entry)

    # (2) reverse pass with λ carry
    def bstep(i, lam):
        t = tblk - 1 - i
        a_t = jnp.exp(dt[t][:, None] * A)
        h_prev = jnp.where(t == 0, h_entry, htraj_s[jnp.maximum(t - 1, 0)])
        h_t = htraj_s[t]
        dh = dy[t][:, None] * Cm[t][None, :] + lam      # (dblk, n)
        dc_ref[0, t, :] = (dy[t][:, None] * h_t).sum(0).astype(dc_ref.dtype)
        da_t = dh * h_prev
        ddt_ref[0, t, :] = ((da_t * A * a_t).sum(-1)
                            + (dh * Bm[t][None, :]).sum(-1) * x[t]
                            ).astype(ddt_ref.dtype)
        dx_ref[0, t, :] = (dt[t] * (dh * Bm[t][None, :]).sum(-1)
                           ).astype(dx_ref.dtype)
        db_ref[0, t, :] = (dh * (dt[t] * x[t])[:, None]).sum(0
                                                             ).astype(db_ref.dtype)
        da_s[...] += da_t * dt[t][:, None] * a_t
        return dh * a_t

    lam_s[...] = jax.lax.fori_loop(0, tblk, bstep, lam_s[...])

    @pl.when(ti == n_t - 1)
    def _():
        da_ref[0] = da_s[...].astype(da_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tblk", "dblk", "interpret"))
def fused_ssm_fwd(dt, x, Bm, Cm, A, *, tblk=64, dblk=128, interpret=True):
    """Returns (y, h_entries): y (B,T,di); h_entries (B, T/tblk, di, n)."""
    B, T, di = x.shape
    n = A.shape[1]
    assert T % tblk == 0 and di % dblk == 0, (T, tblk, di, dblk)
    n_t = T // tblk
    grid = (B, di // dblk, n_t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tblk=tblk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tblk, dblk), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, tblk, dblk), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, tblk, n), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, tblk, n), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((dblk, n), lambda b, d, t: (d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tblk, dblk), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, 1, dblk, n), lambda b, d, t: (b, t, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, di), x.dtype),
            jax.ShapeDtypeStruct((B, n_t, di, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dblk, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_ssm_fwd",
    )(dt, x, Bm, Cm, A)


@functools.partial(jax.jit, static_argnames=("tblk", "dblk", "interpret"))
def fused_ssm_bwd(dt, x, Bm, Cm, A, h_entries, dy, *, tblk=64, dblk=128,
                  interpret=True):
    """Returns (ddt, dx, dB_partial, dC_partial, dA_partial).

    dB/dC partials have an extra leading di-block axis; dA partials an
    extra batch axis — ops.py reduces them."""
    B, T, di = x.shape
    n = A.shape[1]
    n_t = T // tblk
    n_d = di // dblk
    rev = lambda b, d, t: (b, n_t - 1 - t, d)       # reversed time chunks
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tblk=tblk, n_t=n_t),
        grid=(B, n_d, n_t),
        in_specs=[
            pl.BlockSpec((1, tblk, dblk), rev),
            pl.BlockSpec((1, tblk, dblk), rev),
            pl.BlockSpec((1, tblk, n), lambda b, d, t: (b, n_t - 1 - t, 0)),
            pl.BlockSpec((1, tblk, n), lambda b, d, t: (b, n_t - 1 - t, 0)),
            pl.BlockSpec((dblk, n), lambda b, d, t: (d, 0)),
            pl.BlockSpec((1, 1, dblk, n),
                         lambda b, d, t: (b, n_t - 1 - t, d, 0)),
            pl.BlockSpec((1, tblk, dblk), rev),
        ],
        out_specs=[
            pl.BlockSpec((1, tblk, dblk), rev),
            pl.BlockSpec((1, tblk, dblk), rev),
            pl.BlockSpec((1, tblk, n),
                         lambda b, d, t: (b * n_d + d, n_t - 1 - t, 0)),
            pl.BlockSpec((1, tblk, n),
                         lambda b, d, t: (b * n_d + d, n_t - 1 - t, 0)),
            pl.BlockSpec((1, dblk, n), lambda b, d, t: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, di), jnp.float32),
            jax.ShapeDtypeStruct((B, T, di), jnp.float32),
            jax.ShapeDtypeStruct((B * n_d, T, n), jnp.float32),
            jax.ShapeDtypeStruct((B * n_d, T, n), jnp.float32),
            jax.ShapeDtypeStruct((B, di, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dblk, n), jnp.float32),          # λ carry
            pltpu.VMEM((tblk, dblk, n), jnp.float32),    # local trajectory
            pltpu.VMEM((dblk, n), jnp.float32),          # dA accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_ssm_bwd",
    )(dt, x, Bm, Cm, A, h_entries, dy)
