"""Pallas TPU kernel: the MINIMALIST core as ONE fused inference kernel.

This is the digital twin of the paper's switched-capacitor core (§3) at
kernel granularity — one HBM pass per time chunk performs what one clock
phase of the circuit performs:

  MXU:  the two interleaved IMC matrix-vector products (h̃ and z columns,
        2 b codes dequantized in VMEM — weights stay int8 in HBM, 4× less
        weight traffic, exactly the circuit's "weights never move" story)
  VPU:  the SAR-ADC transfer  z = floor(63·hard_sigmoid(·))/63
        (quant.quantize_unit_6b's grid — bit-exact with the circuit),
        the capacitor-swap state update  h ← z·h̃ + (1−z)·h  with the
        state resident in VMEM across the whole sequence (the kernel
        analogue of "no buffering, charge stays on the capacitors"),
        and the comparator  y = Θ(h).

Grid (B, N/nblk, T/tblk), time sequential; carry h in VMEM scratch.
Inputs per cell: x chunk (tblk, K) binary; codes (K, nblk) int8 ×2;
biases (nblk,) ×2.  Outputs: y (binary) and h (analog trace) chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


GATE_UNITS = 63.0


def _kernel(x_ref, ch_ref, cz_ref, bh_ref, bz_ref, h0_ref, y_ref, h_ref,
            h_s, pre_h_s, zq_s, *, tblk, scale):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_s[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)                       # (tblk, K)
    wh = (ch_ref[...].astype(jnp.float32) - 1.5) * scale   # (K, nblk)
    wz = (cz_ref[...].astype(jnp.float32) - 1.5) * scale
    pre_h_s[...] = jax.lax.dot_general(
        x, wh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + bh_ref[...].astype(jnp.float32)
    pre_z = jax.lax.dot_general(x, wz, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        + bz_ref[...].astype(jnp.float32)
    # SAR-ADC transfer (mid-rise floor on the 63-unit capacitor grid)
    zq_s[...] = jnp.floor(jnp.clip(pre_z / 6.0 + 0.5, 0.0, 1.0)
                          * GATE_UNITS) / GATE_UNITS

    # the time loop indexes rows of fp32 VMEM refs (Mosaic lowers a
    # dynamic row index on an fp32 ref, not a dynamic slice of a value);
    # each step's h overwrites the pre_h row it was computed from, and
    # the chunk's outputs leave in two whole-block stores
    def step(t, h):                                        # h: (1, nblk)
        row = pl.ds(t, 1)
        zq = zq_s[row, :]
        h = zq * pre_h_s[row, :] + (1.0 - zq) * h
        pre_h_s[row, :] = h
        return h

    h_s[...] = jax.lax.fori_loop(0, tblk, step, h_s[...])
    h_ref[0] = pre_h_s[...].astype(h_ref.dtype)
    y_ref[0] = (pre_h_s[...] > 0.0).astype(y_ref.dtype)


def _step_kernel(x_ref, ch_ref, cz_ref, bh_ref, bz_ref, h0_ref, y_ref, h_ref,
                 *, scale):
    x = x_ref[...].astype(jnp.float32)                     # (B, K)
    wh = (ch_ref[...].astype(jnp.float32) - 1.5) * scale   # (K, nblk)
    wz = (cz_ref[...].astype(jnp.float32) - 1.5) * scale
    pre_h = jax.lax.dot_general(x, wh, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        + bh_ref[...].astype(jnp.float32)
    pre_z = jax.lax.dot_general(x, wz, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        + bz_ref[...].astype(jnp.float32)
    zq = jnp.floor(jnp.clip(pre_z / 6.0 + 0.5, 0.0, 1.0) * GATE_UNITS) \
        / GATE_UNITS
    h = zq * pre_h + (1.0 - zq) * h0_ref[...].astype(jnp.float32)
    h_ref[...] = h.astype(h_ref.dtype)
    y_ref[...] = (h > 0.0).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "nblk", "interpret"))
def minimalist_step_pallas(x, codes_h, codes_z, scale, bh, bz, h_prev, *,
                           nblk=128, interpret=True):
    """ONE decode step of the fused core: projection + SAR-ADC gate +
    capacitor-swap state update + comparator in a single kernel launch —
    the serving engine's hot path at O(1) state.

    x: (B, K) {0,1}; codes: (K, N) int8; bh/bz: (N,); h_prev: (B, N)
    -> (y, h) each (B, N).  N % nblk == 0.
    """
    B, K = x.shape
    N = codes_h.shape[1]
    assert N % nblk == 0, (N, nblk)
    kern = functools.partial(_step_kernel, scale=float(scale))
    return pl.pallas_call(
        kern,
        grid=(N // nblk,),
        in_specs=[
            pl.BlockSpec((B, K), lambda n: (0, 0)),
            pl.BlockSpec((K, nblk), lambda n: (0, n)),
            pl.BlockSpec((K, nblk), lambda n: (0, n)),
            pl.BlockSpec((1, nblk), lambda n: (0, n)),
            pl.BlockSpec((1, nblk), lambda n: (0, n)),
            pl.BlockSpec((B, nblk), lambda n: (0, n)),
        ],
        out_specs=[
            pl.BlockSpec((B, nblk), lambda n: (0, n)),
            pl.BlockSpec((B, nblk), lambda n: (0, n)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, N), x.dtype),
            jax.ShapeDtypeStruct((B, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="minimalist_step",
    )(x, codes_h, codes_z, bh.reshape(1, N), bz.reshape(1, N), h_prev)


@functools.partial(jax.jit,
                   static_argnames=("scale", "tblk", "nblk", "interpret"))
def minimalist_block_pallas(x, codes_h, codes_z, scale, bh, bz, h0, *,
                            tblk=128, nblk=128, interpret=True):
    """x: (B,T,K) {0,1}; codes: (K,N) int8; scale float; bh/bz: (N,);
    h0: (B,N) -> (y, h) each (B,T,N).  T % tblk == 0, N % nblk == 0."""
    B, T, K = x.shape
    N = codes_h.shape[1]
    assert T % tblk == 0 and N % nblk == 0, (T, tblk, N, nblk)
    grid = (B, N // nblk, T // tblk)
    kern = functools.partial(_kernel, tblk=tblk, scale=float(scale))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tblk, K), lambda b, n, t: (b, t, 0)),
            pl.BlockSpec((K, nblk), lambda b, n, t: (0, n)),
            pl.BlockSpec((K, nblk), lambda b, n, t: (0, n)),
            pl.BlockSpec((1, nblk), lambda b, n, t: (0, n)),
            pl.BlockSpec((1, nblk), lambda b, n, t: (0, n)),
            # h0 as (B, 1, N): a (1, nblk) tile of (B, N) breaks the TPU's
            # (8, 128) rule for the second-to-last block dim
            pl.BlockSpec((1, 1, nblk), lambda b, n, t: (b, 0, n)),
        ],
        out_specs=[
            pl.BlockSpec((1, tblk, nblk), lambda b, n, t: (b, t, n)),
            pl.BlockSpec((1, tblk, nblk), lambda b, n, t: (b, t, n)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, N), x.dtype),
            jax.ShapeDtypeStruct((B, T, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, nblk), jnp.float32),
                        pltpu.VMEM((tblk, nblk), jnp.float32),
                        pltpu.VMEM((tblk, nblk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="minimalist_block",
    )(x, codes_h, codes_z, bh.reshape(1, N), bz.reshape(1, N), h0[:, None, :])
