"""Pallas TPU kernel: chunked diagonal linear recurrence (first-order scan).

    h_t = a_t ⊙ h_{t-1} + b_t

TPU mapping (hardware-adaptation notes, DESIGN.md §3):
  * The recurrence is element-wise over the channel dim D — the "capacitor
    swap" of the paper keeps state updates fully local, which on TPU means
    the scan body is pure VPU work, vectorized across (8, 128) vregs.
  * Grid is (B, D/dblk, T/tblk).  The last grid axis iterates time chunks
    *sequentially* ("arbitrary" dimension semantics); the running state h is
    carried across time chunks in a VMEM scratch buffer, so HBM traffic is
    exactly one read of (a, b) and one write of h — the kernel is
    memory-bound by construction (arithmetic intensity 2 flops / 12 bytes
    at bf16) and the roofline target is HBM bandwidth.
  * Within a chunk the time loop is a jax.lax.fori_loop over tblk steps;
    each step is a (1, dblk)-wide fused multiply-add.
  * dblk is a multiple of 128 (lane width); tblk trades VMEM footprint
    (the a, b and h blocks plus two fp32 staging copies of a and b)
    against grid overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(h0_ref, a_ref, b_ref, out_ref, carry_ref, a_s, b_s, *,
                 tblk: int):
    """One (batch, channel-block, time-chunk) grid cell."""
    t_idx = pl.program_id(2)

    # On the first time chunk, seed the carry from h0.
    @pl.when(t_idx == 0)
    def _():
        carry_ref[...] = h0_ref[0].astype(jnp.float32)

    # Stage the chunk in fp32 VMEM and step through it one row at a time.
    # Mosaic lowers a dynamic row index on an fp32 ref, but neither a
    # dynamic slice of a value nor a single-row access to a packed bf16
    # tile; the output row overwrites the ``b`` row it was computed from.
    a_s[...] = a_ref[0].astype(jnp.float32)
    b_s[...] = b_ref[0].astype(jnp.float32)

    def step(i, h):                                   # h: (1, dblk) fp32
        row = pl.ds(i, 1)
        h = a_s[row, :] * h + b_s[row, :]
        b_s[row, :] = h
        return h

    carry_ref[...] = jax.lax.fori_loop(0, tblk, step, carry_ref[...])
    out_ref[0] = b_s[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tblk", "dblk", "interpret"))
def linear_scan_pallas(a, b, h0, *, tblk: int = 256, dblk: int = 256,
                       interpret: bool = True):
    """a, b: (B, T, D); h0: (B, D) -> h: (B, T, D).

    Shapes must satisfy T % tblk == 0 and D % dblk == 0 (ops.py pads).
    ``interpret=True`` executes on CPU for validation; on TPU pass False.
    """
    B, T, D = a.shape
    assert b.shape == (B, T, D) and h0.shape == (B, D)
    assert T % tblk == 0 and D % dblk == 0, (T, tblk, D, dblk)
    grid = (B, D // dblk, T // tblk)

    kern = functools.partial(_scan_kernel, tblk=tblk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # h0 as (B, 1, D): one (1, 1, dblk) tile per (batch, channel
            # block), constant in t — a (1, dblk) tile of (B, D) breaks the
            # TPU's (8, 128) rule for the second-to-last block dim
            pl.BlockSpec((1, 1, dblk), lambda bi, di, ti: (bi, 0, di)),
            pl.BlockSpec((1, tblk, dblk), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, tblk, dblk), lambda bi, di, ti: (bi, ti, di)),
        ],
        out_specs=pl.BlockSpec((1, tblk, dblk), lambda bi, di, ti: (bi, ti, di)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, dblk), jnp.float32),
                        pltpu.VMEM((tblk, dblk), jnp.float32),
                        pltpu.VMEM((tblk, dblk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="linear_scan",
    )(h0[:, None, :], a, b)
