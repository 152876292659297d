"""Pallas TPU kernel: binary-activation × 2 b-weight IMC matmul (Eq. 6).

TPU adaptation of the switched-capacitor charge-sharing MVM (DESIGN.md §3):
the MXU plays the role of the capacitor array.  Key properties exploited:

  * Weights live in HBM as **int8 codes** (2 b of information; int8 is the
    narrowest dense dtype with native TPU load paths).  Dequantization
    ``w = (code − 1.5)·Δ`` is two VPU ops performed on the VMEM tile right
    before the MXU op — a 4× reduction in weight HBM traffic vs fp32, which
    is what makes the kernel memory-roofline-optimal for the skinny
    activation shapes RNN inference produces.
  * Activations are binary but stored as bf16 0/1 (TPU has no 1 b datapath);
    the matmul then *is* the select-and-accumulate of the circuit.
  * The 1/K charge-sharing normalization folds into the output epilogue.
  * Blocking: (bm × bk) ⊗ (bk × bn) MXU tiles, K-axis innermost and
    sequential, fp32 accumulator in VMEM scratch (one per (m, n) tile).

Grid: (M/bm, N/bn, K/bk), dimension_semantics = (parallel, parallel,
arbitrary) so the accumulator carries across the contraction axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.kernels.imc_mvm.ref import LEVEL_OFFSET


def _imc_kernel(x_ref, codes_ref, scale_ref, out_ref, acc_ref, *, n_k: int,
                inv_k: float):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # (bm, bk) bf16 {0,1}
    w = (codes_ref[...].astype(jnp.float32) - LEVEL_OFFSET)  # (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x.astype(jnp.float32), w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _():
        scale = scale_ref[...].astype(jnp.float32)   # (1, bn) per-column Δ
        out_ref[...] = (acc_ref[...] * scale * inv_k).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret", "out_dtype"))
def imc_mvm_pallas(x, codes, scale, *, bm: int = 128, bn: int = 128,
                   bk: int = 128, interpret: bool = True,
                   out_dtype=jnp.float32):
    """x: (M, K) {0,1}; codes: (K, N) int8; scale: (N,) -> (M, N).

    M % bm == K % bk == N % bn == 0 (ops.py pads).
    """
    M, K = x.shape
    K2, N = codes.shape
    assert K == K2 and scale.shape == (N,)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)

    kern = functools.partial(_imc_kernel, n_k=n_k, inv_k=1.0 / K)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="imc_mvm",
    )(x, codes, scale.reshape(1, N))
