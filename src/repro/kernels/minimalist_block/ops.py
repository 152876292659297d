"""Public wrapper for the fused MINIMALIST inference kernel.

Inference-only (the deployment path of the paper's edge accelerator);
training uses the STE-quantized MinGRUBlock.  ``from_block_params`` exports
a trained block exactly like analog.export_layer does for the circuit.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.kernels.dispatch import interpret
from repro.kernels.minimalist_block import ref
from repro.kernels.minimalist_block.minimalist_block import (
    minimalist_block_pallas, minimalist_step_pallas)


def _pad_to(v, m):
    return (v + m - 1) // m * m


def _row_tile(n):
    """Time tile: the biggest multiple of 8 up to 128 dividing n, else all
    of n (the TPU takes a second-to-last block dim that is a multiple of
    8 or the whole axis)."""
    for cand in (128, 64, 32, 16, 8):
        if n % cand == 0:
            return cand
    return n


def _lane_tile(n):
    """Channel tile: 128 lanes where they divide n, else all of n."""
    return 128 if n % 128 == 0 else n


def from_block_params(params):
    """Trained MinGRUBlock params -> (codes_h, codes_z, scale, bh, bz)."""
    scale = float(np.maximum(
        np.asarray(quant.weight_scale(params["wh"])),
        np.asarray(quant.weight_scale(params["wz"]))))
    ch = np.asarray(quant.quantize_weights_2b(params["wh"], scale)[1],
                    np.int8)
    cz = np.asarray(quant.quantize_weights_2b(params["wz"], scale)[1],
                    np.int8)
    bh = np.asarray(quant.quantize_bias_6b(params["bh"]))
    bz = np.asarray(quant.quantize_gate_bias_adc(params["bz"]))
    return ch, cz, scale, bh, bz


def minimalist_block(x, codes_h, codes_z, scale, bh, bz, h0=None, *,
                     backend="pallas"):
    """Fused hardware-mode block inference. Returns (y=Θ(h), h)."""
    B, T, K = x.shape
    N = codes_h.shape[1]
    if h0 is None:
        h0 = jnp.zeros((B, N), jnp.float32)
    if backend == "xla":
        return ref.minimalist_block_ref(x, jnp.asarray(codes_h),
                                        jnp.asarray(codes_z), scale,
                                        jnp.asarray(bh), jnp.asarray(bz), h0)
    tblk = _row_tile(T)
    nblk = _lane_tile(N)
    y, h = minimalist_block_pallas(
        x, jnp.asarray(codes_h, jnp.int8), jnp.asarray(codes_z, jnp.int8),
        float(scale), jnp.asarray(bh, jnp.float32),
        jnp.asarray(bz, jnp.float32), h0, tblk=tblk, nblk=nblk,
        interpret=interpret(backend))
    return y, h


def minimalist_step(x, codes_h, codes_z, scale, bh, bz, h_prev, *,
                    backend="pallas"):
    """Fused single-step hardware-mode decode: projection + gate + state
    update + comparator in ONE kernel.  x: (B, K); h_prev: (B, N) ->
    (y=Θ(h), h) each (B, N).  The serving engine's decode hot path."""
    N = codes_h.shape[1]
    if backend == "xla":
        return ref.minimalist_step_ref(x, jnp.asarray(codes_h),
                                       jnp.asarray(codes_z), scale,
                                       jnp.asarray(bh), jnp.asarray(bz),
                                       h_prev)
    nblk = _lane_tile(N)
    return minimalist_step_pallas(
        x, jnp.asarray(codes_h, jnp.int8), jnp.asarray(codes_z, jnp.int8),
        float(scale), jnp.asarray(bh, jnp.float32),
        jnp.asarray(bz, jnp.float32), h_prev, nblk=nblk,
        interpret=interpret(backend))


def cost_model(B, T, K, N, *, dtype_bytes=2):
    """Analytic (flops, hbm_bytes) per fused block call: two MVMs on the
    MXU + O(BTN) VPU work; HBM sees x once, int8 codes once, y/h out."""
    flops = 2 * 2 * B * T * K * N + 8 * B * T * N
    bytes_ = (B * T * K * dtype_bytes        # x (binary, stored bf16)
              + 2 * K * N                    # int8 code matrices
              + B * T * N * (dtype_bytes + 4))  # y + h out
    return flops, bytes_
