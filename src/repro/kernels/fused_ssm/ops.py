"""Public fused selective-scan op with custom VJP + analytic cost model."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import interpret
from repro.kernels.fused_ssm import ref
from repro.kernels.fused_ssm.fused_ssm import fused_ssm_bwd, fused_ssm_fwd


def _blk(v, opts):
    for b in opts:
        if v % b == 0:
            return b
    return 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def selective_scan(dt, x, Bm, Cm, A, backend="pallas"):
    """dt, x: (B,T,di); Bm, Cm: (B,T,n); A: (di,n) -> y (B,T,di)."""
    y, _ = _fwd(dt, x, Bm, Cm, A, backend)
    return y


def _fwd(dt, x, Bm, Cm, A, backend):
    if backend == "xla":
        return ref.selective_scan_ref(dt, x, Bm, Cm, A), \
            (dt, x, Bm, Cm, A, None)
    tblk = _blk(x.shape[1], (256, 128, 64, 32, 16, 8, 4, 2, 1))
    dblk = _blk(x.shape[2], (128, 64, 32, 16, 8, 4, 2, 1))
    y, h_entries = fused_ssm_fwd(dt, x, Bm, Cm, A, tblk=tblk, dblk=dblk,
                                 interpret=interpret(backend))
    return y, (dt, x, Bm, Cm, A, h_entries)


def _bwd(backend, res, dy):
    dt, x, Bm, Cm, A, h_entries = res
    if backend == "xla" or h_entries is None:
        _, vjp = jax.vjp(lambda *a: ref.selective_scan_ref(*a),
                         dt, x, Bm, Cm, A)
        return vjp(dy)
    tblk = _blk(x.shape[1], (256, 128, 64, 32, 16, 8, 4, 2, 1))
    dblk = _blk(x.shape[2], (128, 64, 32, 16, 8, 4, 2, 1))
    ddt, dx, dBp, dCp, dAp = fused_ssm_bwd(
        dt, x, Bm, Cm, A, h_entries, dy, tblk=tblk, dblk=dblk,
        interpret=interpret(backend))
    B, T, di = x.shape
    n_d = di // dblk
    dB = dBp.reshape(B, n_d, T, -1).sum(1).astype(Bm.dtype)
    dC = dCp.reshape(B, n_d, T, -1).sum(1).astype(Cm.dtype)
    dA = dAp.sum(0).astype(A.dtype)
    return (ddt.astype(dt.dtype), dx.astype(x.dtype), dB, dC, dA)


selective_scan.defvjp(lambda dt, x, Bm, Cm, A, b: _fwd(dt, x, Bm, Cm, A, b),
                      _bwd)


def cost_model(B, T, di, n, *, train=True, dtype_bytes=2, tblk=256):
    """Analytic (flops, hbm_bytes) per fused selective-scan call.

    flops: fwd ≈ 6 VPU ops per (t, d, n) element (exp, 2 mul-add for the
    recurrence, mul-add for y) ⇒ 6·B·T·di·n; bwd ≈ 2.5× (recompute + grads).
    hbm_bytes: inputs dt,x (B·T·di), B,C (B·T·n), y out, chunk-entry
    residuals (B·T/tblk·di·n fp32); bwd re-reads inputs + writes grads.
    The (B,T,di,n) a/b/h tensors NEVER touch HBM — that is the point.
    """
    el = B * T * di * n
    flops = 6 * el * (3.5 if train else 1.0)
    io = (2 * B * T * di + 2 * B * T * n) * dtype_bytes
    resid = (B * (T // tblk) * di * n) * 4
    out = B * T * di * dtype_bytes
    if train:
        return flops, 2 * io + 2 * out + 2 * resid + io  # re-read + grads
    return flops, io + out + resid
