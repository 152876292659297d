"""Public wrapper for the IMC MVM kernel.

Inference-only op (the hardware path): weights are frozen 2 b codes, so no
VJP is defined for `codes`; gradients w.r.t. the binary activations are
given a straight-through surrogate so the op can sit inside QAT graphs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import interpret
from repro.kernels.imc_mvm import ref
from repro.kernels.imc_mvm.imc_mvm import imc_mvm_pallas

_DEFAULT_BACKEND = "xla"


def _round_up(x, m):
    return (x + m - 1) // m * m


def imc_mvm(x, codes, scale, *, backend=_DEFAULT_BACKEND,
            bm=128, bn=128, bk=128):
    """Charge-sharing MVM: (x @ deq(codes)) / K.

    x: (..., K) in {0,1}; codes: (K, N) int; scale: scalar or (N,).
    """
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (codes.shape[1],))
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    if backend == "xla":
        out = ref.imc_mvm_ref(x2, codes, scale)
    elif backend in ("pallas", "pallas_tpu"):
        M = x2.shape[0]
        N = codes.shape[1]
        bm_, bn_, bk_ = (min(bm, _round_up(M, 8)), min(bn, _round_up(N, 128)),
                         min(bk, _round_up(K, 128)))
        Mp, Np, Kp = _round_up(M, bm_), _round_up(N, bn_), _round_up(K, bk_)
        xp = jnp.pad(x2.astype(jnp.float32), [(0, Mp - M), (0, Kp - K)])
        # pad codes with 1.5-offset-neutral values? code padding contributes
        # (c-1.5)≠0 even for x=0 rows — but padded x rows are 0 so K-padding
        # of codes only meets x-padding columns == 0; safe. N-padding sliced.
        cp = jnp.pad(codes.astype(jnp.int8), [(0, Kp - K), (0, Np - N)])
        sp = jnp.pad(scale, [(0, Np - N)])
        out = imc_mvm_pallas(xp, cp, sp, bm=bm_, bn=bn_, bk=bk_,
                             interpret=interpret(backend))
        # kernel divides by padded K; rescale to true K
        out = out[:M, :N] * (Kp / K)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return out.reshape(*lead, codes.shape[1])
