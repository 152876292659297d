"""Public flash-attention op with custom VJP + analytic roofline cost model.

``flash_attention(q, k, v, causal, window, backend)``:
  * backend "pallas"      — the TPU kernel: compiled on TPU, interpreted
                            elsewhere (CPU tests)
  * backend "pallas_tpu"  — compiled unconditionally
  * backend "xla"         — naive reference (baseline path)

The VJP runs the FlashAttention-2 backward kernels (dKdV + dQ), reducing
dk/dv over GQA groups.  ``cost_model`` returns the analytic per-call
(flops, hbm_bytes) used by launch.dryrun when accounting kernel regions the
XLA cost model cannot see into (Pallas custom calls are opaque).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import interpret
from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.flash_attention import flash_bwd, flash_fwd


def _blocks(S):
    for b in (128, 64, 32, 16, 8, 4, 2, 1):
        if S % b == 0:
            return b
    return 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, window=None, backend="pallas"):
    """q: (B, H, S, D); k, v: (B, KV, S, D) with H % KV == 0 -> (B, H, S, D)."""
    out, _ = _fwd(q, k, v, causal, window, backend)
    return out


def _fwd(q, k, v, causal, window, backend):
    if backend == "xla":
        G = q.shape[1] // k.shape[1]
        kx = jnp.repeat(k, G, axis=1)
        vx = jnp.repeat(v, G, axis=1)
        out = ref.mha_ref(q, kx, vx, causal=causal, window=window)
        return out, (q, k, v, out, None)
    group = q.shape[1] // k.shape[1]
    b = _blocks(q.shape[2])
    out, lse = flash_fwd(q, k, v, bq=b, bk=b, causal=causal, window=window,
                         group=group, interpret=interpret(backend))
    return out, (q, k, v, out, lse)


def _bwd(causal, window, backend, res, g):
    q, k, v, out, lse = res
    group = q.shape[1] // k.shape[1]
    if backend == "xla" or lse is None:
        # differentiate the reference directly
        def f(q, k, v):
            G = q.shape[1] // k.shape[1]
            return ref.mha_ref(q, jnp.repeat(k, G, 1), jnp.repeat(v, G, 1),
                               causal=causal, window=window)

        _, vjp = jax.vjp(f, q, k, v)
        return vjp(g)
    b = _blocks(q.shape[2])
    dq, dk, dv = flash_bwd(q, k, v, out, lse, g, bq=b, bk=b, causal=causal,
                           window=window, group=group,
                           interpret=interpret(backend))
    B, H, S, D = q.shape
    KV = k.shape[1]
    dk = dk.reshape(B, KV, H // KV, S, D).sum(2).astype(k.dtype)
    dv = dv.reshape(B, KV, H // KV, S, D).sum(2).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


flash_attention.defvjp(lambda q, k, v, c, w, b: _fwd(q, k, v, c, w, b),
                       _bwd)


def cost_model(B, H, KV, S, D, *, causal=True, window=None, train=True,
               dtype_bytes=2):
    """Analytic (flops, hbm_bytes) per flash-attention call.

    flops: 2 matmuls fwd (QKᵀ, PV) = 4·B·H·S_eff·S·D; bwd adds 3 matmul
    pairs + recompute ≈ 2.5× fwd.  causal/window halve/shrink S_eff.
    hbm_bytes: q,k,v read + o written (+ lse), ×3 passes for bwd (re-read in
    dKdV and dQ) + gradient writes — O(S·D), never O(S²).
    """
    frac = 0.5 if causal else 1.0
    if window is not None and window < S:
        frac = min(frac, window / S)
    fwd_flops = 4 * B * H * S * S * D * frac
    flops = fwd_flops * (1 + 2.5 if train else 1)
    qkv = B * (H + 2 * KV) * S * D * dtype_bytes
    o = B * H * S * D * dtype_bytes
    lse = B * H * S * 4
    passes = 3 if train else 1
    grads = (qkv + o) if train else 0
    return flops, qkv * passes + o + lse * passes + grads
