"""jit'd public wrapper for the linear_scan kernel, with a custom VJP.

The adjoint of the recurrence  h_t = a_t ⊙ h_{t-1} + b_t  is itself a
reverse-time diagonal linear recurrence:

    λ_t = g_t + a_{t+1} ⊙ λ_{t+1}          (λ: cotangent of h)
    ∂b_t = λ_t ,  ∂a_t = λ_t ⊙ h_{t-1} ,  ∂h0 = a_0 ⊙ λ_0

so the backward pass reuses the *same* scan engine on time-reversed inputs —
one extra memory-bound pass, no O(T) recomputation and no saved
intermediates beyond the forward output itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import interpret
from repro.kernels.linear_scan import ref
from repro.kernels.linear_scan.linear_scan import linear_scan_pallas

# Backend selection:
#   "xla"       — associative scan (O(log T) depth); default on CPU hosts
#   "pallas"    — the TPU kernel: compiled on TPU, interpreted elsewhere
#   "pallas_tpu"— the TPU kernel, compiled unconditionally
#   "seq"       — definitional lax.scan (debugging)
_DEFAULT_BACKEND = "xla"


def _round_up(x, m):
    return (x + m - 1) // m * m


def _dispatch(a, b, h0, backend, tblk, dblk):
    if backend == "seq":
        return ref.linear_scan_sequential(a, b, h0)
    if backend == "xla":
        return ref.linear_scan_associative(a, b, h0)
    if backend in ("pallas", "pallas_tpu"):
        B, T, D = a.shape
        tblk = min(tblk, T)
        dblk = min(dblk, _round_up(D, 128))
        Tp, Dp = _round_up(T, tblk), _round_up(D, dblk)
        pad3 = [(0, 0), (0, Tp - T), (0, Dp - D)]
        ap = jnp.pad(a, pad3)           # a=0 in padding keeps the carry exact
        bp = jnp.pad(b, pad3)
        h0p = jnp.pad(h0, [(0, 0), (0, Dp - D)])
        h = linear_scan_pallas(ap, bp, h0p, tblk=tblk, dblk=dblk,
                               interpret=interpret(backend))
        return h[:, :T, :D]
    raise ValueError(f"unknown backend {backend!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def linear_scan(a, b, h0, backend=_DEFAULT_BACKEND, tblk=256, dblk=256):
    """h_t = a_t ⊙ h_{t-1} + b_t over axis 1. a, b: (B,T,D); h0: (B,D)."""
    return _dispatch(a, b, h0, backend, tblk, dblk)


def _fwd(a, b, h0, backend, tblk, dblk):
    h = _dispatch(a, b, h0, backend, tblk, dblk)
    return h, (a, h, h0)


def _bwd(backend, tblk, dblk, res, g):
    a, h, h0 = res
    # a shifted one step forward in time, reversed:  A_rev[t] = a[T-t]
    a_shift = jnp.concatenate(
        [jnp.zeros_like(a[:, :1]), jnp.flip(a[:, 1:], axis=1)], axis=1)
    g_rev = jnp.flip(g, axis=1)
    lam_rev = _dispatch(a_shift, g_rev, jnp.zeros_like(h0), backend, tblk, dblk)
    lam = jnp.flip(lam_rev, axis=1)
    h_prev = jnp.concatenate([h0[:, None, :], h[:, :-1, :]], axis=1)
    da = lam * h_prev
    db = lam
    dh0 = a[:, 0, :] * lam[:, 0, :]
    return da, db, dh0


linear_scan.defvjp(_fwd, _bwd)


def mingru_scan(z, htilde, h0, **kw):
    """minGRU state update (paper Eq. 1): h_t = (1−z_t)⊙h_{t−1} + z_t⊙h̃_t."""
    return linear_scan(1.0 - z, z * htilde, h0, **kw)
