"""Plain float32 reference of minimalist-lm-360m: the paper's minGRU
(arXiv:2505.08599, Eq. 1-4 in its float form) as the time-mixing layer of
a pre-norm decoder at SmolLM-360M geometry, with a SwiGLU MLP and tied
embeddings whose logits are scaled by 1/sqrt(d_model):

    h~_t = W^h x_t + b^h          z_t = sigmoid(W^z x_t + b^z)
    h_t  = z_t * h~_t + (1 - z_t) * h_{t-1},   h_{-1} = 0

The layer adds h_t to the residual stream.  The recurrence runs one
position at a time in float32, 64 positions to a loop iteration (a loop
of one position per iteration takes the TPU compiler minutes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.onchip.references.common import (dot, head, rmsnorm, stack,
                                                 swiglu)

BLOCK = 64


def mingru(x, p, quant):
    ht = dot(x, p["wh"], quant) + p["bh"]
    z = jax.nn.sigmoid(dot(x, p["wz"], quant) + p["bz"])
    T, D = z.shape
    pad = -T % BLOCK
    zb = jnp.pad(z, ((0, pad), (0, 0))).reshape(-1, BLOCK, D)
    hb = jnp.pad(ht, ((0, pad), (0, 0))).reshape(-1, BLOCK, D)

    def block(h, zh):
        zk, hk = zh
        out = []
        for i in range(BLOCK):
            h = zk[i] * hk[i] + (1.0 - zk[i]) * h
            out.append(h)
        return h, jnp.stack(out)

    _h, h = jax.lax.scan(block, jnp.zeros((D,), z.dtype), (zb, hb))
    return h.reshape(-1, D)[:T]


def logits(params, tokens, rows, sizes, quant=None):
    """tokens: (S,) ids; rows: (R,) positions -> (R, vocab) float32
    logits of the next token after each of those positions."""
    eps = sizes["norm_eps"]

    def block(x, p):
        x = x + mingru(rmsnorm(x, p["norm1"]["scale"], eps), p["mixer"],
                       quant)
        return x + swiglu(rmsnorm(x, p["norm2"]["scale"], eps), p["mlp"],
                          quant)

    x = stack(params, block, tokens, sizes, quant)
    return head(params, x, rows, sizes, quant)
