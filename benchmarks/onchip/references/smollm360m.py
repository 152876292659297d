"""Plain float32 reference of smollm-360m (hf:HuggingFaceTB/SmolLM-360M):
a Llama-style decoder of pre-norm GQA attention with rotary positions and
a SwiGLU MLP, tied embeddings.

Departures from the published model, both stated by the configuration
that runs: the RMSNorm epsilon is ``sizes["norm_eps"]`` (1e-6 where the
published config says 1e-5) and the tied-embedding logits are scaled by
1/sqrt(d_model).  The rotary embedding rotates the two halves of each
head (``rotate_half``), as the published model does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.onchip.references.common import (HIGHEST, dot, fake_quant,
                                                 head, rmsnorm, stack,
                                                 swiglu)


def rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, sizes, quant):
    S, d = x.shape
    H, KV, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    q = dot(x, p["wq"].reshape(d, H * hd), quant).reshape(S, H, hd)
    k = dot(x, p["wk"].reshape(d, KV * hd), quant).reshape(S, KV, hd)
    v = dot(x, p["wv"].reshape(d, KV * hd), quant).reshape(S, KV, hd)
    q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
    # head h reads key/value head h // (H // KV)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("shd,thd->hst", fake_quant(q, quant, -1),
                   fake_quant(k, quant, -1), precision=HIGHEST)
    s = s / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,thd->shd", fake_quant(w, quant, -1),
                   fake_quant(v, quant, 0), precision=HIGHEST)
    return dot(o.reshape(S, H * hd), p["wo"].reshape(H * hd, d), quant)


def logits(params, tokens, rows, sizes, quant=None):
    """tokens: (S,) ids; rows: (R,) positions -> (R, vocab) float32
    logits of the next token after each of those positions."""
    eps = sizes["norm_eps"]

    def block(x, p):
        x = x + attention(rmsnorm(x, p["norm1"]["scale"], eps), p["mixer"],
                          sizes, quant)
        return x + swiglu(rmsnorm(x, p["norm2"]["scale"], eps), p["mlp"],
                          quant)

    x = stack(params, block, tokens, sizes, quant)
    return head(params, x, rows, sizes, quant)
