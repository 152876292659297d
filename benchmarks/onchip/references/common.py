"""Pieces shared by the plain float32 references: every matrix product in
float32 at ``Precision.HIGHEST``, and the control's lower-precision
products.

``quant`` selects the control: ``None`` is the reference itself;
``"int8"`` and ``"fp8"`` hold in int8 or in float8 e4m3 what the program
holds in bfloat16: both operands of every product (weights per output
column, activations per row, each scaled to its absolute maximum) and the
residual stream after every layer (per row).  Products accumulate in
float32.  That is the reference put in the program's place one precision
step below the bfloat16 the configurations compute in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_QMAX = {"int8": 127.0, "fp8": 448.0}


def fake_quant(x, quant, axis):
    """Round ``x`` to ``quant`` with one scale per slice along ``axis``
    (the axis a product sums over), and back to float32."""
    if quant is None:
        return x
    qmax = _QMAX[quant]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    s = jnp.where(s > 0, s, 1.0)
    y = x / s
    if quant == "int8":
        y = jnp.clip(jnp.round(y), -qmax, qmax)
    else:
        y = y.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return y * s


def dot(x, w, quant=None):
    """x (..., k) @ w (k, n) in float32."""
    return jnp.matmul(fake_quant(x, quant, -1), fake_quant(w, quant, 0),
                      precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def swiglu(x, p, quant=None):
    g = dot(x, p["w_gate"], quant)
    u = dot(x, p["w_up"], quant)
    return dot(jax.nn.silu(g) * u, p["w_down"], quant)


def stack(params, block, tokens, sizes, quant=None):
    """Embedding, the scanned layer stack (``block(x, layer_params)``
    returns the layer's output) and the final norm: (S,) ids -> (S, d)."""
    x = fake_quant(params["embed"]["table"][tokens].astype(jnp.float32),
                   quant, -1)
    layers = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    params["unit0"])

    def body(x, p):
        return fake_quant(block(x, p), quant, -1), None

    x, _ = jax.lax.scan(body, x, layers)
    return rmsnorm(x, params["final_norm"]["scale"], sizes["norm_eps"])


def head(params, x, rows, sizes, quant=None):
    """Tied-embedding logits of the rows ``rows`` of x, scaled by
    1/sqrt(d_model) as the configuration states: -> (len(rows), vocab)."""
    table = params["embed"]["table"].astype(jnp.float32)
    h = x[rows]
    logits = dot(h, table.T, quant) / jnp.sqrt(float(sizes["d_model"]))
    return logits[:, :sizes["vocab"]]
