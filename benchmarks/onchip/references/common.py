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

import re

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


_LAYER = re.compile(r"(head|unit|tail)(\d+)(?:_r(\d+))?")


def _layers(params):
    """The program's layer names by kind of place: ``head``, ``unit``
    (the pattern, its leaves stacked over the repeats), ``repeat`` (the
    pattern of a model with one repeat, which the program does not stack:
    ``unit<i>_r<r>``) and ``tail``, each in the order the layers run."""
    out = {"head": [], "unit": [], "repeat": [], "tail": []}
    for k in params:
        m = _LAYER.fullmatch(k)
        if m:
            place, i, r = m.group(1), int(m.group(2)), m.group(3)
            place = "repeat" if r is not None else place
            out[place].append(((int(r or 0), i), k))
    return {p: [k for _o, k in sorted(v)] for p, v in out.items()}


def stack(params, block, tokens, sizes, quant=None, kinds=None):
    """Embedding, the layers in the program's order and the final norm:
    (S,) ids -> (S, d).

    The program's layers are ``head0, head1, ..``, then the pattern
    ``unit0 .. unitK-1`` once for each repeat, then ``tail0, tail1, ..``.
    ``block(x, layer_params)`` returns a layer's output.  Where layers
    differ in kind, ``block`` is a dict of such functions by kind and
    ``kinds`` gives each layer's kind by its name (``{"unit4": "attn"}``;
    a pattern layer by its ``unit<i>`` name at every repeat).
    """
    x = fake_quant(params["embed"]["table"][tokens].astype(jnp.float32),
                   quant, -1)
    names = _layers(params)

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def layer(name, x, p):
        fn = block if kinds is None else block[kinds[name.split("_r")[0]]]
        return fake_quant(fn(x, p), quant, -1)

    for name in names["head"] + names["repeat"]:
        x = layer(name, x, f32(params[name]))
    if names["unit"]:
        def body(x, ps):
            for name in names["unit"]:
                x = layer(name, x, ps[name])
            return x, None

        x, _ = jax.lax.scan(body, x,
                            {n: f32(params[n]) for n in names["unit"]})
    for name in names["tail"]:
        x = layer(name, x, f32(params[name]))
    return rmsnorm(x, params["final_norm"]["scale"], sizes["norm_eps"])


def head(params, x, rows, sizes, quant=None, table="embed", scale=None):
    """Logits of the rows ``rows`` of x against the output table
    ``params[table]["table"]`` (the tied embedding; ``"lm_head"`` for an
    untied one), over sqrt(d_model) as the configurations state, or times
    ``scale`` where a configuration states its own: -> (len(rows), vocab).
    """
    out = params[table]["table"].astype(jnp.float32)
    h = x[rows]
    logits = dot(h, out.T, quant)
    if scale is None:
        logits = logits / jnp.sqrt(float(sizes["d_model"]))
    else:
        logits = logits * scale
    return logits[:, :sizes["vocab"]]
