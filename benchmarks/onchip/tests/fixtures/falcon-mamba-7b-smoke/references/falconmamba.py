"""What a configuration of falcon-mamba-7b-smoke brings beside its file of
sizes: init rules for the Mamba mixer, which the shared table lacks, and
its model FLOPs.  Every layer is a Mamba mixer with no MLP; the output
table is untied."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.onchip.weights import FanIn, Normal


def a_log(key, shape, dtype):
    """S4D-real: A = -(1 .. d_state) on every channel, stored as its log."""
    n = shape[-1]
    a = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
    return jnp.broadcast_to(a, shape).astype(dtype)


def dt_bias(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniform in [e^-6, e^-4]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * 2.0 - 6.0)
    return jnp.log(jnp.expm1(dt)).astype(dtype)


INIT = {"a_log": a_log, "dt_bias": dt_bias, "d_skip": 1.0, "conv_b": 0.0,
        "conv": Normal(0.1), "w_in": FanIn((-2,)), "w_bcdt": FanIn((-2,)),
        "w_dt": FanIn((-2,)), "w_out": FanIn((-2,))}


def _per_token(s):
    """In and out projections, the depthwise conv, the B/C/dt projection
    (dt rank d_model/16), dt's up-projection and the selective scan
    (discretise A and B, update the state, read it by C: 6 per state)."""
    d, n = s["d_model"], s["mamba.d_state"]
    di, r = s["mamba.expand"] * d, max(1, s["d_model"] // 16)
    per_layer = 2 * d * 2 * di + 2 * s["mamba.d_conv"] * di \
        + 2 * di * (2 * n + r) + 2 * r * di + 6 * di * n + 2 * di * d
    return float(s["n_layers"] * per_layer)


def prefill_flops(sizes, prompt_len):
    """A whole prompt, logits for the last token."""
    return prompt_len * _per_token(sizes) \
        + 2.0 * sizes["d_model"] * sizes["vocab"]


def decode_flops(sizes, n_active, ctx_tokens):
    """One decode step of n_active tokens; the state is fixed, so the
    live context costs nothing."""
    del ctx_tokens
    return n_active * (_per_token(sizes) + 2.0 * sizes["d_model"]
                       * sizes["vocab"])
