"""What a configuration of jamba-1.5-large-398b-smoke brings beside its
file of sizes: init rules for the Mamba mixer and the router, which the
shared table lacks, and its model FLOPs.  Each period of PERIOD layers has
GQA attention at ATTN_AT and Mamba elsewhere; odd layers have a routed
SwiGLU expert layer (top_k of n_experts), even ones a dense SwiGLU of
d_ff; the output table is untied."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.onchip.weights import FanIn, Normal

PERIOD, ATTN_AT = 8, 4


def a_log(key, shape, dtype):
    """S4D-real: A = -(1 .. d_state) on every channel, stored as its log."""
    n = shape[-1]
    a = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
    return jnp.broadcast_to(a, shape).astype(dtype)


def dt_bias(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniform in [e^-6, e^-4]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * 2.0 - 6.0)
    return jnp.log(jnp.expm1(dt)).astype(dtype)


INIT = {"router": FanIn((-2,)), "a_log": a_log, "dt_bias": dt_bias,
        "d_skip": 1.0, "conv_b": 0.0, "conv": Normal(0.1),
        "w_in": FanIn((-2,)), "w_bcdt": FanIn((-2,)), "w_dt": FanIn((-2,)),
        "w_out": FanIn((-2,))}


def _mamba(s):
    """In and out projections, the depthwise conv, the B/C/dt projection
    (dt rank d_model/16), dt's up-projection and the selective scan
    (discretise A and B, update the state, read it by C: 6 per state)."""
    d, n = s["d_model"], s["mamba.d_state"]
    di, r = s["mamba.expand"] * d, max(1, s["d_model"] // 16)
    return 2 * d * 2 * di + 2 * s["mamba.d_conv"] * di \
        + 2 * di * (2 * n + r) + 2 * r * di + 6 * di * n + 2 * di * d


def _per_token(s):
    d, H, KV, hd = s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"]
    periods = s["n_layers"] // PERIOD
    attn = 2 * d * (H + 2 * KV) * hd + 2 * H * hd * d
    moe = 2 * d * s["moe.n_experts"] + s["moe.top_k"] * 6 * d * \
        s["moe.d_ff_expert"]
    per_period = attn + (PERIOD - 1) * _mamba(s) \
        + PERIOD // 2 * (moe + 6 * d * s["d_ff"])
    return float(periods * per_period)


def _attention(s, pairs):
    return 4.0 * (s["n_layers"] // PERIOD) * s["n_heads"] * s["head_dim"] \
        * pairs


def prefill_flops(sizes, prompt_len):
    """A whole prompt: causal attention, logits for the last token."""
    pairs = prompt_len * (prompt_len + 1) / 2.0
    return prompt_len * _per_token(sizes) + _attention(sizes, pairs) \
        + 2.0 * sizes["d_model"] * sizes["vocab"]


def decode_flops(sizes, n_active, ctx_tokens):
    """One decode step of n_active tokens over ctx_tokens live keys (in
    the attention layers; Mamba reads its fixed state)."""
    return n_active * (_per_token(sizes) + 2.0 * sizes["d_model"]
                       * sizes["vocab"]) + _attention(sizes, ctx_tokens)
