"""What a configuration of qwen3-moe-30b-a3b-smoke brings beside its file
of sizes: the init rule of the router, which the shared table lacks, and
its model FLOPs.  Every layer is GQA attention followed by a routed
SwiGLU expert layer (top_k of n_experts, no shared expert); the output
table is untied."""
from __future__ import annotations

from benchmarks.onchip.weights import FanIn

INIT = {"router": FanIn((-2,))}


def _per_token(s):
    d, H, KV, hd = s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"]
    attn = 2 * d * (H + 2 * KV) * hd + 2 * H * hd * d
    moe = 2 * d * s["moe.n_experts"] + s["moe.top_k"] * 6 * d * \
        s["moe.d_ff_expert"]
    return float(s["n_layers"] * (attn + moe))


def _attention(s, pairs):
    return 4.0 * s["n_layers"] * s["n_heads"] * s["head_dim"] * pairs


def prefill_flops(sizes, prompt_len):
    """A whole prompt: causal attention, logits for the last token."""
    pairs = prompt_len * (prompt_len + 1) / 2.0
    return prompt_len * _per_token(sizes) + _attention(sizes, pairs) \
        + 2.0 * sizes["d_model"] * sizes["vocab"]


def decode_flops(sizes, n_active, ctx_tokens):
    """One decode step of n_active tokens over ctx_tokens live keys."""
    return n_active * (_per_token(sizes) + 2.0 * sizes["d_model"]
                       * sizes["vocab"]) + _attention(sizes, ctx_tokens)
