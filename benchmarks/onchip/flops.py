"""Model FLOPs per token from the configuration's sizes (what the
algorithm needs; recomputation and padding are not counted).

A matrix product of an m-by-k by k-by-n costs 2mkn.  Per token and layer:
attention projections 2d(H + 2KV)hd + 2 H hd d, the SwiGLU MLP 6 d f,
the minGRU projections 4 d^2 (+ 4 d for the gate and update), and the
tied head 2 d V once per token whose logits are taken.  Attention over a
context of ``ctx`` keys adds 4 H hd ctx per layer.
"""
from __future__ import annotations


def dense_per_token(sizes: dict, mixer: str) -> float:
    d, L, f = sizes["d_model"], sizes["n_layers"], sizes["d_ff"]
    per_layer = 6 * d * f
    if mixer == "attn":
        H, KV, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
        per_layer += 2 * d * (H + 2 * KV) * hd + 2 * H * hd * d
    elif mixer == "mingru":
        per_layer += 4 * d * d + 4 * d
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    return float(L * per_layer)


def head_per_token(sizes: dict) -> float:
    return 2.0 * sizes["d_model"] * sizes["vocab"]


def attention(sizes: dict, mixer: str, ctx_tokens: float) -> float:
    """Attention FLOPs for a total of ``ctx_tokens`` query-key pairs."""
    if mixer != "attn":
        return 0.0
    return 4.0 * sizes["n_layers"] * sizes["n_heads"] * sizes["head_dim"] \
        * ctx_tokens


def decode_step(sizes, mixer, n_active, ctx_tokens) -> float:
    """One decode step: n_active tokens, logits for each."""
    return n_active * (dense_per_token(sizes, mixer) + head_per_token(sizes)) \
        + attention(sizes, mixer, ctx_tokens)


def prefill(sizes, mixer, prompt_len) -> float:
    """A whole prompt: every token through the stack, causal attention
    (sum of 1..P keys), logits for the last token only."""
    pairs = prompt_len * (prompt_len + 1) / 2.0
    return prompt_len * dense_per_token(sizes, mixer) \
        + attention(sizes, mixer, pairs) + head_per_token(sizes)
