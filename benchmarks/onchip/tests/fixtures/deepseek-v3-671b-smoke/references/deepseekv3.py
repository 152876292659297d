"""What a configuration of deepseek-v3-671b-smoke brings beside its file
of sizes: init rules for the latent attention's projections and norms and
the router, which the shared table lacks, and its model FLOPs.  The first
layer is MLA with a dense SwiGLU MLP of HEAD_D_FF; every later layer is
MLA with top_k routed experts and n_shared shared ones; the output table
is untied.  Latent attention is counted in its plain form: queries and
keys of nope + rope dims, values of v_head_dim, per head."""
from __future__ import annotations

from benchmarks.onchip.weights import FanIn

INIT = {"router": FanIn((-2,)), "q_norm": 1.0, "kv_norm": 1.0,
        "w_dq": FanIn((-2,)), "w_dkv": FanIn((-2,)), "w_kr": FanIn((-2,)),
        "w_uq": FanIn((-3,)), "w_ukv": FanIn((-3,))}

# the leading dense layers' MLP width, which the preset keeps as published
HEAD_D_FF = 18432
HEAD_LAYERS = 1


def _mla(s):
    d, H = s["d_model"], s["n_heads"]
    q, kv = s["mla.q_lora_rank"], s["mla.kv_lora_rank"]
    nope, rope, v = (s["mla.qk_nope_head_dim"], s["mla.qk_rope_head_dim"],
                     s["mla.v_head_dim"])
    return 2 * (d * q + q * H * (nope + rope) + d * (kv + rope)
                + kv * H * (nope + v) + H * v * d)


def _per_token(s):
    d = s["d_model"]
    experts = s["moe.top_k"] + s["moe.n_shared"]
    moe = 2 * d * s["moe.n_experts"] + experts * 6 * d * s["moe.d_ff_expert"]
    return float(s["n_layers"] * _mla(s) + HEAD_LAYERS * 6 * d * HEAD_D_FF
                 + (s["n_layers"] - HEAD_LAYERS) * moe)


def _attention(s, pairs):
    per_pair = 2 * s["n_heads"] * (s["mla.qk_nope_head_dim"]
                                   + s["mla.qk_rope_head_dim"]
                                   + s["mla.v_head_dim"])
    return float(s["n_layers"] * per_pair) * pairs


def prefill_flops(sizes, prompt_len):
    """A whole prompt: causal attention, logits for the last token."""
    pairs = prompt_len * (prompt_len + 1) / 2.0
    return prompt_len * _per_token(sizes) + _attention(sizes, pairs) \
        + 2.0 * sizes["d_model"] * sizes["vocab"]


def decode_flops(sizes, n_active, ctx_tokens):
    """One decode step of n_active tokens over ctx_tokens live keys."""
    return n_active * (_per_token(sizes) + 2.0 * sizes["d_model"]
                       * sizes["vocab"]) + _attention(sizes, ctx_tokens)
