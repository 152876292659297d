"""Kernel cost functions and model FLOPs against hand counts."""
import pytest

from benchmarks.onchip import flops, spec


def test_paged_gqa_decode_one_shape():
    # 2 slots with 10 and 6 live tokens, 1 layer, 4 heads on 2 KV heads
    # of 8: per key and head, QK^T and PV take 2 * 8 flops each
    f, b = spec.kernel_cost("paged_gqa_decode")(
        ctx_tokens=16, n_active=2, n_layers=1, n_heads=4, n_kv_heads=2,
        head_dim=8)
    assert f == 16 * 4 * (2 * 8 + 2 * 8)
    # K and V of 16 tokens x 2 heads x 8 x 2 bytes, q and out of 2 slots
    assert b == 16 * 2 * 8 * 2 * 2 + 2 * 4 * 8 * 2 * 2


def test_linear_scan_one_shape():
    # 100 tokens x 4 channels x 3 layers: an FMA each; a, b, h in bf16
    f, b = spec.kernel_cost("linear_scan")(tokens=100, rows=1, width=4,
                                           n_layers=3)
    assert f == 3 * 100 * 4 * 2
    assert b == 3 * (100 * 4 * 3 * 2 + 1 * 4 * 2 * 2)


def test_model_flops_smollm360m():
    s = spec.load_cell("smollm360m.chat").config["sizes"]
    d, f = 960, 2560
    attn = 2 * d * (15 + 10) * 64 + 2 * 15 * 64 * d
    per_tok = 32 * (6 * d * f + attn)
    assert flops.dense_per_token(s, "attn") == per_tok
    # about 2 x 316M non-embedding parameters
    assert flops.dense_per_token(s, "attn") == pytest.approx(2 * 316e6,
                                                             rel=0.02)
    assert flops.decode_step(s, "attn", 2, 10) == \
        2 * (per_tok + 2 * d * 49152) + 4 * 32 * 15 * 64 * 10
    assert flops.prefill(s, "attn", 3) == \
        3 * per_tok + 4 * 32 * 15 * 64 * 6 + 2 * d * 49152


def test_model_flops_mingru360m():
    s = spec.load_cell("mingru360m.longdoc").config["sizes"]
    d = 960
    assert flops.dense_per_token(s, "mingru") == \
        32 * (6 * d * 2560 + 4 * d * d + 4 * d)
    assert flops.attention(s, "mingru", 1e6) == 0.0
