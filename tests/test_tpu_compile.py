"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The chip's compiler is installed here and compiles for a chip that is
described, not attached, so these tests run on the CPU: they catch block
shapes and in-kernel indexing that interpret mode accepts and the chip
refuses.  Widths are those ``chip_smoke.py`` serves (smollm-360m and
minimalist-lm-360m) and the paper's 64-wide sMNIST network.  Each test
asserts the kernel lands in the compiled module as a ``tpu_custom_call``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dispatch import tpu_kernels
from repro.kernels.linear_scan.linear_scan import linear_scan_pallas
from repro.kernels.minimalist_block.minimalist_block import (
    minimalist_block_pallas, minimalist_step_pallas)
from repro.kernels.paged_attention.paged_attention import (
    paged_gqa_fwd, paged_gqa_fwd_q8, paged_mla_fwd, paged_mla_fwd_q8)

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
# smollm-360m decode under chip_smoke: 16 slots, 15 heads over 5 KV heads
# of 64, 16-token pages, max_len 2048
B, H, KV, HD, PS, L = 16, 15, 5, 64, 16, 2048
P = B * L // PS


@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on one chip of a described v5e, with the
    persistent compile cache off (its entries for a described chip cannot
    be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_kernels(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return tpu_kernels(jax.jit(fn).lower(*args).compile().as_text())


@pytest.mark.parametrize("window", [None, 1024])
def test_paged_gqa_compiles(chip, window):
    length = window or L
    fn = lambda q, k, v, bt, pos: paged_gqa_fwd(  # noqa: E731
        q, k, v, bt, pos, length=length, interpret=False)
    assert "paged_gqa_decode" in _compiled_kernels(
        chip, fn, ((B, H, HD), BF16), ((P, PS, KV, HD), BF16),
        ((P, PS, KV, HD), BF16), ((B, L // PS), I32), ((B,), I32))


def test_paged_gqa_q8_compiles(chip):
    fn = lambda q, k, v, ks, vs, bt, pos: paged_gqa_fwd_q8(  # noqa: E731
        q, k, v, ks, vs, bt, pos, length=L, interpret=False)
    assert "paged_gqa_decode_q8" in _compiled_kernels(
        chip, fn, ((B, H, HD), BF16), ((P, PS, KV, HD), I8),
        ((P, PS, KV, HD), I8), ((P, KV), F32), ((P, KV), F32),
        ((B, L // PS), I32), ((B,), I32))


@pytest.mark.parametrize("q8", [False, True])
def test_paged_mla_compiles(chip, q8):
    # DeepSeek-V3 latent widths: kv_lora_rank 512, rope dim 64
    Hm, r, dr = 16, 512, 64
    pool = I8 if q8 else BF16
    shapes = [((B, Hm, r), BF16), ((B, Hm, dr), BF16), ((P, PS, r), pool),
              ((P, PS, dr), pool)]
    if q8:
        shapes += [((P,), F32), ((P,), F32)]
        fn = lambda qa, qr, c, k, cs, ks, bt, pos: paged_mla_fwd_q8(  # noqa
            qa, qr, c, k, cs, ks, bt, pos, length=L, scale=0.1,
            interpret=False)
    else:
        fn = lambda qa, qr, c, k, bt, pos: paged_mla_fwd(  # noqa: E731
            qa, qr, c, k, bt, pos, length=L, scale=0.1, interpret=False)
    shapes += [((B, L // PS), I32), ((B,), I32)]
    name = "paged_mla_decode_q8" if q8 else "paged_mla_decode"
    assert name in _compiled_kernels(chip, fn, *shapes)


@pytest.mark.parametrize("batch,T,D,tblk,dtype", [
    (1, 256, 1024, 256, BF16),   # minimalist-lm-360m prefill chunk (d 960)
    (16, 256, 1024, 256, F32),
    (3, 7, 128, 7, BF16),        # a chunk shorter than one sublane tile
])
def test_linear_scan_compiles(chip, batch, T, D, tblk, dtype):
    fn = lambda a, b, h0: linear_scan_pallas(  # noqa: E731
        a, b, h0, tblk=tblk, dblk=min(256, D), interpret=False)
    assert "linear_scan" in _compiled_kernels(
        chip, fn, ((batch, T, D), dtype), ((batch, T, D), dtype),
        ((batch, D), dtype))


# the paper's sMNIST network: dims 1-64-64-64-64-10 over 784 time steps
@pytest.mark.parametrize("K,N", [(1, 64), (64, 64), (64, 10)])
def test_minimalist_step_compiles(chip, K, N):
    fn = lambda x, ch, cz, bh, bz, h: minimalist_step_pallas(  # noqa: E731
        x, ch, cz, 0.1, bh, bz, h, nblk=N, interpret=False)
    assert "minimalist_step" in _compiled_kernels(
        chip, fn, ((B, K), F32), ((K, N), I8), ((K, N), I8), ((N,), F32),
        ((N,), F32), ((B, N), F32))


@pytest.mark.parametrize("K,N,dtype", [(1, 64, BF16), (64, 64, F32),
                                       (64, 10, F32)])
def test_minimalist_block_compiles(chip, K, N, dtype):
    fn = lambda x, ch, cz, bh, bz, h: minimalist_block_pallas(  # noqa
        x, ch, cz, 0.1, bh, bz, h, tblk=16, nblk=N, interpret=False)
    assert "minimalist_block" in _compiled_kernels(
        chip, fn, ((B, 784, K), dtype), ((K, N), I8), ((K, N), I8),
        ((N,), F32), ((N,), F32), ((B, N), F32))
