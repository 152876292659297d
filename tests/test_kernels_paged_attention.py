"""Paged-attention decode kernels: Pallas (interpret) vs dense-gather ref,
page-indirection semantics (chain permutation / stale-page immunity),
equivalence against the dense decode attention they emulate, the int8
per-page-scale kernel variants, and the traffic cost models.

The TOLERANCE CONTRACT lives here: ``paged_impl="pallas"`` is the
serving default, and its per-family max-abs deviation from the
``gather`` oracle is pinned below (both paths are fp32; the kernel's
online softmax reassociates the reduction, the oracle subtracts one
global max — measured worst case is ~4e-7 across page sizes and ragged
chains, pinned at 5x headroom).  The int8 variants are pinned against
the DEQUANTIZED oracle with the same bound: kernel and oracle dequantize
the identical codes with the identical scales, so quantization error
cancels and only the softmax reassociation remains."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import ops, quant, ref
from repro.kernels.paged_attention import paged_attention as pa

# pallas-vs-gather max |err| bound, per attention family (see module
# docstring; README "Paged KV cache" documents the same numbers)
PALLAS_TOL = {"gqa_global": 2e-6, "gqa_window": 2e-6, "mla": 2e-6}


def _chains(rng, B, n_chain, num_pages):
    """Disjoint random page chains (one per request), like the pool's."""
    ids = rng.permutation(num_pages)[:B * n_chain]
    return ids.reshape(B, n_chain).astype(np.int32)


def _scatter_dense(pool, bt, dense):
    """Write each request's dense cache rows into its page chain."""
    P, ps = pool.shape[:2]
    out = np.array(pool)
    B, L = dense.shape[:2]
    for b in range(B):
        for j in range(L):
            out[bt[b, j // ps], j % ps] = dense[b, j]
    return out


def _setup_gqa(rng, *, B=3, H=4, KV=2, hd=16, L=24, ps=8, num_pages=32):
    n_chain = -(-L // ps)
    q = rng.standard_normal((B, H, hd)).astype(np.float32)
    dense_k = rng.standard_normal((B, L, KV, hd)).astype(np.float32)
    dense_v = rng.standard_normal((B, L, KV, hd)).astype(np.float32)
    bt = _chains(rng, B, n_chain, num_pages)
    # unowned pages hold garbage — they must never matter
    pool_k = _scatter_dense(
        rng.standard_normal((num_pages, ps, KV, hd)).astype(np.float32) * 50,
        bt, dense_k)
    pool_v = _scatter_dense(
        rng.standard_normal((num_pages, ps, KV, hd)).astype(np.float32) * 50,
        bt, dense_v)
    pos = rng.integers(0, L, size=B).astype(np.int32)
    return q, dense_k, dense_v, pool_k, pool_v, bt, pos


def _dense_gqa(q, dense_k, dense_v, pos, *, window=None):
    """Masked softmax attention over the dense cache (fp32), the oracle."""
    B, H, hd = q.shape
    KV = dense_k.shape[2]
    L = dense_k.shape[1]
    idx = np.arange(L)
    if window is None:
        k_pos = np.broadcast_to(idx, (B, L))
    else:
        k_pos = pos[:, None] - ((pos[:, None] - idx[None, :]) % L)
    valid = (k_pos >= 0) & (k_pos <= pos[:, None])
    if window is not None:
        valid &= (pos[:, None] - k_pos) < window
    qg = q.reshape(B, KV, H // KV, hd)
    s = np.einsum("bkgd,blkd->bkgl", qg, dense_k) / math.sqrt(hd)
    s = np.where(valid[:, None, None, :], s, -1e30)
    s = s - s.max(-1, keepdims=True)
    w = np.exp(s)
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bkgl,blkd->bkgd", w, dense_v).reshape(B, H, hd)


@pytest.mark.parametrize("window", [None, 5])
def test_ref_matches_dense_oracle(window):
    rng = np.random.default_rng(0)
    L = 24 if window is None else 5        # ring length = min(window, L)
    q, dk, dv, pk, pv, bt, pos = _setup_gqa(rng, L=L, ps=4)
    got = ops.paged_gqa_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(bt),
        jnp.asarray(pos), length=L, window=window, backend="xla")
    np.testing.assert_allclose(np.asarray(got),
                               _dense_gqa(q, dk, dv, pos, window=window),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [None, 7])
@pytest.mark.parametrize("ps", [4, 8])
def test_pallas_matches_ref_gqa(window, ps):
    rng = np.random.default_rng(1)
    L = 24 if window is None else 7
    q, _dk, _dv, pk, pv, bt, pos = _setup_gqa(rng, L=L, ps=ps)
    args = (jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(bt), jnp.asarray(pos))
    want = ops.paged_gqa_attention(*args, length=L, window=window,
                                   backend="xla")
    got = ops.paged_gqa_attention(*args, length=L, window=window,
                                  backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_chain_permutation_invariance():
    """WHERE a chain's pages live in the pool is irrelevant: permuting
    the page ids (and moving the contents along) leaves the output
    bitwise unchanged."""
    rng = np.random.default_rng(2)
    q, dk, dv, _pk, _pv, bt, pos = _setup_gqa(rng, L=16, ps=4,
                                              num_pages=32)
    perm = rng.permutation(32)
    bt2 = perm[bt].astype(np.int32)
    outs = []
    for table in (bt, bt2):
        pool_k = _scatter_dense(np.zeros((32, 4, 2, 16), np.float32),
                                table, dk)
        pool_v = _scatter_dense(np.zeros((32, 4, 2, 16), np.float32),
                                table, dv)
        for backend in ("xla", "pallas"):
            outs.append(np.asarray(ops.paged_gqa_attention(
                jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
                jnp.asarray(table), jnp.asarray(pos), length=16,
                backend=backend)))
    np.testing.assert_array_equal(outs[0], outs[2])   # xla: bt == bt2
    np.testing.assert_array_equal(outs[1], outs[3])   # pallas: bt == bt2


def test_stale_pages_and_unallocated_entries_ignored():
    """Garbage in unowned pages and in block-table entries beyond the
    live position must contribute exactly nothing (the engine's page
    recycling correctness property)."""
    rng = np.random.default_rng(3)
    q, dk, dv, pk, pv, bt, pos = _setup_gqa(rng, L=24, ps=8)
    pos = np.minimum(pos, 7)               # only chain entry 0 is live
    clean_k = _scatter_dense(np.zeros_like(pk), bt, dk)
    clean_v = _scatter_dense(np.zeros_like(pv), bt, dv)
    # poison every unallocated block-table entry with a foreign page id
    bt_poison = np.array(bt)
    bt_poison[:, 1:] = (bt[:, 1:] + 1) % pk.shape[0]
    for backend in ("xla", "pallas"):
        a = np.asarray(ops.paged_gqa_attention(
            jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(bt), jnp.asarray(pos), length=24, backend=backend))
        b = np.asarray(ops.paged_gqa_attention(
            jnp.asarray(q), jnp.asarray(clean_k), jnp.asarray(clean_v),
            jnp.asarray(bt_poison), jnp.asarray(pos), length=24,
            backend=backend))
        np.testing.assert_array_equal(a, b)


def test_pallas_matches_ref_mla():
    rng = np.random.default_rng(4)
    B, H, r, dr, L, ps, num_pages = 3, 4, 16, 8, 20, 4, 16
    n_chain = -(-L // ps)
    q_abs = rng.standard_normal((B, H, r)).astype(np.float32)
    q_rope = rng.standard_normal((B, H, dr)).astype(np.float32)
    dense_c = rng.standard_normal((B, L, r)).astype(np.float32)
    dense_r = rng.standard_normal((B, L, dr)).astype(np.float32)
    bt = _chains(rng, B, n_chain, num_pages)
    pool_c = _scatter_dense(
        rng.standard_normal((num_pages, ps, r)).astype(np.float32) * 50,
        bt, dense_c)
    pool_r = _scatter_dense(
        rng.standard_normal((num_pages, ps, dr)).astype(np.float32) * 50,
        bt, dense_r)
    pos = rng.integers(0, L, size=B).astype(np.int32)
    scale = 1.0 / math.sqrt(r + dr)
    args = (jnp.asarray(q_abs), jnp.asarray(q_rope), jnp.asarray(pool_c),
            jnp.asarray(pool_r), jnp.asarray(bt), jnp.asarray(pos))
    want = ops.paged_mla_attention(*args, length=L, scale=scale,
                                   backend="xla")
    got = ops.paged_mla_attention(*args, length=L, scale=scale,
                                  backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # the ref itself against a straight dense MLA softmax
    s = (np.einsum("bhr,blr->bhl", q_abs, dense_c)
         + np.einsum("bhk,blk->bhl", q_rope, dense_r)) * scale
    s = np.where(np.arange(L)[None, None] <= pos[:, None, None], s, -1e30)
    s = s - s.max(-1, keepdims=True)
    w = np.exp(s)
    w /= w.sum(-1, keepdims=True)
    oracle = np.einsum("bhl,blr->bhr", w, dense_c)
    np.testing.assert_allclose(np.asarray(want), oracle, atol=1e-5,
                               rtol=1e-5)


def test_bad_backend_and_ring_length_rejected():
    z = jnp.zeros((1, 2, 4))
    pool = jnp.zeros((2, 2, 1, 4))
    bt = jnp.zeros((1, 1), jnp.int32)
    pos = jnp.zeros(1, jnp.int32)
    with pytest.raises(ValueError, match="backend"):
        ops.paged_gqa_attention(z, pool, pool, bt, pos, length=2,
                                backend="cuda")
    with pytest.raises(ValueError, match="ring length"):
        ops.paged_gqa_attention(z, pool, pool, bt, pos, length=4, window=2)


def test_page_gather_helper():
    """gather_pages reconstructs the dense view exactly."""
    rng = np.random.default_rng(5)
    pool = rng.standard_normal((8, 4, 3)).astype(np.float32)
    bt = np.array([[6, 1, 3], [0, 7, 2]], np.int32)
    got = np.asarray(ref.gather_pages(jnp.asarray(pool), jnp.asarray(bt),
                                      10))
    for b in range(2):
        for j in range(10):
            np.testing.assert_array_equal(got[b, j],
                                          pool[bt[b, j // 4], j % 4])


def test_gather_dequant_helper():
    """gather_dequant == dequantize-whole-pool + gather_pages: each
    gathered row carries ITS page's scale."""
    rng = np.random.default_rng(6)
    pool = rng.integers(-127, 128, size=(8, 4, 2, 3)).astype(np.int8)
    sc = (rng.random((8, 2)) + 0.1).astype(np.float32)
    bt = np.array([[6, 1, 3], [0, 7, 2]], np.int32)
    got = np.asarray(ref.gather_dequant(jnp.asarray(pool), jnp.asarray(sc),
                                        jnp.asarray(bt), 10))
    dense_pool = pool.astype(np.float32) * sc[:, None, :, None]
    want = np.asarray(ref.gather_pages(jnp.asarray(dense_pool),
                                       jnp.asarray(bt), 10))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# tolerance contract: the default pallas path vs the gather oracle,
# swept over page sizes (incl. ps that doesn't divide the length — ragged
# page ends) and ragged per-request positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 7])
@pytest.mark.parametrize("ps", [2, 4, 5, 8, 16])
def test_tolerance_contract_gqa(window, ps):
    fam = "gqa_global" if window is None else "gqa_window"
    tol = PALLAS_TOL[fam]
    rng = np.random.default_rng(7)
    L = 24 if window is None else 7
    q, _dk, _dv, pk, pv, bt, pos = _setup_gqa(rng, L=L, ps=ps,
                                              num_pages=64)
    args = (jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(bt), jnp.asarray(pos))
    want = ops.paged_gqa_attention(*args, length=L, window=window,
                                   backend="xla")
    got = ops.paged_gqa_attention(*args, length=L, window=window,
                                  backend="pallas")
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    assert err <= tol, f"{fam} ps={ps}: |err|={err:.3e} > pinned {tol:.0e}"


def _setup_mla(rng, *, B=3, H=4, r=16, dr=8, L=20, ps=4, num_pages=48):
    n_chain = -(-L // ps)
    q_abs = rng.standard_normal((B, H, r)).astype(np.float32)
    q_rope = rng.standard_normal((B, H, dr)).astype(np.float32)
    dense_c = rng.standard_normal((B, L, r)).astype(np.float32)
    dense_r = rng.standard_normal((B, L, dr)).astype(np.float32)
    bt = _chains(rng, B, n_chain, num_pages)
    pool_c = _scatter_dense(
        rng.standard_normal((num_pages, ps, r)).astype(np.float32) * 50,
        bt, dense_c)
    pool_r = _scatter_dense(
        rng.standard_normal((num_pages, ps, dr)).astype(np.float32) * 50,
        bt, dense_r)
    pos = rng.integers(0, L, size=B).astype(np.int32)
    return q_abs, q_rope, pool_c, pool_r, bt, pos


@pytest.mark.parametrize("ps", [2, 4, 5, 8])
def test_tolerance_contract_mla(ps):
    tol = PALLAS_TOL["mla"]
    rng = np.random.default_rng(8)
    L, r, dr = 20, 16, 8
    qa, qr, pc, pr, bt, pos = _setup_mla(rng, L=L, ps=ps)
    scale = 1.0 / math.sqrt(r + dr)
    args = (jnp.asarray(qa), jnp.asarray(qr), jnp.asarray(pc),
            jnp.asarray(pr), jnp.asarray(bt), jnp.asarray(pos))
    want = ops.paged_mla_attention(*args, length=L, scale=scale,
                                   backend="xla")
    got = ops.paged_mla_attention(*args, length=L, scale=scale,
                                  backend="pallas")
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    assert err <= tol, f"mla ps={ps}: |err|={err:.3e} > pinned {tol:.0e}"


# ---------------------------------------------------------------------------
# int8 per-page-scale kernel variants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 7])
def test_pallas_q8_matches_dequant_oracle_gqa(window):
    """The q8 kernel (in-register dequant) vs the gather oracle over the
    SAME codes+scales: quantization error cancels, only the softmax
    reassociation remains — same pinned bound as the bf16 contract."""
    fam = "gqa_global" if window is None else "gqa_window"
    rng = np.random.default_rng(9)
    L = 24 if window is None else 7
    q, _dk, _dv, pk, pv, bt, pos = _setup_gqa(rng, L=L, ps=4)
    ks = quant.page_abs_scale(jnp.asarray(pk))
    kc = quant.quantize(jnp.asarray(pk), ks)
    vs = quant.page_abs_scale(jnp.asarray(pv))
    vc = quant.quantize(jnp.asarray(pv), vs)
    kw = dict(length=L, window=window, k_scale=ks, v_scale=vs)
    want = ops.paged_gqa_attention(jnp.asarray(q), kc, vc, jnp.asarray(bt),
                                   jnp.asarray(pos), backend="xla", **kw)
    got = ops.paged_gqa_attention(jnp.asarray(q), kc, vc, jnp.asarray(bt),
                                  jnp.asarray(pos), backend="pallas", **kw)
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    assert err <= PALLAS_TOL[fam], err
    # and the dequantized attention tracks the full-precision one at the
    # coarse level 8-bit storage allows (sanity, not the contract)
    full = ops.paged_gqa_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(bt),
        jnp.asarray(pos), length=L, window=window, backend="xla")
    np.testing.assert_allclose(np.asarray(want), np.asarray(full),
                               atol=0.15, rtol=0.15)


def test_pallas_q8_matches_dequant_oracle_mla():
    rng = np.random.default_rng(10)
    L, r, dr = 20, 16, 8
    qa, qr, pc, pr, bt, pos = _setup_mla(rng, L=L)
    scale = 1.0 / math.sqrt(r + dr)
    cs = quant.page_abs_scale(jnp.asarray(pc))
    cc = quant.quantize(jnp.asarray(pc), cs)
    rs = quant.page_abs_scale(jnp.asarray(pr))
    rc = quant.quantize(jnp.asarray(pr), rs)
    kw = dict(length=L, scale=scale, ckv_scale=cs, krope_scale=rs)
    want = ops.paged_mla_attention(jnp.asarray(qa), jnp.asarray(qr), cc,
                                   rc, jnp.asarray(bt), jnp.asarray(pos),
                                   backend="xla", **kw)
    got = ops.paged_mla_attention(jnp.asarray(qa), jnp.asarray(qr), cc,
                                  rc, jnp.asarray(bt), jnp.asarray(pos),
                                  backend="pallas", **kw)
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    assert err <= PALLAS_TOL["mla"], err


def test_scales_must_come_in_pairs():
    rng = np.random.default_rng(11)
    q, _dk, _dv, pk, pv, bt, pos = _setup_gqa(rng, L=8, ps=4)
    ks = quant.page_abs_scale(jnp.asarray(pk))
    with pytest.raises(ValueError, match="k_scale/v_scale"):
        ops.paged_gqa_attention(jnp.asarray(q), jnp.asarray(pk),
                                jnp.asarray(pv), jnp.asarray(bt),
                                jnp.asarray(pos), length=8, k_scale=ks)


# ---------------------------------------------------------------------------
# cost models (the roofline / benchmark bytes accounting)
# ---------------------------------------------------------------------------

def test_cost_model_window_caps_live_tokens():
    """Satellite fix: a sliding-window layer streams at most
    ceil(min(live, window)/ps) pages — the model used to bill the full
    chain, overstating window-layer bytes by live/window."""
    base = ops.cost_model(4, 8, 2, 64, live_tokens=4096, page_size=16,
                          window=128)
    capped = ops.cost_model(4, 8, 2, 64, live_tokens=128, page_size=16)
    assert base == capped                  # (flops, bytes) both capped
    # window larger than the live chain: no cap kicks in
    short = ops.cost_model(4, 8, 2, 64, live_tokens=64, page_size=16,
                           window=128)
    assert short[1] < base[1]


def test_cost_model_int8_and_scale_bytes():
    """int8 pools stream half the KV bytes plus the per-page scale rows;
    q/o stay priced at bf16 (activations are never quantized)."""
    B, H, KV, hd, T, ps = 4, 8, 2, 64, 4096, 16
    bf_f, bf_b = ops.cost_model(B, H, KV, hd, live_tokens=T, page_size=ps)
    q8_f, q8_b = ops.cost_model(B, H, KV, hd, live_tokens=T, page_size=ps,
                                dtype_bytes=1, scale_bytes=4)
    pages = -(-T // ps)
    assert (bf_b - q8_b
            == 2 * B * pages * ps * KV * hd            # kv bytes halved
            - 2 * B * pages * KV * 4)                  # minus scale rows
    assert q8_f == bf_f                    # math is fp32 either way


def test_cost_model_mla_variant():
    """Satellite fix: MLA latent pages stream r+dr rows per token ONCE
    (keys and values share the ckv latents), not the 2x KV-head shape
    the GQA model assumes."""
    B, H, r, dr, T, ps = 4, 16, 512, 64, 4096, 16
    flops, nbytes = ops.cost_model_mla(B, H, r, dr, live_tokens=T,
                                       page_size=ps)
    pages = -(-T // ps)
    kv_bytes = B * pages * ps * (r + dr) * 2
    assert nbytes == (kv_bytes + B * pages * 4
                      + B * H * (r + dr) * 2 + B * H * r * 2)
    assert flops == 2 * B * H * T * (r + dr) + 2 * B * H * T * r
    # int8 + scales
    _q8_f, q8_b = ops.cost_model_mla(B, H, r, dr, live_tokens=T,
                                     page_size=ps, dtype_bytes=1,
                                     scale_bytes=4)
    assert q8_b < nbytes


# ---------------------------------------------------------------------------
# the GQA block walk: chains of several blocks, walks that end at each
# slot's live length, inactive slots, wrapped rings, int8 pools
# ---------------------------------------------------------------------------

# 1040 tokens of 16-token pages: 65 pages, walked in blocks of 32 pages
# (512 tokens), so a full walk is two whole blocks and a partial one
WALK_L, WALK_PS, WALK_KV, WALK_HD = 1040, 16, 2, 16


def _walk_pools(rng, L, *, B=4):
    q, _dk, _dv, pk, pv, bt, _pos = _setup_gqa(
        rng, B=B, H=4, KV=WALK_KV, hd=WALK_HD, L=L, ps=WALK_PS,
        num_pages=B * (-(-L // WALK_PS)) + 8)
    return q, pk, pv, bt


def test_walk_blocks_are_several_and_ragged():
    """The shapes below do walk several blocks, the last one partial."""
    n_pages = -(-WALK_L // WALK_PS)
    ppb = pa.pages_per_block(WALK_PS, WALK_KV, WALK_HD, jnp.float32,
                             n_pages)
    assert (ppb, n_pages % ppb) == (32, 1)
    # a chain shorter than one block is one block of the whole chain
    assert pa.pages_per_block(WALK_PS, WALK_KV, WALK_HD, jnp.float32,
                              5) == 5
    # int8 pages take half the VMEM of bf16 ones, never more tokens
    assert pa.pages_per_block(16, 5, 64, jnp.int8, 128) == 32
    # wide pages are cut to the VMEM budget
    assert pa.pages_per_block(16, 8, 128, jnp.float32, 128) == 16


BT = 512      # tokens of one block at the walk shapes

WALK_CASES = {
    # name: (pos, active, window, int8)
    "ragged": ([37, 1000, 530, 1039], None, None, False),
    "block_edges": ([BT - 1, BT, 0, 2 * BT - 1], None, None, False),
    "one_token_and_full_length": ([0, WALK_L - 1, 0, WALK_L - 1], None,
                                  None, False),
    "inactive_slot": ([700, WALK_L - 1, 3, 900], [True, False, True, False],
                      None, False),
    # ring of 600 entries (38 pages: a block and a partial one), past
    # its wrap in three slots and just filled in one
    "ring_wrapped": ([700, 1500, 599, 100], None, 600, False),
    "int8": ([BT - 1, BT, 0, WALK_L - 1], [True, True, False, True], None,
             True),
}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_block_walk_matches_oracle(case):
    pos, active, window, q8 = WALK_CASES[case]
    fam = "gqa_global" if window is None else "gqa_window"
    rng = np.random.default_rng(12)
    L = window or WALK_L
    q, pk, pv, bt = _walk_pools(rng, L)
    pos = np.asarray(pos, np.int32)
    kw = dict(length=L, window=window)
    pools = (jnp.asarray(pk), jnp.asarray(pv))
    if q8:
        ks = quant.page_abs_scale(pools[0])
        vs = quant.page_abs_scale(pools[1])
        pools = (quant.quantize(pools[0], ks), quant.quantize(pools[1], vs))
        kw.update(k_scale=ks, v_scale=vs)
    args = (jnp.asarray(q),) + pools + (jnp.asarray(bt), jnp.asarray(pos))
    want = np.asarray(ops.paged_gqa_attention(*args, backend="xla", **kw))
    act = None if active is None else jnp.asarray(active)
    got = np.asarray(ops.paged_gqa_attention(*args, backend="pallas",
                                             active=act, **kw))
    live = np.ones(len(pos), bool) if active is None else np.asarray(active)
    err = float(np.max(np.abs(got[live] - want[live])))
    assert err <= PALLAS_TOL[fam], f"{case}: |err|={err:.3e}"
    # an inactive slot walks nothing and reads zeros
    assert not np.any(got[~live])


@pytest.mark.parametrize("q8", [False, True])
def test_dead_blocks_are_never_read(q8):
    """Every page past each slot's live length — and every block-table
    entry past it — holds NaN (int8: NaN scales).  Had the walk copied
    one into the math, its NaN values would reach the output through
    P·V, whatever the score mask did; so active slots' outputs must stay
    bit-for-bit those of clean pools."""
    rng = np.random.default_rng(13)
    q, pk, pv, bt = _walk_pools(rng, WALK_L)
    pos = np.array([0, BT - 1, WALK_L - 1, 700], np.int32)
    active = np.array([True, True, True, False])
    live_pages = np.where(active, -(-(pos + 1) // WALK_PS), 0)
    P = pk.shape[0]
    dead = np.zeros(P, bool)
    bt_nan = np.array(bt)
    spare = np.setdiff1d(np.arange(P), bt)       # pages no chain owns
    for b in range(len(pos)):
        dead[bt[b, live_pages[b]:]] = True
        bt_nan[b, live_pages[b]:] = spare[b % len(spare)]
    dead[spare] = True
    runs = []
    for poisoned in (False, True):
        k, v = jnp.asarray(pk), jnp.asarray(pv)
        kw = {}
        if q8:
            ks, vs = quant.page_abs_scale(k), quant.page_abs_scale(v)
            k, v = quant.quantize(k, ks), quant.quantize(v, vs)
            if poisoned:
                ks = jnp.where(dead[:, None], jnp.nan, ks)
                vs = jnp.where(dead[:, None], jnp.nan, vs)
            kw = dict(k_scale=ks, v_scale=vs)
        elif poisoned:
            k = jnp.where(dead[:, None, None, None], jnp.nan, k)
            v = jnp.where(dead[:, None, None, None], jnp.nan, v)
        table = bt_nan if poisoned else bt
        runs.append(np.asarray(ops.paged_gqa_attention(
            jnp.asarray(q), k, v, jnp.asarray(table), jnp.asarray(pos),
            length=WALK_L, backend="pallas", active=jnp.asarray(active),
            **kw)))
    clean, poisoned = runs
    assert np.all(np.isfinite(clean))
    np.testing.assert_array_equal(poisoned[active], clean[active])
