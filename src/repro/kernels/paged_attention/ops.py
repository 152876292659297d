"""Public paged-attention decode ops + analytic cost models.

``paged_gqa_attention`` / ``paged_mla_attention`` dispatch one
single-token decode read of a paged KV cache:

  * backend "xla"         — dense-gather reference (ref.py): materializes
                            each request's page chain and runs masked
                            softmax attention.  The definitional oracle.
  * backend "pallas"      — the block-table kernel, PLATFORM-ADAPTIVE:
                            interpret mode off-TPU (CPU tests and dev
                            boxes), compiled on TPU.  The default
                            serving path (``ModelConfig.paged_impl``).
  * backend "pallas_tpu"  — compiled unconditionally (fails fast off-TPU;
                            use to guarantee the production lowering).

Passing the per-page scale tensors (``k_scale``/``v_scale`` for GQA,
``ckv_scale``/``krope_scale`` for MLA) selects the int8 read path: the
kernels dequantize in-register (see ``quant``), the oracle dequantizes
up front.  Scales must come as a pair — an int8 pool without its scales
is uninterpretable.

Decode is inference-only, so no custom VJP is defined (the train/prefill
regimes never see a page table).  ``cost_model`` (GQA, window-aware) and
``cost_model_mla`` (latent pages) return the analytic per-call
(flops, hbm_bytes): paged decode is memory-bound — it streams the LIVE
pages once (the dense path would stream slots × max_len regardless of
occupancy), plus q/out, which is the whole point.
"""
from __future__ import annotations

from repro.kernels.dispatch import interpret
from repro.kernels.paged_attention import ref
from repro.kernels.paged_attention.paged_attention import (
    paged_gqa_fwd, paged_gqa_fwd_q8, paged_mla_fwd, paged_mla_fwd_q8)

BACKENDS = ("xla", "pallas", "pallas_tpu")


def _check_backend(backend):
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, "
                         f"got {backend!r}")


def _check_scales(a, b, names):
    if (a is None) != (b is None):
        raise ValueError(f"pass both {names} or neither (int8 pools are "
                         "uninterpretable without their scales)")


def paged_gqa_attention(q, pool_k, pool_v, block_tables, pos, *, length,
                        window=None, backend="xla", k_scale=None,
                        v_scale=None, active=None):
    """q: (B, H, hd); pool_k/v: (P, page, KV, hd) with H % KV == 0;
    block_tables: (B, n_chain) int32 page ids; pos: (B,) -> (B, H, hd).

    ``length`` is the dense cache length being emulated (ring length for
    sliding-window, where it must be <= ``window``).  ``k_scale`` /
    ``v_scale`` (P, KV) float32 select the int8 read path.  ``active``
    (B,) bool lets the kernel skip inactive slots (their rows come back
    zero); the oracle computes every row."""
    _check_backend(backend)
    _check_scales(k_scale, v_scale, "k_scale/v_scale")
    if window is not None and length > window:
        raise ValueError(f"ring length {length} exceeds window {window} "
                         "(pass length = min(window, max_len))")
    if backend == "xla":
        return ref.paged_gqa_ref(q, pool_k, pool_v, block_tables, pos,
                                 length=length, window=window,
                                 k_scale=k_scale, v_scale=v_scale)
    if k_scale is not None:
        return paged_gqa_fwd_q8(q, pool_k, pool_v, k_scale, v_scale,
                                block_tables, pos, active, length=length,
                                interpret=interpret(backend))
    return paged_gqa_fwd(q, pool_k, pool_v, block_tables, pos, active,
                         length=length, interpret=interpret(backend))


def paged_mla_attention(q_abs, q_rope, pool_ckv, pool_krope, block_tables,
                        pos, *, length, scale, backend="xla",
                        ckv_scale=None, krope_scale=None):
    """Weight-absorbed MLA decode over latent pages -> (B, H, r) latent
    output (caller up-projects through W^{UV}).  ``ckv_scale`` /
    ``krope_scale`` (P,) float32 select the int8 read path."""
    _check_backend(backend)
    _check_scales(ckv_scale, krope_scale, "ckv_scale/krope_scale")
    if backend == "xla":
        return ref.paged_mla_ref(q_abs, q_rope, pool_ckv, pool_krope,
                                 block_tables, pos, length=length,
                                 scale=scale, ckv_scale=ckv_scale,
                                 krope_scale=krope_scale)
    if ckv_scale is not None:
        return paged_mla_fwd_q8(q_abs, q_rope, pool_ckv, pool_krope,
                                ckv_scale, krope_scale, block_tables, pos,
                                length=length, scale=scale,
                                interpret=interpret(backend))
    return paged_mla_fwd(q_abs, q_rope, pool_ckv, pool_krope, block_tables,
                         pos, length=length, scale=scale,
                         interpret=interpret(backend))


def cost_model(B, H, KV, hd, *, live_tokens, page_size, dtype_bytes=2,
               window=None, scale_bytes=0):
    """Analytic (flops, hbm_bytes) for one paged GQA decode call.

    flops: 2 matmuls (q·Kᵀ, P·V) over the live tokens = 4·B·H·T·hd.
    hbm_bytes: the LIVE K/V pages streamed once (rounded up to whole
    pages — the page is the DMA granule) + q and out; block tables are
    int32 noise.  That is what the kernel's block walk copies: it stops
    at each slot's live length.  Left out: the pool's layout puts a
    page's KV·hd row on whole 128-lane tiles, so the copies move that
    row rounded up to a multiple of 128 (320 -> 384 at KV 5, hd 64).
    Compare: a dense decode streams slots × max_len K/V regardless of
    how many tokens are actually live.

    A sliding-window ring holds at most ``window`` live entries — its
    page chain is bounded and recycled in place, so both terms cap
    there (the old model overcounted long-context window rows by
    live/window×).  ``dtype_bytes`` prices the POOL dtype (1 for int8);
    q/out are activations and stay in the model dtype (bf16 = 2).  For
    int8 pools pass ``scale_bytes=4`` to charge the per-(page, KV-head)
    float32 scales of each K and V page."""
    live = live_tokens if window is None else min(live_tokens, window)
    pages = -(-live // page_size)
    flops = 4 * B * H * live * hd
    kv = 2 * B * pages * page_size * KV * hd * dtype_bytes
    sc = 2 * B * pages * KV * scale_bytes
    qo = 2 * B * H * hd * 2
    bt = B * pages * 4
    return flops, kv + sc + qo + bt


def cost_model_mla(B, H, r, dr, *, live_tokens, page_size, dtype_bytes=2,
                   scale_bytes=0):
    """Analytic (flops, hbm_bytes) for one paged MLA decode call.

    Latent pages stream (r + dr)-dim ROWS — ckv plus k_rope — not
    KV×hd: bytes are B·pages·ps·(r+dr)·dtype_bytes once (the old GQA
    model had no MLA variant and the roofline rows priced phantom KV
    heads).  flops: scores read both latents (2·B·H·T·(r+dr)) and the
    P·V output contracts over ckv only (2·B·H·T·r).  q_abs/q_rope/out
    stay in the model dtype; ``scale_bytes=4`` adds the two per-page
    float32 scales (ckv, krope) for int8 latent pools."""
    pages = -(-live_tokens // page_size)
    flops = 2 * B * H * live_tokens * (r + dr) + 2 * B * H * live_tokens * r
    kv = B * pages * page_size * (r + dr) * dtype_bytes
    sc = 2 * B * pages * scale_bytes
    qo = B * H * (r + dr) * 2 + B * H * r * 2
    bt = B * pages * 4
    return flops, kv + sc + qo + bt
