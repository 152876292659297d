"""Architecture configuration schema.

One ``ModelConfig`` describes any architecture in the assigned pool: dense /
MoE decoder LMs, MLA, sliding-window patterns, Mamba/hybrid stacks, the
Whisper encoder-decoder backbone, the LLaVA VLM backbone, and the paper's
minGRU time-mixing blocks.  Per-layer heterogeneity (Jamba 1:7, Gemma-3 5:1
local:global, DeepSeek first-k-dense) is expressed as a repeating
``pattern`` of LayerSpec entries plus optional head/tail layers; the model
stack scans over pattern repeats so HLO size stays O(|pattern|).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

# Block kinds
ATTN = "attn"            # global self-attention (GQA)
ATTN_LOCAL = "attn_local"  # sliding-window self-attention
MLA = "mla"              # DeepSeek multi-head latent attention
MAMBA = "mamba"          # Mamba-1 selective SSM
MINGRU = "mingru"        # paper's minGRU time-mixing block


#: Legal values of :attr:`MoEConfig.dispatch`.
MOE_DISPATCH_MODES = ("pooled", "per_request", "auto")

#: Legal values of :attr:`ModelConfig.paged_impl`.
PAGED_IMPLS = ("gather", "pallas", "pallas_tpu")

#: Legal values of :attr:`ModelConfig.kv_dtype`.
KV_DTYPES = ("bf16", "int8")


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    router_noise: float = 0.0
    # dispatch groups (typically = DP degree): scatter/gather stay local to
    # each group's shard; only the combine's partial-sum crosses the mesh
    # (§Perf cell B). groups=1 reproduces single-pool dispatch.
    groups: int = 1
    # How tokens reach their experts (see models.moe):
    #   "pooled"      — every token of a call shares one capacity-limited
    #                   dispatch (Switch-style drops, EP sharding, aux loss;
    #                   the training semantics).  Routing depends on the
    #                   co-batched tokens, so served outputs vary with
    #                   concurrent traffic and prefill chunking.
    #   "per_request" — tokens are grouped by request (batch row) at the
    #                   drop-free capacity bound: routing is pure per-token
    #                   top-k, independent of neighbors and of chunking.
    #   "auto"        — training keeps "pooled"; serving prefill uses
    #                   "per_request" and the slot-batch decode step uses
    #                   the capacity-free gather-GEMM path.  This is the
    #                   default: training semantics are untouched while
    #                   serving becomes batch-invariant.
    dispatch: str = "auto"

    def __post_init__(self):
        if self.dispatch not in MOE_DISPATCH_MODES:
            raise ValueError(
                f"dispatch must be one of {MOE_DISPATCH_MODES}, "
                f"got {self.dispatch!r}")
        if self.groups < 1:
            raise ValueError(f"groups must be >= 1, got {self.groups}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(
                f"top_k must be in [1, n_experts={self.n_experts}], "
                f"got {self.top_k}")


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str = ATTN          # one of the block kinds above
    moe: bool = False         # MoE MLP instead of dense MLP
    d_ff: Optional[int] = None  # dense-MLP width override (DeepSeek head)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_layers: int
    vocab: int
    # attention geometry
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    # layer structure: n_head_layers of head_pattern, then pattern repeated,
    # then tail. len(head) + repeats*len(pattern) + len(tail) == n_layers.
    pattern: Sequence[LayerSpec] = (LayerSpec(),)
    head_layers: Sequence[LayerSpec] = ()
    tail_layers: Sequence[LayerSpec] = ()
    # sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[MambaConfig] = None
    # attention details
    sliding_window: int = 4096
    rope_theta: float = 1e4
    # model kind: "decoder" | "encdec" | "vlm" | "audio"
    arch_type: str = "decoder"
    # enc-dec: encoder geometry (defaults mirror decoder)
    n_enc_layers: int = 0
    # vlm/audio stub frontend: inputs are precomputed embeddings of this dim
    frontend_embed_dim: int = 0
    frontend_seq: int = 0       # e.g. 1500 whisper frames / image patches
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    # paper technique hooks
    mingru_quant: str = "float"   # float | quantized | hardware
    # multi-token prediction depth (DeepSeek-V3 MTP); 0 = off
    mtp_depth: int = 0
    # kernel implementations (§Perf hillclimb):
    #   attention_impl: naive | flash (Pallas kernel) | stub (dry-run cost
    #     accounting stand-in — cheap op with correct shapes/grads; the
    #     analytic kernel cost is added by launch.dryrun)
    #   ssm_impl: xla | fused (Pallas kernel) | stub
    attention_impl: str = "naive"
    ssm_impl: str = "xla"
    # linear-scan backend for recurrent mixers (minGRU/Mamba prefill):
    #   seq | xla | pallas (compiled on TPU, interpreted elsewhere) |
    #   pallas_tpu (compiled unconditionally)
    scan_backend: str = "xla"
    # paged-KV decode attention read (serving, kv_layout="paged"):
    #   pallas     — kernels.paged_attention block-table kernel, platform-
    #                adaptive: interpret mode off-TPU, compiled on TPU.
    #                The DEFAULT fast path: no dense-view materialization;
    #                fp32 online softmax, within the pinned per-family
    #                tolerance of gather, not bitwise (README §Paged KV)
    #   pallas_tpu — same kernel, compiled unconditionally (fails off-TPU)
    #   gather     — block-table gather to a dense view + the exact dense
    #                decode math (bitwise-identical to the dense cache;
    #                the oracle the kernels are pinned against)
    paged_impl: str = "pallas"
    # paged KV-pool storage dtype (serving, kv_layout="paged"):
    #   bf16 — pages stored in the model dtype (bitwise-dense gather math)
    #   int8 — symmetric per-page quantized codes + float32 scales per
    #          page per KV head (kernels.paged_attention.quant); halves
    #          pool bytes so ~2x the concurrent requests fit a fixed pool
    kv_dtype: str = "bf16"
    # explicit sharding constraints on MoE dispatch buffers (cell B fix)
    moe_constraints: bool = False

    def __post_init__(self):
        if self.paged_impl not in PAGED_IMPLS:
            raise ValueError(
                f"paged_impl must be one of {PAGED_IMPLS}, "
                f"got {self.paged_impl!r}")
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, "
                f"got {self.kv_dtype!r}")

    # ---- derived ----
    def layer_specs(self) -> list:
        n_rep = (self.n_layers - len(self.head_layers) - len(self.tail_layers))
        assert n_rep % len(self.pattern) == 0, (
            f"{self.name}: {self.n_layers} layers do not decompose into "
            f"head({len(self.head_layers)}) + k*pattern({len(self.pattern)}) "
            f"+ tail({len(self.tail_layers)})")
        reps = n_rep // len(self.pattern)
        return list(self.head_layers) + list(self.pattern) * reps + list(self.tail_layers)

    @property
    def n_repeats(self) -> int:
        return (self.n_layers - len(self.head_layers) - len(self.tail_layers)) \
            // len(self.pattern)

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to a multiple of 512 (shardable over any mesh
        axis ≤ 512; Megatron-style padding, logits masked at the loss)."""
        return (self.vocab + 511) // 512 * 512

    def param_count(self) -> int:
        """Analytical parameter count (for 6·N·D model-FLOPs estimates)."""
        d = self.d_model
        total = self.vocab_padded * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_padded * d
        for spec in self.layer_specs():
            if spec.kind in (ATTN, ATTN_LOCAL):
                total += d * self.n_heads * self.head_dim      # q
                total += 2 * d * self.n_kv_heads * self.head_dim  # k, v
                total += self.n_heads * self.head_dim * d      # o
            elif spec.kind == MLA:
                m = self.mla
                qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
                total += d * m.q_lora_rank + m.q_lora_rank * self.n_heads * qk_head
                total += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                total += m.kv_lora_rank * self.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
                total += self.n_heads * m.v_head_dim * d
            elif spec.kind == MAMBA:
                mc = self.mamba
                di = mc.d_inner(d)
                total += d * 2 * di                  # in_proj
                total += di * mc.d_conv              # conv
                total += di * (2 * mc.d_state + 1)   # B, C, dt proj (approx)
                total += di * mc.d_state + di        # A_log, D
                total += di * d                      # out_proj
            elif spec.kind == MINGRU:
                total += 2 * (d * d + d)             # W^h, W^z + biases
            # MLP follows ANY mixer kind when configured (Jamba puts MoE
            # after Mamba layers too) — mirrors models.transformer exactly
            if spec.moe:
                e = self.moe
                total += d * e.n_experts              # router
                total += e.n_experts * 3 * d * e.d_ff_expert
                total += e.n_shared * 3 * d * e.d_ff_expert
            else:
                ff = spec.d_ff or self.d_ff
                total += 3 * d * ff                   # SwiGLU
            total += 2 * d                            # norms
        return int(total)

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top-k + shared only)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        total = self.param_count()
        e = self.moe
        n_moe_layers = sum(1 for s in self.layer_specs() if s.moe)
        total -= n_moe_layers * e.n_experts * 3 * d * e.d_ff_expert
        total += n_moe_layers * (e.top_k + e.n_shared) * 3 * d * e.d_ff_expert
        return int(total)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Continuous-batching serving knobs (consumed by
    repro.launch.serve.build_engine)."""
    slots: int = 8            # fixed slot-batch capacity (jit shape)
    max_len: int = 256        # cache length for attention-bearing stacks
    prefill_chunk: int = 256  # chunked-prefill chunk size (tokens)
    # KV-cache layout for attention-bearing stacks (README §Paged KV):
    #   "dense" — every slot preallocates (max_len, ...) cache rows
    #   "paged" — a shared page pool + per-request block tables; memory
    #             scales with live tokens, not slots × worst case
    kv_layout: str = "dense"
    page_size: int = 16       # tokens per KV page (paged layout)
    num_pages: int = 0        # pool capacity; 0 = auto (dense-equivalent)
    # hash-keyed prompt-prefix reuse (paged layout only): requests whose
    # page-aligned prompt prefix is resident attach to the existing
    # pages and prefill only the tail (README §Prefix caching)
    prefix_cache: bool = False
    # admission/preemption policy (repro.serve.scheduler.POLICIES):
    #   "fifo"     — strict arrival order, defer-at-head (the historical
    #                behavior, byte for byte); never preempts
    #   "priority" — higher submit(priority=...) first; may evict a
    #                strictly-lower-priority running request when a
    #                high-priority arrival is blocked (paged layout)
    #   "sjf"      — shortest-prefill-first with aging (README
    #                §Scheduling & preemption)
    #   "edf"      — earliest submit(deadline=...) first; may evict a
    #                strictly-later-deadline running request (paged)
    policy: str = "fifo"
    # speculative decoding (README §Speculative decoding): ``drafter``
    # names the draft arch (a pure O(1)-state stack, e.g.
    # "minimalist-lm-360m-smoke"); ``spec_k`` is the verify width — the
    # target scores spec_k positions per wave and commits the accepted
    # prefix.  spec_k == 1 (the default) is plain decode.
    spec_k: int = 1
    drafter: str = ""

    def __post_init__(self):
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.spec_k > 1 and not self.drafter:
            raise ValueError(
                f"spec_k={self.spec_k} needs a drafter — name a pure "
                "O(1)-state arch (ServeConfig.drafter) to propose the "
                "speculative tokens")
        if self.drafter and self.kv_layout != "paged":
            raise ValueError(
                "speculative decoding needs kv_layout='paged': rollback "
                "relies on uncommitted pages (the pool never holds a "
                f"rejected token), got kv_layout={self.kv_layout!r}")
        if self.drafter and self.prefix_cache:
            raise ValueError(
                "speculative decoding and prefix_cache are mutually "
                "exclusive (singleton admission waves would serialize "
                "the drafter's wave prefill; lift when needed)")


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding knobs (see repro.serve.sampling).

    The defaults are greedy argmax.  ``seed`` is folded with the request
    uid and the absolute token position into a counter-based PRNG key, so
    a request's tokens are bitwise reproducible regardless of co-batched
    traffic; knobs travel as per-slot ARRAYS through the one jitted
    decode step, never as retrace-triggering constants.
    """
    temperature: float = 0.0  # <= 0 means greedy
    top_k: int = 0            # 0 disables
    top_p: float = 1.0        # >= 1 disables; else minimal nucleus
    seed: int = 0

    def validate(self):
        """Bounds match the per-slot knob dtypes (serve.sampling): values
        outside them would overflow the slot arrays at admission time."""
        if not self.temperature >= 0:          # NaN fails this too
            raise ValueError("temperature must be >= 0 and not NaN")
        if not 0 <= self.top_k <= 2**31 - 1:
            raise ValueError("top_k must be in [0, 2**31)")
        if self.top_p <= 0:
            raise ValueError("top_p must be > 0 (>= 1 disables the filter)")
        if not 0 <= self.seed <= 2**32 - 1:
            raise ValueError("seed must be a uint32 (in [0, 2**32))")
        return self


# The four assigned input-shape regimes
SHAPES = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}
