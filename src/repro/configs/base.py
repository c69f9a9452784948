"""Config system.

A ``ModelConfig`` fully determines the parameter tree, sharding, and step
functions. Architectures are expressed as a repeating ``block_pattern`` of
(mixer, mlp) layer kinds (plus an optional unrolled ``prefix_pattern``) so the
decoder stack can be lowered as a ``lax.scan`` over stacked blocks — this
keeps the HLO (and compile time / remat behaviour) independent of depth.

The paper's techniques are runtime-selectable through ``AttentionRuntime``:
  mode = dense | decomposed (T1 X-cache) | cpq (T2) | retrieval (T3)
MLA layers (deepseek-v2-lite) always use the absorbed/decomposed path — see
DESIGN.md for why MLA *is* an instance of the paper's decomposition.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

# ---------------------------------------------------------------- sub-configs


@dataclass(frozen=True)
class MoECfg:
    num_experts: int = 64
    num_shared: int = 2
    top_k: int = 6
    d_ff_expert: int = 1408
    capacity_factor: float = 1.25   # training dispatch only (serving drops none)
    router_jitter: float = 0.0
    # gating: softmax over all experts, top_k of them; the chosen weights are
    # renormalised to sum to 1 when ``norm_topk``
    norm_topk: bool = True


@dataclass(frozen=True)
class YarnCfg:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2 publishes it."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class MLACfg:
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 0  # 0 => direct q projection (V2-Lite)


@dataclass(frozen=True)
class MambaCfg:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 => d_model // 16


@dataclass(frozen=True)
class XLSTMCfg:
    proj_factor: float = 2.0
    conv_kernel: int = 4
    chunk: int = 256  # chunkwise-parallel block for mLSTM training


@dataclass(frozen=True)
class CPQCfg:
    """T2: cascade pruning-quantization of the KV / X cache."""

    prune_ratio: float = 0.4      # fraction of elements zeroed (per channel, magnitude)
    bits: int = 4                 # quantized code width (4 or 8)
    max_levels: int = 4           # HQE: max hierarchical extension levels
    tolerance: float = 1.0        # TR multiplier: token spawns new level if |x| > tol * range
    residual_window: int = 32     # most-recent tokens kept in full precision


@dataclass(frozen=True)
class RetrievalCfg:
    """T3: attention as nearest-neighbor retrieval."""

    top_k: int = 512              # exact re-score candidates per query
    proxy_bits: int = 8           # proxy similarity precision (CAM analogue)
    proxy_dim: int = 0            # 0 => full d_head at low precision; else low-rank proxy
    recent_window: int = 64       # always-attended recent tokens (dense tail)


@dataclass(frozen=True)
class AttentionRuntime:
    # dense | decomposed (T1) | cpq (T2) | retrieval (T3)
    # | decomposed_cpq (T1+T2: CPQ-compressed X cache)
    mode: str = "dense"
    cpq: Optional[CPQCfg] = None
    retrieval: Optional[RetrievalCfg] = None
    # paged serving decode: fuse the block-table gather into the Pallas
    # kernels (dense/CPQ/X-MLA tiers) instead of materializing logical views.
    # False falls back to the jnp gather path (the tests' oracle).
    paged_kernels: bool = True
    # serving device mesh (jax.sharding.Mesh with a "model" axis, or None =
    # single device). When set, decode_attend_paged / chunk_attend_paged
    # route the supported tiers through shard_map over the kv-head axis so
    # each device sweeps only its local head shard of the paged arena
    # (serving/sharded.py); None keeps today's single-device path untouched.
    mesh: Optional[object] = None

    def __post_init__(self):
        assert self.mode in ("dense", "decomposed", "cpq", "retrieval",
                             "decomposed_cpq"), self.mode
        if self.mode in ("cpq", "decomposed_cpq") and self.cpq is None:
            object.__setattr__(self, "cpq", CPQCfg())
        if self.mode == "retrieval" and self.retrieval is None:
            object.__setattr__(self, "retrieval", RetrievalCfg())


@dataclass(frozen=True)
class ServingCfg:
    """Continuous-batching serving layer (serving/scheduler.py + engine.py).

    The physical arena is ``num_pages`` pages of ``page_size`` tokens per
    attention layer (page 0 reserved as the null page); each request slot may
    map at most ``max_blocks_per_slot`` logical pages (its context ceiling).
    Watermarks are FREE-page fractions of the base arena: below ``low`` new
    admissions are assigned the compressed tier, below ``critical`` the
    longest running dense request is escalated in place (dense -> T2; pages
    freed back to the dense pool). Escalation needs ``enable_escalation`` and
    a base mode of "dense"."""

    num_slots: int = 4
    page_size: int = 16
    num_pages: int = 129           # incl. the reserved null page 0
    max_blocks_per_slot: int = 16
    escalated_pages: int = 65      # CPQ arena pages (tiered engines only)
    low_watermark: float = 0.25
    critical_watermark: float = 0.10
    # recovery threshold: when the dense free fraction climbs back ABOVE
    # this, policies with de-escalation enabled restore escalated (T2) rows
    # to the dense tier via chunked re-admission (hysteresis: must be >= low;
    # the 1.0 default can never be exceeded, so recovery is opt-in)
    high_watermark: float = 1.0
    enable_escalation: bool = False
    # admission/preemption/escalation decision policy (serving/policies.py):
    # fifo (default; decision-identical to the pre-policy scheduler) |
    # priority (strict SloClass levels + aging) | slo (TTFT-slack EDF
    # admission + de-escalation). An engine ``policy=`` object overrides it.
    policy: str = "fifo"
    prefill_bucket: int = 16       # prompts padded up to a multiple of this
    # chunked paged prefill (the DEFAULT admission path): prompts stream into
    # their slot's arena pages in page-aligned chunks of this many tokens,
    # at most one chunk interleaved per decode step — no contiguous scratch
    # prefill cache, no monolithic admission stall. 0 restores the one-shot
    # B=1 prefill + pack path (the construction-exact admission oracle).
    prefill_chunk: int = 16
    # prefix sharing + copy-on-write pages: admission mounts a request's
    # longest indexed page-aligned prefix as refcount bumps on already-
    # resident pages (zero arena writes) and chunked prefill streams only
    # the unshared tail; a write into a still-shared page splits it first.
    # Token-exact (greedy and seeded sampling outputs are bit-identical to
    # sharing off); active only for chunked admissions in the dense / T1 /
    # MLA / tiered modes — CPQ and retrieval pages read through per-slot
    # side state and never share.
    share_prefix: bool = False
    # base-arena compaction: every N retirements the engine applies the
    # scheduler's defrag plan (mapped pages relabel onto the lowest physical
    # ids — locality for the fused kernels' sequential page reads). 0 = off.
    # Logical contents are invariant (property-tested, incl. sharded arenas);
    # the count surfaces as the ``defrags`` serve stat.
    defrag_every: int = 0
    # ---- fault tolerance (serving/health.py + router) -------------------
    # health-probe cadence in router ticks (HealthMonitor; 0 disables
    # probing entirely — the router then only reacts to step() faults)
    probe_interval: int = 4
    # consecutive failed probes (liveness / progress / arena pressure)
    # before the monitor auto-drains a replica
    probe_failures: int = 3
    # initial re-probe backoff (router ticks) after an auto-drain; doubles
    # per failed recovery probe up to 8x (bounded so a recovered replica
    # re-admits within a handful of probes)
    probe_backoff: int = 4
    # dense free-page fraction at/below which a replica WITH queued work
    # counts as arena-exhausted for probing purposes (negative disables the
    # pressure check; injected exhaust faults also set an explicit flag)
    probe_exhaust_frac: float = 0.0
    # auto-drain: let the HealthMonitor drain an unhealthy replica through
    # the normal engine.drain() snapshot path (and re-admit it after
    # recovery probes succeed). Off by default: drains are caller-driven
    # exactly as before unless opted in.
    auto_drain: bool = False
    # deadline-aware load shedding: scale applied to SloClass-derived
    # per-request budgets (deadline = arrival + scale * (ttft_target +
    # max_tokens * itl_target), enforced at tick boundaries with a counted
    # ``timeout`` finish reason). 0 = deadlines off; explicit
    # SamplingParams.deadline budgets are honored regardless.
    deadline_scale: float = 0.0
    # router-level admission backpressure: parked-request backlog capacity.
    # When every replica is draining or saturated, new work PARKS in the
    # router backlog instead of raising; beyond this many parked requests,
    # deadline-free batch-class arrivals are SHED (counted, never raised).
    # 0 = unbounded parking, never shed.
    max_backlog: int = 0
    # ---- speculative decoding (serving/speculative.py) ------------------
    # n-gram / prompt-lookup speculative decoding: propose up to this many
    # draft tokens per running row from its own context (no second model),
    # land them in refcount-aliased scratch pages, and score all of them in
    # ONE Q-chunk>1 paged attend — amortizing a full weight stream over
    # spec_len candidates where decode is weight-stream-bound (low
    # occupancy). 0 = off. Accepted tokens are ALWAYS re-drawn through the
    # per-request fold_in(seed, token_index) sampler, so speculative
    # on-vs-off is bit-exact for greedy rows and replay-stable for seeded
    # ones. Active only for chunked engines in dense/T1/MLA/tiered modes
    # (same gate as share_prefix).
    spec_len: int = 0
    # longest suffix n-gram matched against the row's earlier context when
    # drafting (falls back to shorter n-grams down to 1; no match = normal
    # single-token decode for that row this tick)
    spec_ngram: int = 3

    def __post_init__(self):
        self.validate(strict=False)

    def validate(self, strict: bool = True) -> "ServingCfg":
        """Raise ``ValueError`` (with the knob names spelled out) for
        inconsistent configurations, instead of letting them fail deep in
        the scheduler or silently gate features off.

        ``strict=False`` checks only the hard construction invariants
        (ranges, page alignment of the prefill chunk, watermark ordering) —
        this is what ``__post_init__`` runs, so an invalid combination can
        never be constructed. ``strict=True`` (the default; called at
        ``ContinuousServeEngine`` construction) additionally rejects config-level
        cross-knob inconsistencies: knobs that REQUEST a feature the rest of
        the config gates off (speculative decoding without chunked
        admission) and capacity settings no request could ever run under.
        Returns ``self`` so call sites can chain it."""

        def bad(msg: str):
            raise ValueError(f"ServingCfg: {msg}")

        if not (self.num_pages >= 2 and self.escalated_pages >= 2):
            bad(f"num_pages={self.num_pages} and escalated_pages="
                f"{self.escalated_pages} must each be >= 2 (page 0 is the "
                "reserved null page)")
        if not (self.page_size >= 1 and self.num_slots >= 1
                and self.max_blocks_per_slot >= 1):
            bad(f"page_size={self.page_size}, num_slots={self.num_slots}, "
                f"max_blocks_per_slot={self.max_blocks_per_slot} must all "
                "be >= 1")
        if not 0.0 <= self.critical_watermark <= self.low_watermark <= 1.0:
            bad(f"watermarks must satisfy 0 <= critical_watermark "
                f"({self.critical_watermark}) <= low_watermark "
                f"({self.low_watermark}) <= 1")
        if not self.low_watermark <= self.high_watermark <= 1.0:
            bad(f"high_watermark ({self.high_watermark}) must lie in "
                f"[low_watermark ({self.low_watermark}), 1] — it is the "
                "de-escalation hysteresis threshold above low")
        if self.policy not in ("fifo", "priority", "slo"):
            bad(f"policy={self.policy!r} not one of fifo|priority|slo")
        if self.prefill_bucket < 1:
            bad(f"prefill_bucket={self.prefill_bucket} must be >= 1")
        if self.prefill_chunk < 0:
            bad(f"prefill_chunk={self.prefill_chunk} must be >= 0 "
                "(0 = one-shot admission)")
        if self.defrag_every < 0:
            bad(f"defrag_every={self.defrag_every} must be >= 0 (0 = off)")
        if self.probe_interval < 0:
            bad(f"probe_interval={self.probe_interval} must be >= 0")
        if self.probe_failures < 1 or self.probe_backoff < 1:
            bad(f"probe_failures={self.probe_failures} and probe_backoff="
                f"{self.probe_backoff} must be >= 1")
        if self.probe_exhaust_frac > 1.0:
            bad(f"probe_exhaust_frac={self.probe_exhaust_frac} must be "
                "<= 1.0 (negative disables the pressure check)")
        if self.deadline_scale < 0.0:
            bad(f"deadline_scale={self.deadline_scale} must be >= 0 "
                "(0 = deadlines off)")
        if self.max_backlog < 0:
            bad(f"max_backlog={self.max_backlog} must be >= 0 "
                "(0 = unbounded parking)")
        if self.spec_len < 0:
            bad(f"spec_len={self.spec_len} must be >= 0 (0 = off)")
        if self.spec_ngram < 1:
            bad(f"spec_ngram={self.spec_ngram} must be >= 1")
        if self.prefill_chunk and self.prefill_chunk % self.page_size != 0:
            bad("prefill_chunk must be page-aligned (chunks stream whole "
                f"arena pages): prefill_chunk={self.prefill_chunk} % "
                f"page_size={self.page_size} != 0")
        if not strict:
            return self
        # ---- strict: cross-knob consistency (engine-construction checks) --
        if self.spec_len > 0 and self.prefill_chunk == 0:
            bad(f"spec_len={self.spec_len} requires chunked admission "
                "(prefill_chunk > 0): the verify pass IS a spec_len+1 wide "
                "prefill chunk. Set prefill_chunk to a page-aligned value "
                "or spec_len=0")
        if self.max_len < 2:
            bad(f"max_len = page_size*max_blocks_per_slot = {self.max_len} "
                "< 2: no request could hold a prompt token plus one "
                "generated token")
        return self

    @property
    def max_len(self) -> int:
        """Per-request logical context ceiling (tokens)."""
        return self.page_size * self.max_blocks_per_slot


# ------------------------------------------------------------------- model


MIXERS = ("attn", "xattn", "mla", "mamba", "mlstm", "slstm")
MLPS = ("dense", "moe", "none")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | vlm | audio | ssm | hybrid
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # layer layout: prefix (unrolled) + num_blocks x block_pattern (scanned)
    block_pattern: tuple[tuple[str, str], ...]
    num_blocks: int
    prefix_pattern: tuple[tuple[str, str], ...] = ()
    # flavor knobs
    mlp_act: str = "swiglu"  # swiglu | geglu | gelu
    norm: str = "rmsnorm"    # rmsnorm | layernorm
    qkv_bias: bool = False
    qk_norm: bool = False
    pos_embedding: str = "rope"  # rope | absolute | none
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnCfg] = None
    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma: scale embeddings by sqrt(d_model)
    logit_softcap: float = 0.0
    input_kind: str = "tokens"  # tokens | audio_frames | text+patches
    num_patch_tokens: int = 0   # vlm: visual tokens per sample (stub frontend)
    # sub-configs
    moe: Optional[MoECfg] = None
    # routed experts held on this chip, the first ``experts_held`` of
    # ``moe.num_experts`` (expert parallelism: the router still scores all of
    # them); 0 = all
    experts_held: int = 0
    mla: Optional[MLACfg] = None
    mamba: Optional[MambaCfg] = None
    xlstm: Optional[XLSTMCfg] = None
    attention: AttentionRuntime = AttentionRuntime()
    dtype: str = "bfloat16"

    def __post_init__(self):
        for mixer, mlp in self.prefix_pattern + self.block_pattern:
            assert mixer in MIXERS, mixer
            assert mlp in MLPS, mlp

    @property
    def num_layers(self) -> int:
        return len(self.prefix_pattern) + self.num_blocks * len(self.block_pattern)

    @property
    def layer_kinds(self) -> tuple[tuple[str, str], ...]:
        return self.prefix_pattern + self.block_pattern * self.num_blocks

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_mha(self) -> bool:
        return self.num_kv_heads == self.num_heads

    @property
    def attention_free(self) -> bool:
        return not any(m in ("attn", "xattn", "mla") for m, _ in self.layer_kinds)

    @property
    def sub_quadratic(self) -> bool:
        """True if long contexts are tractable without dense attention
        (SSM/hybrid family, or T3 retrieval attention enabled)."""
        fams = self.family in ("ssm", "hybrid")
        return fams or self.attention.mode == "retrieval"

    def with_attention(self, mode: str, **kw) -> "ModelConfig":
        return dataclasses.replace(self, attention=AttentionRuntime(mode=mode, **kw))


# ------------------------------------------------------------------- shapes


@dataclass(frozen=True)
class ShapeCfg:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    def __post_init__(self):
        assert self.kind in ("train", "prefill", "decode")


SHAPES: dict[str, ShapeCfg] = {
    "train_4k": ShapeCfg("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCfg("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCfg("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCfg("long_500k", 524288, 1, "decode"),
}


def cell_supported(cfg: ModelConfig, shape: ShapeCfg) -> tuple[bool, str]:
    """Whether (arch x shape) is a defined cell, and why not if skipped."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, (
            "long_500k requires sub-quadratic attention; "
            f"{cfg.name} is pure full-attention (enable attention.mode='retrieval' "
            "— the paper's T3 — to run this cell)"
        )
    return True, ""
