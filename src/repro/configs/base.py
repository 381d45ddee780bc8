"""Config system: model configs, input shapes, training/run configs.

Every assigned architecture is a `ModelConfig` instance in its own module
(one file per arch, exact numbers from the assignment table, source cited).
`reduced()` derives the CPU-smoke variant of the same family.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0           # routed experts
    n_shared: int = 0            # shared (always-on) experts
    top_k: int = 2
    d_expert: int = 0            # per-expert FFN hidden size
    capacity_factor: float = 1.25
    # layers [0, dense_prefix) use a dense FFN instead of MoE (DeepSeek-V2
    # keeps the first block dense).
    dense_prefix: int = 1
    router_aux_weight: float = 0.001


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16            # recurrent state per channel (Mamba) / head
    d_conv: int = 4              # depthwise conv width (Mamba)
    expand: int = 2              # inner expansion for Mamba
    head_dim: int = 64           # RWKV6 head size


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str               # dense | moe | ssm | hybrid | audio | vlm | encoder
    source: str                  # citation for the numbers
    num_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None       # default d_model // n_heads
    max_seq_len: int = 8192

    # attention flavour: gqa | mla | swa | none (attention-free)
    attention: str = "gqa"
    window: Optional[int] = None         # sliding-window size for swa

    # MLA (DeepSeek-V2 / MiniCPM3)
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: Optional[int] = None

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None

    # MLA decode in the compressed latent space (absorb wkv_b into q / out):
    # never expands per-head K/V over the cache — ~200x less decode compute
    # at 32k context (beyond-paper; EXPERIMENTS.md §Perf pair 2-serving)
    mla_absorbed_decode: bool = True

    # encoder-decoder (whisper): num_layers = decoder layers
    encoder_layers: int = 0
    encoder_seq_len: int = 1500          # whisper frames after conv stub
    # VLM: number of stub patch embeddings prepended to text
    n_patch_tokens: int = 0

    norm: str = "rmsnorm"                # rmsnorm | layernorm
    act: str = "silu"                    # silu | gelu
    pos_emb: str = "rope"                # rope | sinusoidal (abs, added at embed)
    rope_theta: float = 10000.0
    tie_embeddings: bool = False

    # which input shapes this arch supports (see DESIGN.md §4 for skips)
    supports_decode: bool = True
    supports_long: bool = False          # sub-quadratic decode at 500k

    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.n_heads

    @property
    def resolved_v_head_dim(self) -> int:
        if self.v_head_dim is not None:
            return self.v_head_dim
        return self.resolved_head_dim

    def padded_vocab(self, tp: int = 1) -> int:
        mult = 128 * max(tp, 1)
        return ((self.vocab_size + mult - 1) // mult) * mult

    def padded_q_heads(self, tp: int = 1) -> int:
        """Physical head count for MLA projections: padded to a TP multiple
        with zero-weight heads (mathematically inert for paired q/kv heads —
        zero q and zero k give zero scores, and wo's zero rows drop the
        padded heads' outputs). Avoids GSPMD choosing a pathological sharding
        for indivisible head counts (observed 14.8 TiB/step of score
        all-reduces on minicpm3-4b at tp=16)."""
        h = self.n_heads
        if self.attention != "mla" or tp <= 1 or h % tp == 0:
            return h
        return ((h + tp - 1) // tp) * tp

    # ------------------------------------------------------------------
    def n_params(self) -> int:
        """Analytic parameter count (matches init_params leaf sizes, un-padded
        vocab; used for MODEL_FLOPS=6ND and Table-3 style analytics)."""
        from repro.models.model import count_params_analytic
        return count_params_analytic(self)

    def n_active_params(self) -> int:
        from repro.models.model import count_params_analytic
        return count_params_analytic(self, active_only=True)

    def reduced(self) -> "ModelConfig":
        """CPU-smoke variant of the same family (<=2 layers, d_model<=256,
        <=4 experts)."""
        d_model = min(self.d_model, 256)
        n_heads = max(2, min(self.n_heads, 4))
        ratio = max(1, self.n_heads // max(self.n_kv_heads, 1))
        n_kv = max(1, n_heads // min(ratio, n_heads))
        hd = 32
        kw = dict(
            num_layers=min(self.num_layers, 2),
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 448),
            vocab_size=min(self.vocab_size, 512),
            max_seq_len=min(self.max_seq_len, 256),
            name=self.name + "-reduced",
        )
        if self.attention == "mla":
            kw.update(q_lora_rank=None, kv_lora_rank=64,
                      qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
        if self.window is not None:
            kw.update(window=64)
        if self.moe is not None:
            kw["moe"] = replace(self.moe, n_experts=4, n_shared=min(self.moe.n_shared, 1),
                                top_k=2, d_expert=128, dense_prefix=min(self.moe.dense_prefix, 1))
        if self.encoder_layers:
            kw.update(encoder_layers=1, num_layers=1, encoder_seq_len=64)
        if self.n_patch_tokens:
            kw.update(n_patch_tokens=16)
        return replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def shape_supported(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """Whether this (arch, shape) pair runs; reason recorded in DESIGN.md."""
    if shape.kind == "decode":
        if not cfg.supports_decode:
            return False, "encoder-only / enc-dec-short arch has no decode step"
        if shape.seq_len > 100_000 and not cfg.supports_long:
            return False, "full-attention arch without sub-quadratic variant"
    return True, ""


# ---------------------------------------------------------------------------
# Training / run config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adama"          # adam | adama | adafactor | sm3
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    # accumulation engine: ga | adama | adama_layerwise
    accumulation: str = "adama"
    micro_batches: int = 8
    zero_stage: int = 0          # 0 | 1 (P_os; arena shards by row range)
    use_pallas: bool = False     # fused kernels for accumulate/apply
    # flat optimizer-state arena (core/arena.py): ONE kernel dispatch per
    # micro-batch fold / mini-batch apply instead of one per param leaf,
    # with the begin-minibatch decay fused into the first fold. Effective
    # only with use_pallas=True. With zero_stage=1 the arena is sharded by
    # ROW RANGE (core/zero.py::shard_rows) instead of per leaf.
    arena: bool = False
    # second-moment codec over the arena (core/state_store.py):
    #   fp32     exact, 4 B/param for v (default)
    #   int8     per-row quantized codes + fp32 scale column, ~1 B/param
    #   factored SM3-style per-row statistic, ~4/1024 B/param
    #   rowcol   Adafactor-style rank-1 row x col marginals, ~2/1024 the
    #            memory of fp32 v (row sums row-indexed + one replicated
    #            (1, LANES) column-sum block)
    # Codecs are arena columns: they require arena=True. All codec state
    # except rowcol's column sums is row-indexed, so every codec composes
    # with zero_stage=1 row sharding (the column sums are replicated and
    # psum-combined once per mini-batch).
    state_codec: str = "fp32"
    # first-moment codec (fp32 | int8 = signed per-row quantization rounding
    # toward zero, never-amplify); requires arena=True when not fp32.
    m_codec: str = "fp32"
    # Bucketed ZeRO-1 schedule in the shard_map DP engine (core/buckets.py):
    # stream per-layer / size-capped gradient reduce-scatters into the
    # slice-fold instead of packing the FULL gradient arena before one
    # monolithic psum_scatter. Peak live packed-gradient memory drops from
    # the arena to one bucket and the collectives overlap the folds; results
    # are bitwise identical to the full-pack schedule (row-local codecs).
    # False restores the legacy full-pack schedule. Consulted only when
    # zero_stage=1 under core/dp_shardmap.make_dp_train_step.
    zero_bucketed: bool = True
    # rest-region bucket cap in arena rows (0 = core/buckets.py default,
    # 4096 rows = 16 MiB fp32 slab); per-layer stack buckets are uncapped.
    zero_bucket_rows: int = 0
    # Async double-buffered bucket pipeline (core/dp_shardmap.py): issue
    # bucket i+1's pack + reduce-scatter while bucket i's received slice is
    # still folding, with an optimization_barrier pinning bucket i+2's pack
    # behind bucket i's fold so EXACTLY two gradient buckets are ever live
    # (launch/dryrun.py gates live_peak_reduce-scatter <= 2x max-bucket).
    # The param all-gather switches to a ppermute ring (same bytes, moved
    # as M-1 collective-permutes the scheduler can overlap with the apply
    # epilogue). Numerics are BITWISE identical to the serial bucketed
    # schedule — the psum_scatter per bucket and its reduction order are
    # unchanged; only instruction-level ordering freedom moves. Requires
    # the bucketed ZeRO-1 schedule (zero_stage=1, arena, zero_bucketed or
    # the layerwise stream).
    zero_async: bool = False
    # Gradient WIRE dtype of the arena fold pipeline (fp32 | bf16): the
    # dtype gradients are PACKED and COLLECTIVELY MOVED in (core/arena.py
    # pack helpers, the per-bucket/per-layer psum_scatters of
    # core/dp_shardmap.py + core/layerwise.py). bf16 halves the live packed
    # slab and every gradient collective; the fold kernels upcast to fp32
    # IN-KERNEL, so the (m, v) accumulation itself stays fp32 (micro-batch-
    # count independent) and no fp32 gradient buffer ever materializes.
    # Requires arena=True (the wire IS the packed slab); the 'ga' engine is
    # excluded — it sums raw gradients across micro-batches in the wire
    # buffer, and bf16 accumulation would violate the fp32-accumulation
    # contract. bf16-wire results match the fp32 wire to each codec's
    # declared tolerance, NOT bitwise: each device's addend is rounded to
    # bf16 before the collective, and the reduction's own arithmetic is
    # backend-defined (ring implementations may keep partial sums in bf16
    # hop-by-hop, so deviation can grow with DP size; tolerances are
    # validated at 4 devices).
    #
    # "fp8_e4m3" quarters the wire: gradients move as float8_e4m3fn codes
    # plus a per-row fp32 scale column (kernels/adama_accum.fp8_encode_rows;
    # the scale is pmax-agreed across devices so summed codes decode, with
    # n_devices of headroom against overflow), the fold kernels fuse the
    # decode into the in-kernel upcast (`grad_scale`), and accuracy is
    # recovered by a MicroAdam-style error-feedback residual state["ef"]
    # (the quantization error each device left on its OWNED rows, re-
    # injected into its next micro-batch's pre-quantization gradient;
    # ZeRO-1 row-sharded, checkpointed, finite-guard-predicated).
    # fp8_e4m3 additionally requires finite_guard=True: e4m3 has no inf,
    # NaN codes are the only overflow signal, and the error-feedback
    # residual must be skip-predicated or a vetoed micro-batch would
    # corrupt it. In the shard_map DP engine it also requires the bucketed
    # ZeRO-1 schedule (the residual is per-owned-row; replicated state
    # would diverge across devices — the engine raises its own error).
    grad_dtype: str = "fp32"
    # MicroAdam-style error feedback for the fp8_e4m3 wire (inert for
    # fp32/bf16): each device's quantization error on its owned rows is
    # kept in state["ef"] and added into the next micro-batch's gradient
    # before quantization. False drops the residual (ablation knob for the
    # fig2 convergence comparison) — the wire still quantizes, nothing
    # recovers the error.
    error_feedback: bool = True
    # fp32 MASTER params in the arena (the standard AMP contract for
    # compute_dtype=bfloat16 runs): state gains a third packed fp32 region
    # "p"; the fused apply updates it in place and emits bf16 WORKING
    # params from the same kernel (one extra output column set, still O(1)
    # dispatch). The working params are a pure cast of the master every
    # step, so the round-trip is exact by construction; under the shard_map
    # ZeRO-1 schedule the param all-gather moves bf16 (half bytes) and the
    # working params are never re-packed. Requires arena=True.
    master_params: bool = False
    # bf16 working-param cache between steps (pjit engines): keep the bf16
    # work arena the master apply emits as state["wp"] and source each
    # step's model params from it with ONE unpack — the engines never
    # re-pack the incoming param tree, and the tree input to the step is
    # dead (XLA prunes it). Step 1's loss then consumes bf16-cast params
    # (the standard AMP contract — every later step already did); from
    # step 2 on the trajectory is bitwise identical to the uncached master
    # run. Requires master_params=True (the fp32 truth must live in "p" —
    # caching bf16 params without a master would make the cast lossy).
    # pjit engines only: the shard_map ZeRO-1 schedule already never
    # re-packs params (it all-gathers the emitted work rows) and raises on
    # this knob.
    work_param_cache: bool = False
    grad_clip: Optional[float] = None
    # Fused non-finite guards (train/scaler.py + kernels/fused_step.py):
    # every arena fold additionally emits a per-call finite flag (a
    # reduction over the packed gradient slab, checked BEFORE the state
    # update commits) and the m/v writes are predicated on it, so a
    # NaN/Inf micro-batch is a bitwise no-op fold instead of poisoned
    # state. The begin-minibatch decay shifts to the first GOOD fold, the
    # mini-batch apply is skipped (and the step counter frozen) when every
    # micro-batch was bad, and skip counters ride in the optimizer state
    # ("scaler"). Under the shard_map ZeRO-1 schedule the flag is checked
    # post-reduce-scatter and psum-agreed so all shards skip or none do.
    # Under accumulation='ga' the guard is the classic whole-step recipe
    # instead: one flag over the ACCUMULATED slab predicates the single
    # fold+apply. Requires arena=True (the flag is a slab reduction).
    finite_guard: bool = False
    # Loss scaling for the gradient wire: "off" | "dynamic" | a positive
    # float literal (e.g. "1024") for a static scale. The loss is
    # multiplied by the scale before backward and the fold kernels divide
    # it back out in-kernel (the scale rides next to the decay pair as an
    # SMEM scalar, so one compiled kernel serves every scale value).
    # "dynamic" grows the scale 2x after scaler_growth_interval consecutive
    # good micro-batches and halves it on every skipped one (floor 1.0).
    # Requires a reduced-precision wire (grad_dtype="bf16" or "fp8_e4m3" —
    # the wire it protects), finite_guard=True (skips drive the backoff)
    # and an AdamA fold engine.
    loss_scale: str = "off"
    # consecutive good micro-batches before a dynamic scale 2x growth
    scaler_growth_interval: int = 200
    # abort the training loop after this many CONSECUTIVE skipped
    # micro-batches (train/loop.py raises); 0 disables the abort.
    scaler_abort_after: int = 0

    def __post_init__(self):
        validate_optimizer_config(self)


# Capability matrix for the optimizer-state store, consulted by
# validate_optimizer_config and mirrored in tests/test_configs.py and the
# README table. Keys: (m_codec, v_codec, zero_stage, accumulation engine)
# dimensions that are NOT universally supported, with the actionable reason.
STATE_CODECS = ("fp32", "int8", "factored", "rowcol")    # second moment (v)
M_CODECS = ("fp32", "int8")                              # first moment (m)
ZERO_STAGES = (0, 1)
ACCUM_ENGINES = ("ga", "adama", "adama_layerwise")
GRAD_DTYPES = ("fp32", "bf16", "fp8_e4m3")               # gradient wire

# Names of the training step's phases (`jax.named_scope`). They reach each
# compiled instruction's `metadata={op_name=...}`: the model's forward under
# MODEL_SCOPE, its backward under `transpose(jvp(model))`, and the optimizer
# phases under their own names, so a device trace can be split by phase.
MODEL_SCOPE = "model"
RECOMPUTE_SCOPE = "model.recompute"    # forward redone inside a backward
GRAD_PACK_SCOPE = "optimizer.grad_pack"      # gradient tree -> arena slab
FOLD_SCOPE = "optimizer.fold"                # slab -> (m, v)
ACCUMULATE_SCOPE = "optimizer.accumulate"    # ga's gradient accumulator
APPLY_SCOPE = "optimizer.apply"              # (m, v) -> params


def grad_wire_dtype(name: str):
    """The jnp dtype a `grad_dtype` config value packs/moves gradients in —
    the ONE mapping every consumer (engines, launchers, benches) shares."""
    import jax.numpy as jnp
    if name not in GRAD_DTYPES:
        raise ValueError(f"unknown grad_dtype {name!r}; expected one of "
                         f"{GRAD_DTYPES}")
    return {"bf16": jnp.bfloat16,
            "fp8_e4m3": jnp.float8_e4m3fn}.get(name, jnp.float32)


def grad_wire_itemsize(name: str) -> int:
    """Bytes per element on the gradient wire (budget/accounting sites)."""
    import numpy as np
    return np.dtype(grad_wire_dtype(name)).itemsize


def parse_loss_scale(value: str):
    """Parse an OptimizerConfig.loss_scale value: returns "off", "dynamic",
    or a positive float (static scale). Raises ValueError otherwise — the
    ONE parser shared by validation, engines and the CLI `--loss-scale`."""
    if value in ("off", "dynamic"):
        return value
    try:
        scale = float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"loss_scale={value!r} unsupported; expected 'off', 'dynamic', "
            f"or a positive float literal (e.g. '1024')") from None
    if not (scale > 0.0):
        raise ValueError(f"loss_scale={value!r} must be > 0")
    return scale


def optimizer_capability(opt: "OptimizerConfig") -> Optional[str]:
    """None when the configuration is supported, else an actionable error
    message. The full matrix is m_codec x v_codec x zero_stage x engine:

      fp32 x fp32     : any engine, any zero stage, arena or per-leaf.
      compressed codec: requires arena=True (codecs are arena columns) —
                        then any engine and any zero stage (codec state is
                        row-indexed, so row-range ZeRO composes; rowcol's
                        replicated column sums psum-combine per mini-batch).
      zero_stage=1    : per-leaf states shard via zero1_state_sharding;
                        arena states shard by row range (shard_rows). In
                        the shard_map DP engine the row-range schedule is
                        BUCKETED by default (zero_bucketed=True: per-layer /
                        size-capped gradient reduce-scatters streamed into
                        the slice-fold, state resident in partition order —
                        core/buckets.py); zero_bucketed=False restores the
                        full-arena pack+scatter. Both fields are inert
                        outside that engine. The 'adama_layerwise' shard_map
                        variant exists only in bucketed ZeRO-1 form (the
                        stream IS its schedule).
      arena=True      : requires use_pallas=True; the 'ga' engine's fused
                        update supports the adam/adama optimizer only.
      grad_dtype=bf16 : requires arena=True (the wire IS the packed arena
                        slab) and an AdamA fold engine (adama |
                        adama_layerwise) — 'ga' accumulates raw gradients
                        across micro-batches in the wire buffer, which must
                        stay fp32. Composes with every (m_codec, v_codec)
                        pair and both ZeRO-1 schedules: the fold kernels
                        upcast in-kernel, so the codec transforms see fp32
                        exactly as on the fp32 wire. Results match the fp32
                        wire to each codec's declared bf16_wire tolerance
                        (a psum of bf16 payloads over many micro-batches is
                        to-tolerance, not bitwise).
      grad_dtype=fp8_e4m3 : everything bf16 requires, PLUS finite_guard=True
                        — e4m3 has no inf (NaN codes are the only overflow
                        signal, which only the fused guards catch) and the
                        error-feedback residual state["ef"] must be
                        skip-predicated so a vetoed micro-batch does not
                        corrupt it. Gradients move as fp8 codes + a per-row
                        fp32 scale column (0.25x the fp32 wire); accuracy
                        is declared per codec pair (Conformance.fp8_wire_lr)
                        and recovered across micro-batches by the residual
                        (error_feedback=False ablates it). The shard_map DP
                        engine additionally requires the bucketed ZeRO-1
                        schedule for fp8 (per-owned-row residual; it raises
                        its own actionable error otherwise).
      master_params   : requires arena=True; any engine, any zero stage
                        (the master region is row-indexed fp32, so it
                        row-shards exactly like m/v; the working-param
                        all-gather moves bf16).
      work_param_cache: requires master_params=True (and therefore arena).
                        The pjit engines keep the bf16 work arena the
                        master apply emits as state["wp"] and read each
                        step's model params from it — the step's param-tree
                        input is dead and never re-packed. pjit engines
                        only; the shard_map DP engine raises (its ZeRO-1
                        schedule already never re-packs params).
      finite_guard    : requires arena=True (the per-fold finite flag is a
                        reduction over the packed gradient slab). Under the
                        AdamA engines the guard is per-MICRO-BATCH (a bad
                        micro-batch is a bitwise no-op fold); under 'ga'
                        it is the classic whole-step recipe — the flag is
                        computed over the accumulated slab and predicates
                        the one fold+apply. Composes with every codec pair,
                        both ZeRO-1 schedules and the bf16 wire.
      loss_scale      : 'off' | 'dynamic' | a positive float literal.
                        != 'off' requires grad_dtype='bf16' (the wire it
                        protects), finite_guard=True (skipped micro-batches
                        drive the backoff; an unguarded scaled run would
                        fold scaled NaNs) and an AdamA fold engine (a ga
                        skip loses the whole mini-batch — too coarse to
                        drive the backoff).

    One engine-selection caveat lives outside this matrix (engine choice is
    not an OptimizerConfig field): the shard_map DP engine
    (core/dp_shardmap.make_dp_train_step) additionally requires
    zero_stage=1 for any compressed m/v codec — its mini-batch-end state
    psum cannot sum codec-encoded moments, while the row-range ZeRO-1
    schedule reduce-scatters fp32 gradients instead. It raises its own
    actionable error at construction.
    """
    if opt.accumulation not in ACCUM_ENGINES:
        return (f"unknown accumulation engine {opt.accumulation!r}; "
                f"expected one of {ACCUM_ENGINES}")
    if opt.state_codec not in STATE_CODECS:
        return (f"unknown state_codec {opt.state_codec!r}; expected one of "
                f"{STATE_CODECS}")
    if opt.m_codec not in M_CODECS:
        return (f"unknown m_codec {opt.m_codec!r}; expected one of "
                f"{M_CODECS}")
    if opt.zero_stage not in ZERO_STAGES:
        return (f"zero_stage={opt.zero_stage} unsupported; expected one of "
                f"{ZERO_STAGES} (ZeRO-2/3 shard gradients/params, which "
                f"AdamA already makes transient)")
    if opt.arena and not opt.use_pallas:
        return ("arena=True requires use_pallas=True (the arena path IS the "
                "fused-kernel path); pass use_pallas=True")
    if opt.state_codec != "fp32" and not opt.arena:
        return (f"state_codec={opt.state_codec!r} requires arena=True: "
                f"codecs are columns of the flat state arena "
                f"(core/state_store.py); pass arena=True use_pallas=True")
    if opt.m_codec != "fp32" and not opt.arena:
        return (f"m_codec={opt.m_codec!r} requires arena=True: codecs are "
                f"columns of the flat state arena (core/state_store.py); "
                f"pass arena=True use_pallas=True")
    if opt.arena and opt.accumulation == "ga" and \
            opt.name not in ("adam", "adama"):
        return (f"arena=True with accumulation='ga' supports the adam/adama "
                f"optimizer only (the fused arena update is Adam), got "
                f"name={opt.name!r}; drop arena or switch optimizer")
    if opt.zero_bucket_rows < 0:
        return (f"zero_bucket_rows must be >= 0 (0 = default cap), got "
                f"{opt.zero_bucket_rows}")
    if opt.zero_async:
        if opt.zero_stage != 1:
            return ("zero_async=True requires zero_stage=1: the double-"
                    "buffered pipeline overlaps per-bucket gradient "
                    "reduce-scatters against slice folds, which only exist "
                    "in the ZeRO-1 row-range schedule; pass zero_stage=1")
        if not opt.arena:
            return ("zero_async=True requires arena=True (use_pallas=True): "
                    "the bucket pipeline streams slices of the flat state "
                    "arena; pass arena=True use_pallas=True")
        if not opt.zero_bucketed and opt.accumulation != "adama_layerwise":
            return ("zero_async=True requires the bucketed ZeRO-1 schedule "
                    "(zero_bucketed=True, or the adama_layerwise stream): "
                    "the full-pack schedule has a single monolithic "
                    "psum_scatter — there is no second bucket to double-"
                    "buffer; drop zero_bucketed=False or zero_async")
    if opt.grad_dtype not in GRAD_DTYPES:
        return (f"unknown grad_dtype {opt.grad_dtype!r}; expected one of "
                f"{GRAD_DTYPES}")
    if opt.grad_dtype != "fp32" and not opt.arena:
        return (f"grad_dtype={opt.grad_dtype!r} requires arena=True: the "
                f"gradient wire is the packed arena slab (core/arena.py); "
                f"pass arena=True use_pallas=True")
    if opt.grad_dtype != "fp32" and opt.accumulation == "ga":
        return (f"grad_dtype={opt.grad_dtype!r} with accumulation='ga' is "
                f"unsupported: the ga engine SUMS raw gradients across "
                f"micro-batches in the wire buffer, and bf16 accumulation "
                f"loses the fp32-accumulation guarantee the AdamA fold "
                f"kernels provide (they upcast in-kernel); use "
                f"accumulation='adama' or 'adama_layerwise'")
    if opt.grad_dtype == "fp8_e4m3" and not opt.finite_guard:
        return ("grad_dtype='fp8_e4m3' requires finite_guard=True: e4m3 "
                "has no inf (overflow surfaces only as NaN codes, which "
                "the fused guards catch) and the error-feedback residual "
                "state['ef'] must be skip-predicated so a vetoed "
                "micro-batch does not corrupt it; pass finite_guard=True")
    if opt.master_params and not opt.arena:
        return ("master_params=True requires arena=True: the fp32 master "
                "region is a packed arena alongside m/v "
                "(core/state_store.py); pass arena=True use_pallas=True")
    if opt.work_param_cache and not opt.master_params:
        return ("work_param_cache=True requires master_params=True: the "
                "cache holds BF16 working params, so the fp32 truth must "
                "live in the master region 'p' — caching without a master "
                "would make the bf16 cast the stored truth and the "
                "precision loss would compound every step; pass "
                "master_params=True (or drop work_param_cache)")
    if opt.finite_guard and not opt.arena:
        return ("finite_guard=True requires arena=True: the per-fold finite "
                "flag is a reduction over the packed gradient slab "
                "(kernels/fused_step.py); pass arena=True use_pallas=True")
    try:
        scale = parse_loss_scale(opt.loss_scale)
    except ValueError as e:
        return str(e)
    if scale != "off":
        if opt.accumulation == "ga":
            return (f"loss_scale={opt.loss_scale!r} with accumulation='ga' "
                    f"is unsupported: the ga engine folds the whole "
                    f"accumulated gradient once per step, so a skip loses "
                    f"the entire mini-batch — too coarse a signal to drive "
                    f"the dynamic backoff (and the ga wire is fp32-only "
                    f"anyway); use accumulation='adama' or "
                    f"'adama_layerwise'")
        if opt.grad_dtype not in ("bf16", "fp8_e4m3"):
            return (f"loss_scale={opt.loss_scale!r} requires a reduced-"
                    f"precision gradient wire (grad_dtype='bf16' or "
                    f"'fp8_e4m3' — loss scaling protects the wire), got "
                    f"grad_dtype={opt.grad_dtype!r}; pass grad_dtype='bf16' "
                    f"or loss_scale='off'")
        if not opt.finite_guard:
            return (f"loss_scale={opt.loss_scale!r} requires "
                    f"finite_guard=True: skipped micro-batches drive the "
                    f"scale backoff, and an unguarded scaled run would fold "
                    f"scaled NaN/Inf into the arena; pass finite_guard=True")
    if opt.scaler_growth_interval <= 0:
        return (f"scaler_growth_interval must be > 0, got "
                f"{opt.scaler_growth_interval}")
    if opt.scaler_abort_after < 0:
        return (f"scaler_abort_after must be >= 0 (0 disables the abort), "
                f"got {opt.scaler_abort_after}")
    return None


def validate_optimizer_config(opt: "OptimizerConfig") -> None:
    reason = optimizer_capability(opt)
    if reason is not None:
        raise ValueError(reason)


def mesh_capability(opt: "OptimizerConfig", mesh_shape: Tuple[int, ...],
                    mesh_axes: Tuple[str, ...], *, tp_axis: Optional[str],
                    engine: str = "shardmap") -> Optional[str]:
    """Mesh-composition capability matrix: None when `opt` runs on a mesh of
    `mesh_shape` x `mesh_axes` with tensor-parallel axis `tp_axis` under
    `engine`, else an actionable refusal naming the unsupported combo.

    The supported compositions:

      pjit engine          : any mesh; tp_axis is a sharding-rules concern
                             (sharding/rules.py), ZeRO-1 per-leaf or arena
                             row sharding both compose with auto TP.
      shardmap, tp_axis
        absent or size 1   : all mesh axes are manual DP axes (the pure-DP
                             profile) — every optimizer feature composes,
                             including a MULTI-AXIS manual dp product
                             (e.g. 2x2 'data' x 'model' both manual), which
                             is bitwise identical to the flat dp mesh of
                             the same size (the reduce-scatter ring order
                             is the linearized axis product either way).
      shardmap, tp_axis
        size > 1           : manual-DP x auto-TP (jax.shard_map with
                             axis_names=). master_params refuses until the
                             working-row all-gather learns a tp-subgroup
                             layout.
    """
    if len(mesh_shape) != len(mesh_axes):
        return (f"mesh_shape={mesh_shape} and mesh_axes={mesh_axes} "
                f"disagree in rank; give one size per axis name")
    if tp_axis is not None and tp_axis not in mesh_axes and mesh_axes:
        return (f"tp_axis={tp_axis!r} is not a mesh axis "
                f"(mesh_axes={mesh_axes}); name one of the mesh axes or "
                f"pass tp_axis=None")
    if engine not in ("pjit", "shardmap"):
        return f"unknown engine {engine!r}; expected 'pjit' or 'shardmap'"
    if engine == "pjit":
        return None
    sizes = dict(zip(mesh_axes, mesh_shape))
    tp = sizes.get(tp_axis, 1) if tp_axis is not None else 1
    if tp <= 1:
        return None                       # pure manual-DP product: supported
    if opt.master_params:
        return (f"master_params=True under mixed manual-DP x auto-TP "
                f"(tp_axis={tp_axis!r} size {tp}) is unsupported: the "
                f"working-row all-gather emits rows in dp partition order "
                f"and has no tp-subgroup layout yet; drop master_params or "
                f"fold {tp_axis!r} into the manual dp product")
    return None


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    shape: InputShape = INPUT_SHAPES["train_4k"]
    seed: int = 0
    steps: int = 100
    log_every: int = 10
    # mesh: axis sizes; () = single device
    mesh_shape: Tuple[int, ...] = ()
    mesh_axes: Tuple[str, ...] = ()
    fsdp: bool = False           # shard params over data axis too
    remat: bool = False          # activation checkpointing per layer
    engine: str = "pjit"         # pjit | shardmap
    checkpoint_dir: Optional[str] = None
    # checkpoint cadence in steps; 0 = legacy max(log_every*5, 50)
    checkpoint_every: int = 0
    # checkpoint retention (train/checkpoint.py _gc)
    keep_last_n: int = 3
    # fault-injection spec (train/faults.py parse_fault), test-only:
    # e.g. "nan@micro=1", "inf@micro=2,device=3,step=0", "crash@step=3"
    inject_fault: Optional[str] = None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "stablelm_1_6b",
    "minicpm3_4b",
    "deepseek_v2_236b",
    "rwkv6_7b",
    "deepseek_v2_lite_16b",
    "mistral_nemo_12b",
    "hymba_1_5b",
    "yi_9b",
    "whisper_base",
    "internvl2_26b",
    "bert_large",                # the paper's own workload
]

_ALIASES = {a.replace("_", "-"): a for a in ARCH_IDS}


def get_config(arch: str) -> ModelConfig:
    arch = _ALIASES.get(arch, arch).replace("-", "_").replace(".", "_")
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def all_configs():
    return {a: get_config(a) for a in ARCH_IDS}
