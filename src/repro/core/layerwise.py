"""Algorithm 2: interleave the per-LAYER backward with the AdamA fold.

PyTorch does this with backward hooks; XLA has no hooks, so we express the
schedule structurally: a reverse `lax.scan` over the stacked layer params
computes each layer's VJP and immediately folds the layer gradient into the
layer's slice of (m, v). The gradient tensor `dlp` is a scan-body temp — its
buffer dies inside the iteration, so peak gradient memory is ONE layer, which
is the paper's 1/M claim.

Non-stacked leaves (embedding, head, final norms — and for whisper the
encoder handled as its own stacked stage) are folded at the boundaries, as in
the paper where the hook granularity is also per-parameter-group.

Note: each layer's forward is recomputed inside its VJP (we saved only the
layer INPUTS), so this engine is simultaneously activation checkpointing —
matching how gradient accumulation baselines are run in the paper's setting.

Arena mode (state from adama.init_arena): (m, v) are flat (rows, LANES)
buffers packed LAYER-MAJOR (core/arena.py), so layer j's entire parameter
group is one contiguous row range. Each backward-scan iteration packs the
layer gradient tree into a single slab and folds it into the layer's arena
slice with ONE offset-indexed kernel (kernels/fused_step.arena_fold_slice) —
O(1) dispatches per layer instead of O(leaves) — and the begin-minibatch
decay rides into micro-batch 0's folds as SMEM scalars.

BOTH moments may be codec-encoded (core/state_store.py): the backward scan
carries each codec's column tuple (e.g. int8 codes + scale column) and the
slice fold dequants/requants both moments in the same single kernel, so the
dispatch count per layer is unchanged for every (m_codec, v_codec) pair.
Replicated codec columns (rowcol's column sums) are decayed once per
micro-batch before the scan — a slice fold sees only its rows and must not
decay shared state per layer.

ZeRO-1 streaming (`zero=ZeroStream(...)`, driven by the shard_map DP engine
in core/dp_shardmap.py): the state carried through the backward scan is the
device's OWNED row block, and each layer's packed gradient slab is
psum_scatter'd the moment the VJP emits it — the received fully-reduced
slice folds straight into the owned block at the layer's partition offset
(core/buckets.py). No gradient tree and no gradient arena ever materialize:
peak live gradient memory is ONE layer's slab, and layer j's collective
overlaps layer j+1's VJP. The rest region streams the same way, one
size-capped bucket at a time, at the stage boundary.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (FOLD_SCOPE, GRAD_PACK_SCOPE, MODEL_SCOPE,
                                RECOMPUTE_SCOPE, ModelConfig)
from repro.core import arena as arena_mod
from repro.core.adama import accumulate_leaf, is_arena_state
from repro.core.arena import STACK_KEYS
from repro.models import modules as md
from repro.models.model import (apply_block, cross_entropy, embed_tokens,
                                main_stack_kind, _cdt)


@dataclass(frozen=True)
class ZeroStream:
    """Bucketed ZeRO-1 streaming context for the layer-wise engine: the
    bucket plan (core/buckets.py), the DP axis names to reduce-scatter
    over, and the replicated-column decay pair (dv pre-divided by the DP
    size so per-shard rowcol column partials psum to the exact global
    statistic — see core/dp_shardmap.py). `rank` is the linear dp index as
    a traced scalar (the sharded-iota input dp_shardmap feeds its
    local_step) — preferred over lax.axis_index, which lowers to a
    PartitionId op GSPMD cannot partition under mixed manual/auto meshes.
    `zero_async` double-buffers the REST-region bucket stream (the stack
    layers already overlap each reduce-scatter with the next layer's VJP
    by construction): bucket i+1's pack + reduce-scatter is issued while
    bucket i's slice folds, barrier-pinned to exactly two live buckets —
    bitwise identical to the serial stream."""
    plan: Any
    axis_names: Tuple[str, ...]
    replicated_decay: Optional[Tuple] = None
    rank: Any = None
    zero_async: bool = False


@jax.named_scope(FOLD_SCOPE)
def _fold_tree(m, v, g, beta1, beta2, use_pallas):
    fold = functools.partial(accumulate_leaf, beta1=beta1, beta2=beta2,
                             use_pallas=use_pallas)
    folded = jax.tree.map(fold, m, v, g)
    new_m = jax.tree.map(lambda x: x[0], folded,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda x: x[1], folded,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_m, new_v


def _agree(ok, zero):
    """Cross-device agreement of a guard verdict under ZeRO-1 streaming:
    all shards skip or none do (a shard folding while its peers skip would
    desync the row ranges). One scalar psum; identity without `zero`."""
    if zero is None:
        return ok
    return lax.psum(1.0 - ok.astype(jnp.float32), zero.axis_names) == 0


def _is_fp8(grad_dtype) -> bool:
    return jnp.dtype(grad_dtype) == jnp.dtype(jnp.float8_e4m3fn)


def _lin_index(axis_names):
    """Linear device index over the DP axes, matching the tiled block
    order of psum_scatter/all_gather (same nesting as dp_shardmap)."""
    d = jnp.int32(0)
    for a in axis_names:
        d = d * lax.psum(1, a) + lax.axis_index(a)
    return d


def _zero_rank(zero):
    """The stream's linear dp rank: the pre-sharded iota (zero.rank) when
    the driver provides it — mandatory under mixed manual/auto meshes,
    where lax.axis_index's PartitionId cannot be partitioned — else the
    axis_index fallback for standalone use."""
    return zero.rank if zero.rank is not None else _lin_index(zero.axis_names)


@jax.named_scope(GRAD_PACK_SCOPE)
def _fp8_wire_slab(slab, axis_names, ef_c, ef_scale, own_offset, own_rows,
                   row0):
    """Shared fp8-wire front half for a packed gradient slab (used by this
    engine AND core/dp_shardmap.py's bucketed schedule): inject this
    device's error-feedback residual into its OWNED rows (`row0` within the
    slab; `own_offset` within the residual/owned block), pmax-agree the
    per-row maxima so every summand of the coming reduce-scatter quantizes
    under ONE shared scale column (with a device-count of headroom so the
    sum of codes stays inside e4m3's finite range), and encode. Returns
    (codes, own-rows scale column, injected slab). axis_names=None is the
    pjit/single-device path: whole-slab residual, headroom 1, and the
    codes ARE the received slab."""
    from repro.kernels.adama_accum import fp8_quantize_rows, fp8_scale_rows
    if axis_names is None:
        if ef_c is not None:
            ef_rows = lax.dynamic_slice_in_dim(ef_c, own_offset, own_rows, 0)
            slab = slab + ef_rows * ef_scale
        rowmax = jnp.max(jnp.abs(slab), axis=-1, keepdims=True)
        s_col = fp8_scale_rows(rowmax)
        return fp8_quantize_rows(slab, s_col), s_col, slab
    if ef_c is not None:
        ef_rows = lax.dynamic_slice_in_dim(ef_c, own_offset, own_rows, 0)
        mine = lax.dynamic_slice_in_dim(slab, row0, own_rows, 0)
        slab = lax.dynamic_update_slice_in_dim(
            slab, mine + ef_rows * ef_scale, row0, 0)
    rowmax = lax.pmax(jnp.max(jnp.abs(slab), axis=-1, keepdims=True),
                      axis_names)
    s_col = fp8_scale_rows(rowmax, lax.psum(1, axis_names))
    codes = fp8_quantize_rows(slab, s_col)
    s_own = lax.dynamic_slice_in_dim(s_col, row0, own_rows, 0)
    return codes, s_own, slab


@jax.named_scope(GRAD_PACK_SCOPE)
def _fp8_ef_update(ef_c, ok, slab, codes, s_own, ef_scale, own_offset,
                   own_rows, row0, axis_names):
    """Back half of the fp8 wire: fold the quantization error THIS device
    left on its owned rows back into the residual, in unscaled units
    (divide the loss scale out), predicated on the same flag as the fold —
    a skipped micro-batch leaves the residual bitwise. Under `axis_names`
    the peers' quantization errors on those rows are dropped (each device
    only knows its own contribution); the pjit path keeps the textbook
    residual."""
    from repro.kernels.adama_accum import fp8_decode_rows
    if axis_names is None:
        inj, mine = slab, codes
    else:
        inj = lax.dynamic_slice_in_dim(slab, row0, own_rows, 0)
        mine = lax.dynamic_slice_in_dim(codes, row0, own_rows, 0)
    ef_new = (inj - fp8_decode_rows(mine, s_own)) / ef_scale
    return jnp.where(ok, lax.dynamic_update_slice_in_dim(
        ef_c, ef_new, own_offset, 0), ef_c)


def _pre_guard(guard, dx, d_rest_post, zero):
    """The pre-backward guard flag: the external verdict (True = none)
    ANDed with finiteness of the head/final-norm gradients and the backward
    seed dx — computed BEFORE any fold or replicated decay commits, and
    psum-agreed under `zero`. A loss-originated NaN is caught here, making
    the whole micro-batch a bitwise no-op."""
    if guard is None:
        return None
    ok = jnp.asarray(True) if guard is True else jnp.asarray(guard)
    ok = jnp.logical_and(ok, jnp.isfinite(dx).all())
    for leaf in jax.tree.leaves(d_rest_post):
        ok = jnp.logical_and(ok, jnp.isfinite(leaf).all())
    return _agree(ok, zero)


def layerwise_loss_and_fold(cfg: ModelConfig, params, batch, state, *,
                            beta1: float, beta2: float, scale: float,
                            use_pallas: bool = False, decay=None, zero=None,
                            grad_dtype=jnp.float32, fold_scale=1.0,
                            guard=None):
    """One micro-batch: forward, then layer-by-layer backward folding grads
    into (m, v). Returns (loss, new_state). Gradients are scaled by `scale`
    (= 1/N; 1/(N*M) under DP), matching Algorithm 1 line 6. `decay` (arena
    mode only) fuses the begin-minibatch decay into this micro-batch's
    folds. `zero` (a ZeroStream) streams every fold through a per-bucket
    psum_scatter into the device's OWNED row block — `state` then carries
    the shard-local columns, in partition order. `grad_dtype` (arena mode)
    is the gradient WIRE dtype: each layer's slab is packed — and
    reduce-scattered, under `zero` — as bf16, halving the live slab and the
    collective payload; the slice-fold kernel upcasts in-pass. With
    float8_e4m3fn each slab is instead ENCODED (fp8 codes + a pmax-agreed
    per-row scale column, 0.25x the fp32 payload) and decoded inside the
    fold kernel; when the state carries the error-feedback residual "ef",
    the owned rows' residual is injected pre-quantization and updated
    per slab, riding the backward scan's carry. fp8 requires `guard`.

    Loss scaling (train/scaler.py): the engine seeds the backward with
    `scale * S` (a traced `scale` is fine) so every wire slab carries
    S-scaled values, and passes `fold_scale = 1/S` so the kernels divide S
    back out on the fp32 upcast — the folded moments never see the scale.

    `guard` (arena mode; OptimizerConfig.finite_guard): True self-checks,
    a traced bool is ANDed in (the engines' forced-skip fault hook). The
    pre-backward flag checks dx and the post-head rest gradients — and is
    psum-AGREED under `zero` — then predicates the begin_micro decay;
    every layer/rest slab is re-checked where it is FOLDED (post-reduce-
    scatter under `zero`, with per-slab agreement) and the verdict carried
    monotonically (once false, every later fold is off). The return
    becomes (loss, new_state, ok). A loss-originated NaN (the realistic
    case) reaches dx and therefore every slab, so the whole micro-batch is
    a bitwise no-op; a NaN born INSIDE one layer's backward can leave
    later-folded (earlier-scanned) layers committed — the streaming
    engine's documented tradeoff, bounded by the monotone carry."""
    assert decay is None or is_arena_state(state), \
        "fused decay requires arena-backed state"
    assert zero is None or is_arena_state(state), \
        "ZeRO-1 streaming requires arena-backed state"
    assert guard is None or is_arena_state(state), \
        "finite guards require arena-backed state"
    if cfg.arch_type == "audio":
        return _layerwise_audio(cfg, params, batch, state, beta1=beta1,
                                beta2=beta2, scale=scale,
                                use_pallas=use_pallas, decay=decay,
                                zero=zero, grad_dtype=grad_dtype,
                                fold_scale=fold_scale, guard=guard)

    kind = main_stack_kind(cfg)
    causal = cfg.arch_type != "encoder"
    tokens = batch["tokens"]
    b, s = tokens.shape
    rest = {k: v for k, v in params.items() if k not in STACK_KEYS}
    scale = jnp.asarray(scale, jnp.float32)

    if cfg.arch_type == "vlm":
        patches = batch["patches"].astype(_cdt(cfg))
        p_ = patches.shape[1]
        total = p_ + s
        positions = jnp.broadcast_to(jnp.arange(total, dtype=jnp.int32),
                                     (b, total))

        @jax.named_scope(MODEL_SCOPE)
        def pre(rest_):
            xt = embed_tokens(cfg, rest_, tokens, positions[:, p_:])
            return jnp.concatenate([patches, xt], axis=1)
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        @jax.named_scope(MODEL_SCOPE)
        def pre(rest_):
            return embed_tokens(cfg, rest_, tokens, positions)

    # ---- forward, saving layer inputs ----
    x0, pre_vjp = jax.vjp(pre, rest)

    stages = []
    if "dense_blocks" in params:
        stages.append(("dense_blocks", "dense"))
    stages.append(("blocks", kind))

    from repro.sharding.ctx import maybe_shard

    @jax.named_scope(MODEL_SCOPE)
    def fwd_stack(stack, x, knd):
        def f(carry, lp):
            h, auxs = carry
            y, a = apply_block(cfg, lp, h, positions, kind=knd, causal=causal)
            # 2D-shard the carry so the saved-input stack (the ys below) is
            # sharded over batch x d_model, not one axis (see model.scan_blocks)
            y = maybe_shard(y, "dp", None, "model")
            return (y, auxs + a), h                       # emit layer INPUT
        x = maybe_shard(x, "dp", None, "model")
        (y, auxs), saved = lax.scan(f, (x, jnp.zeros((), jnp.float32)), stack)
        return y, auxs, saved

    x = x0
    aux_total = jnp.zeros((), jnp.float32)
    saved_inputs: Dict[str, Any] = {}
    for name, knd in stages:
        x, auxs, saved_inputs[name] = fwd_stack(params[name], x, knd)
        aux_total = aux_total + auxs

    @jax.named_scope(MODEL_SCOPE)
    def post(rest_, xn):
        xf = xn[:, -s:] if cfg.arch_type == "vlm" else xn
        h = md.apply_norm(cfg, rest_, xf, "final_norm_")
        logits = (h @ rest_["lm_head"].astype(h.dtype)).astype(jnp.float32)
        return cross_entropy(logits, batch["labels"])

    ce, post_vjp = jax.vjp(post, rest, x)
    loss = ce + aux_total
    d_rest_post, dx = post_vjp(scale)

    # ---- backward, reverse scan per stack, folding per layer ----
    # Tree mode: (m, v) stacks ride in the CARRY and are updated in place
    # with dynamic_update_index — as scan ys they would be double-buffered
    # (xs and ys can't alias), costing an extra m+v of stack memory.
    # Arena mode: the WHOLE (m, v) arenas ride in the carry; each iteration
    # folds into layer j's row slice via one offset-indexed kernel (rows
    # outside the slice pass through aliased, so there is no re-write).
    arena_st = is_arena_state(state)
    guarded = guard is not None
    fp8 = _is_fp8(grad_dtype)
    assert not fp8 or (guarded and arena_st), \
        "fp8 wire requires finite guards over arena state " \
        "(OptimizerConfig enforces finite_guard for grad_dtype='fp8_e4m3')"
    use_ef = fp8 and "ef" in state
    # residual stored UNSCALED; slabs carry the loss scale S (the VJP seed),
    # so injection multiplies by S = 1/fold_scale and the update divides it
    ef_scale = 1.0 / fold_scale if fp8 else None
    ef_acc = state["ef"].data if use_ef else None
    ok = _pre_guard(guard, dx, d_rest_post, zero)
    if arena_st:
        from repro.core import state_store
        mc, vc = state_store.state_codecs(state)
        codec = (mc, vc)
        lay = state["m"].layout
        m_acc = mc.parts_of(state["m"])          # codec column tuples
        v_acc = vc.parts_of(state["v"])
        if decay is not None:
            # replicated codec columns (e.g. rowcol's column sums) decay
            # ONCE per micro-batch here — the per-layer slice folds below
            # each see only part of the rows and must not decay them again.
            # Under ZeRO-1 the dv is pre-divided by the DP size so the
            # per-shard partials psum to the exact global statistic.
            # Guarded, the decay is where-predicated on the pre-backward
            # flag (skip => replicated columns stay bitwise).
            rdm, rdv = (decay if zero is None or zero.replicated_decay is None
                        else zero.replicated_decay)
            with jax.named_scope(FOLD_SCOPE):
                m_acc = state_store._guarded_begin_micro(mc, m_acc, rdm, ok)
                v_acc = state_store._guarded_begin_micro(vc, v_acc, rdv, ok)
    else:
        codec = None
        new_m = dict(state["m"])
        new_v = dict(state["v"])
    for name, knd in reversed(stages):
        n_layers = jax.tree.leaves(params[name])[0].shape[0]
        spec = lay.stack(name) if arena_st else None

        def bwd(carry, xs, knd=knd, spec=spec):
            ef_cc = None
            if use_ef:
                dx_c, m_c, v_c, ef_cc, ok_c = carry
            elif guarded:
                dx_c, m_c, v_c, ok_c = carry
            else:
                (dx_c, m_c, v_c), ok_c = carry, None
            j, lp, xin = xs
            # the layer's forward is recomputed here (only its input was
            # saved): a backward cost, as remat's recompute is
            _, vjp = jax.vjp(jax.named_scope(RECOMPUTE_SCOPE)(
                lambda lp_, xi_: apply_block(cfg, lp_, xi_, positions,
                                             kind=knd, causal=causal)),
                lp, xin)
            dlp, dxin = vjp((dx_c, scale))               # aux cotangent=scale
            out = _fold_layer(m_c, v_c, dlp, j, spec, lay if arena_st
                              else None, beta1, beta2, use_pallas, decay,
                              codec, zero, grad_dtype, fold_scale, ok_c,
                              ef_cc, ef_scale)
            if use_ef:
                m_c, v_c, ef_cc, ok_c = out
                return (dxin, m_c, v_c, ef_cc, ok_c), None
            if guarded:
                m_c, v_c, ok_c = out
                return (dxin, m_c, v_c, ok_c), None
            m_c, v_c = out
            return (dxin, m_c, v_c), None

        carry0 = ((dx, m_acc, v_acc, ef_acc, ok) if use_ef else
                  (dx, m_acc, v_acc, ok) if guarded else
                  (dx, m_acc, v_acc) if arena_st else
                  (dx, state["m"][name], state["v"][name]))
        xs = (jnp.arange(n_layers), params[name], saved_inputs[name])
        if use_ef:
            (dx, m_new, v_new, ef_acc, ok), _ = lax.scan(bwd, carry0, xs,
                                                         reverse=True)
        elif guarded:
            (dx, m_new, v_new, ok), _ = lax.scan(bwd, carry0, xs,
                                                 reverse=True)
        else:
            (dx, m_new, v_new), _ = lax.scan(bwd, carry0, xs, reverse=True)
        if arena_st:
            m_acc, v_acc = m_new, v_new
        else:
            new_m[name], new_v[name] = m_new, v_new

    (d_rest_pre,) = pre_vjp(dx)
    d_rest = jax.tree.map(lambda a, b_: a + b_, d_rest_post, d_rest_pre)
    if arena_st:
        out = _fold_rest(m_acc, v_acc, d_rest, lay, beta1, beta2,
                         decay, codec, zero, grad_dtype, fold_scale, ok,
                         ef_c=ef_acc, ef_scale=ef_scale)
        m_acc, v_acc = out[0], out[1]
        new_state = dict(state, m=mc.wrap(lay, m_acc), v=vc.wrap(lay, v_acc))
        if use_ef:
            new_state = dict(new_state, ef=state["ef"].with_data(out[2]))
            return loss, new_state, out[3]
        if guarded:
            return loss, new_state, out[2]
        return loss, new_state
    for k in d_rest:
        new_m[k], new_v[k] = _fold_tree(state["m"][k], state["v"][k],
                                        d_rest[k], beta1, beta2, use_pallas)
    return loss, {"m": new_m, "v": new_v, "step": state["step"]}


def _fold_layer(m_c, v_c, dlp, j, spec, lay, beta1, beta2, use_pallas, decay,
                codec=None, zero=None, grad_dtype=jnp.float32,
                fold_scale=1.0, guard_ok=None, ef_c=None, ef_scale=None):
    """Fold one layer's gradient tree. Tree mode: per-leaf fold into row j of
    the (m, v) stacks. Arena mode: pack dlp into one slab and fold it into
    the layer's arena row slice with a single offset-indexed kernel fusing
    BOTH moments' codec transforms (codec is the (m_codec, v_codec) pair;
    m_c/v_c their column tuples). Grads arrive pre-scaled (via the VJP
    cotangent), so the kernel scale is `fold_scale` = 1 — or 1/S under loss
    scaling, un-scaling in the upcast. With `zero` the slab is
    reduce-scattered the moment it exists and the received slice folds into
    the OWNED block at the layer's partition offset — the slab has no
    reader after the collective, so its buffer dies inside the iteration.
    `guard_ok` (traced bool): the carried finite verdict; this slab is
    re-checked where it lands (post-reduce-scatter, agreed under `zero`),
    the fold is guard-predicated, and the return gains the updated flag.

    fp8 wire (grad_dtype=float8_e4m3fn; requires guard_ok): the slab packs
    fp32, the owned rows gain the error-feedback residual (`ef_c`, scaled
    back up by `ef_scale` = the loss scale), the CODES reduce-scatter under
    a pmax-agreed per-row scale column, and the fold decodes in-kernel
    (`grad_scale`). With `ef_c` the return becomes (m, v, ef, ok)."""
    if lay is not None and _is_fp8(grad_dtype):
        from repro.core import state_store
        assert guard_ok is not None, \
            "fp8 wire requires finite guards (e4m3 has no inf; NaN codes " \
            "are the only overflow signal)"
        with jax.named_scope(GRAD_PACK_SCOPE):
            g2 = arena_mod.pack_layer(dlp, spec, dtype=jnp.float32)
        if zero is not None:
            base, lslice, block = zero.plan.stack_slice(spec.name)
            off = base + j * lslice
            row0 = _zero_rank(zero) * lslice
            rows = lslice
        else:
            off = spec.row + j * spec.layer_rows
            block = lay.slice_block(spec)
            row0, rows = off, spec.layer_rows
        names = zero.axis_names if zero is not None else None
        codes, s_own, g2 = _fp8_wire_slab(g2, names, ef_c, ef_scale, off,
                                          rows, row0)
        own = (lax.psum_scatter(codes, zero.axis_names,
                                scatter_dimension=0, tiled=True)
               if zero is not None else codes)
        ok = jnp.logical_and(guard_ok,
                             _agree(jnp.isfinite(own).all(), zero))
        m2, v2, _ = state_store.fold_slice(
            codec[0], codec[1], m_c, v_c, own, off, beta1=beta1,
            beta2=beta2, block=block, scale=fold_scale, decay=decay,
            grad_dtype=grad_dtype, grad_scale=s_own, guard=ok)
        if ef_c is None:
            return m2, v2, ok
        ef_c = _fp8_ef_update(ef_c, ok, g2, codes, s_own, ef_scale, off,
                              rows, row0, names)
        return m2, v2, ef_c, ok
    if lay is not None:
        from repro.core import state_store
        with jax.named_scope(GRAD_PACK_SCOPE):
            g2 = arena_mod.pack_layer(dlp, spec, dtype=grad_dtype)
        if zero is not None:
            g2 = lax.psum_scatter(g2, zero.axis_names, scatter_dimension=0,
                                  tiled=True)
            base, lslice, block = zero.plan.stack_slice(spec.name)
            off = base + j * lslice
        else:
            off = spec.row + j * spec.layer_rows
            block = lay.slice_block(spec)
        if guard_ok is not None:
            ok = jnp.logical_and(guard_ok,
                                 _agree(jnp.isfinite(g2).all(), zero))
            m2, v2, _ = state_store.fold_slice(
                codec[0], codec[1], m_c, v_c, g2, off, beta1=beta1,
                beta2=beta2, block=block, scale=fold_scale, decay=decay,
                grad_dtype=grad_dtype, guard=ok)
            return m2, v2, ok
        return state_store.fold_slice(
            codec[0], codec[1], m_c, v_c, g2, off, beta1=beta1, beta2=beta2,
            block=block, scale=fold_scale, decay=decay, grad_dtype=grad_dtype)
    m_j = jax.tree.map(lambda s: lax.dynamic_index_in_dim(
        s, j, 0, keepdims=False), m_c)
    v_j = jax.tree.map(lambda s: lax.dynamic_index_in_dim(
        s, j, 0, keepdims=False), v_c)
    m2, v2 = _fold_tree(m_j, v_j, dlp, beta1, beta2, use_pallas)
    m_c = jax.tree.map(
        lambda s, u: lax.dynamic_update_index_in_dim(s, u, j, 0), m_c, m2)
    v_c = jax.tree.map(
        lambda s, u: lax.dynamic_update_index_in_dim(s, u, j, 0), v_c, v2)
    return m_c, v_c


def _fold_rest(m_acc, v_acc, d_rest, lay, beta1, beta2, decay, codec,
               zero=None, grad_dtype=jnp.float32, fold_scale=1.0,
               guard_ok=None, ef_c=None, ef_scale=None):
    """Arena mode: fold ALL non-stacked leaves' gradients with one
    codec-aware kernel over the contiguous rest region. With `zero` the
    region streams one size-capped bucket at a time: pack the bucket's rows
    only, reduce-scatter, fold the received slice into the owned block —
    the region's packed gradient is never live all at once. `guard_ok`
    (traced bool): each slab re-checked where it folds, verdict carried
    monotonically, return gains the final flag. fp8 wire: each slab runs
    the encode + scale-agreement front half (_fp8_wire_slab) so the
    reduce-scatter moves codes; with `ef_c` the residual updates per slab
    and the return becomes (m, v, ef, ok)."""
    fp8 = _is_fp8(grad_dtype)
    tail = ((ef_c, guard_ok) if ef_c is not None else
            (guard_ok,) if guard_ok is not None else ())
    if not lay.rest.rows:
        return (m_acc, v_acc) + tail
    from repro.core import state_store
    ok = guard_ok
    if fp8:
        assert ok is not None, "fp8 wire requires finite guards"
        if zero is not None:
            for b in zero.plan.grad_buckets():
                if b.kind != "rest":
                    continue
                with jax.named_scope(GRAD_PACK_SCOPE):
                    slab = arena_mod.pack_rest_rows(d_rest, lay, b.start,
                                                    b.stop, dtype=jnp.float32)
                row0 = _zero_rank(zero) * b.slice_rows
                codes, s_own, slab = _fp8_wire_slab(
                    slab, zero.axis_names, ef_c, ef_scale, b.own_offset,
                    b.slice_rows, row0)
                own = lax.psum_scatter(codes, zero.axis_names,
                                       scatter_dimension=0, tiled=True)
                ok = jnp.logical_and(ok,
                                     _agree(jnp.isfinite(own).all(), zero))
                m_acc, v_acc, _ = state_store.fold_slice(
                    codec[0], codec[1], m_acc, v_acc, own, b.own_offset,
                    beta1=beta1, beta2=beta2, block=b.fold_block,
                    scale=fold_scale, decay=decay, grad_dtype=grad_dtype,
                    grad_scale=s_own, guard=ok)
                if ef_c is not None:
                    ef_c = _fp8_ef_update(ef_c, ok, slab, codes, s_own,
                                          ef_scale, b.own_offset,
                                          b.slice_rows, row0,
                                          zero.axis_names)
        else:
            with jax.named_scope(GRAD_PACK_SCOPE):
                g2 = arena_mod.pack_rest(d_rest, lay, dtype=jnp.float32)
            off, rows = lay.rest.row, lay.rest.rows
            codes, s_col, g2 = _fp8_wire_slab(g2, None, ef_c, ef_scale,
                                              off, rows, off)
            ok = jnp.logical_and(ok, jnp.isfinite(codes).all())
            m_acc, v_acc, _ = state_store.fold_slice(
                codec[0], codec[1], m_acc, v_acc, codes, off, beta1=beta1,
                beta2=beta2, block=lay.slice_block(lay.rest),
                scale=fold_scale, decay=decay, grad_dtype=grad_dtype,
                grad_scale=s_col, guard=ok)
            if ef_c is not None:
                ef_c = _fp8_ef_update(ef_c, ok, g2, codes, s_col, ef_scale,
                                      off, rows, off, None)
        return ((m_acc, v_acc, ef_c, ok) if ef_c is not None
                else (m_acc, v_acc, ok))
    if zero is not None:
        rbks = [b for b in zero.plan.grad_buckets() if b.kind == "rest"]

        def issue(b):
            with jax.named_scope(GRAD_PACK_SCOPE):
                slab = arena_mod.pack_rest_rows(d_rest, lay, b.start, b.stop,
                                                dtype=grad_dtype)
            return lax.psum_scatter(slab, zero.axis_names,
                                    scatter_dimension=0, tiled=True)

        def fold(m_acc, v_acc, ok, b, own):
            if ok is not None:
                ok = jnp.logical_and(ok,
                                     _agree(jnp.isfinite(own).all(), zero))
                m_acc, v_acc, _ = state_store.fold_slice(
                    codec[0], codec[1], m_acc, v_acc, own, b.own_offset,
                    beta1=beta1, beta2=beta2, block=b.fold_block,
                    scale=fold_scale, decay=decay, grad_dtype=grad_dtype,
                    guard=ok)
            else:
                m_acc, v_acc = state_store.fold_slice(
                    codec[0], codec[1], m_acc, v_acc, own, b.own_offset,
                    beta1=beta1, beta2=beta2, block=b.fold_block,
                    scale=fold_scale, decay=decay, grad_dtype=grad_dtype)
            return m_acc, v_acc, ok

        if zero.zero_async and len(rbks) > 1:
            # double-buffered rest stream (see ZeroStream docstring):
            # bucket j's reduce-scatter in flight while bucket j-1's
            # slice folds; the barrier pins bucket j+1's pack behind
            # bucket j-1's fold — exactly two rest buckets live, and
            # bitwise the serial stream (same scatters, same folds)
            pending = issue(rbks[0])
            for b_prev, b in zip(rbks, rbks[1:]):
                own = issue(b)
                m_acc, v_acc, ok = fold(m_acc, v_acc, ok, b_prev, pending)
                if ok is not None:
                    m_acc, v_acc, ok, d_rest = lax.optimization_barrier(
                        (m_acc, v_acc, ok, d_rest))
                else:
                    m_acc, v_acc, d_rest = lax.optimization_barrier(
                        (m_acc, v_acc, d_rest))
                pending = own
            m_acc, v_acc, ok = fold(m_acc, v_acc, ok, rbks[-1], pending)
        else:
            for b in rbks:
                m_acc, v_acc, ok = fold(m_acc, v_acc, ok, b, issue(b))
        return (m_acc, v_acc, ok) if guard_ok is not None \
            else (m_acc, v_acc)
    with jax.named_scope(GRAD_PACK_SCOPE):
        g2 = arena_mod.pack_rest(d_rest, lay, dtype=grad_dtype)
    if ok is not None:
        ok = jnp.logical_and(ok, jnp.isfinite(g2).all())
        m_acc, v_acc, _ = state_store.fold_slice(
            codec[0], codec[1], m_acc, v_acc, g2, lay.rest.row, beta1=beta1,
            beta2=beta2, block=lay.slice_block(lay.rest), scale=fold_scale,
            decay=decay, grad_dtype=grad_dtype, guard=ok)
        return m_acc, v_acc, ok
    return state_store.fold_slice(
        codec[0], codec[1], m_acc, v_acc, g2, lay.rest.row, beta1=beta1,
        beta2=beta2, block=lay.slice_block(lay.rest), scale=fold_scale,
        decay=decay, grad_dtype=grad_dtype)


# ---------------------------------------------------------------------------
# Whisper (enc-dec): decoder stack layerwise, then encoder stack layerwise
# ---------------------------------------------------------------------------


def _layerwise_audio(cfg, params, batch, state, *, beta1, beta2, scale,
                     use_pallas, decay=None, zero=None,
                     grad_dtype=jnp.float32, fold_scale=1.0, guard=None):
    tokens = batch["tokens"]
    frames = batch["frames"].astype(_cdt(cfg))
    b, s = tokens.shape
    se = frames.shape[1]
    scale = jnp.asarray(scale, jnp.float32)
    rest = {k: v for k, v in params.items() if k not in STACK_KEYS}
    epos = jnp.broadcast_to(jnp.arange(se, dtype=jnp.int32), (b, se))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    from repro.sharding.ctx import maybe_shard

    # encoder forward (save layer inputs)
    e0 = frames + md.sinusoidal_positions(epos, cfg.d_model).astype(frames.dtype)

    @jax.named_scope(MODEL_SCOPE)
    def enc_f(carry, lp):
        h = carry
        y, _ = apply_block(cfg, lp, h, epos, kind="dense", causal=False)
        return maybe_shard(y, "dp", None, "model"), h
    eN, enc_saved = lax.scan(enc_f, maybe_shard(e0, "dp", None, "model"),
                             params["enc_blocks"])

    @jax.named_scope(MODEL_SCOPE)
    def enc_norm(rest_, en):
        return md.apply_norm(cfg, rest_, en, "enc_norm_")
    enc_out, encn_vjp = jax.vjp(enc_norm, rest, eN)

    @jax.named_scope(MODEL_SCOPE)
    def pre(rest_):
        return embed_tokens(cfg, rest_, tokens, positions)
    x0, pre_vjp = jax.vjp(pre, rest)

    @jax.named_scope(MODEL_SCOPE)
    def dec_block(lp, x, eo):
        enc_kv = md.encode_cross_kv(lp, eo)
        y, a = apply_block(cfg, lp, x, positions, kind="dec", causal=True,
                           enc_kv=enc_kv)
        return y, a

    def dec_f(carry, lp):
        h = carry
        y, _ = dec_block(lp, h, enc_out)
        return maybe_shard(y, "dp", None, "model"), h
    xN, dec_saved = lax.scan(dec_f, maybe_shard(x0, "dp", None, "model"),
                             params["blocks"])

    @jax.named_scope(MODEL_SCOPE)
    def post(rest_, xn):
        h = md.apply_norm(cfg, rest_, xn, "final_norm_")
        logits = (h @ rest_["lm_head"].astype(h.dtype)).astype(jnp.float32)
        return cross_entropy(logits, batch["labels"])
    ce, post_vjp = jax.vjp(post, rest, xN)
    d_rest_post, dx = post_vjp(scale)

    arena_st = is_arena_state(state)
    guarded = guard is not None
    fp8 = _is_fp8(grad_dtype)
    assert not fp8 or (guarded and arena_st), \
        "fp8 wire requires finite guards over arena state"
    use_ef = fp8 and "ef" in state
    ef_scale = 1.0 / fold_scale if fp8 else None
    ef0 = state["ef"].data if use_ef else None
    ok = _pre_guard(guard, dx, d_rest_post, zero)
    if arena_st:
        from repro.core import state_store
        mc, vc = state_store.state_codecs(state)
        codec = (mc, vc)
        lay = state["m"].layout
        m0, v0 = mc.parts_of(state["m"]), vc.parts_of(state["v"])
        if decay is not None:            # replicated columns: once per micro
            rdm, rdv = (decay if zero is None or zero.replicated_decay is None
                        else zero.replicated_decay)
            with jax.named_scope(FOLD_SCOPE):
                m0 = state_store._guarded_begin_micro(mc, m0, rdm, ok)
                v0 = state_store._guarded_begin_micro(vc, v0, rdv, ok)
        dec_spec, enc_spec = lay.stack("blocks"), lay.stack("enc_blocks")
    else:
        codec = None
        lay = dec_spec = enc_spec = None
        new_m = dict(state["m"])
        new_v = dict(state["v"])
        m0, v0 = state["m"]["blocks"], state["v"]["blocks"]

    # decoder backward: carry (dx, d_enc_out accumulator, m, v[, ef][, ok])
    def dbwd(carry, xs):
        ef_cc = None
        if use_ef:
            dx_c, denc, m_c, v_c, ef_cc, ok_c = carry
        elif guarded:
            dx_c, denc, m_c, v_c, ok_c = carry
        else:
            (dx_c, denc, m_c, v_c), ok_c = carry, None
        j, lp, xin = xs
        _, vjp = jax.vjp(dec_block, lp, xin, enc_out)
        dlp, dxin, denc_j = vjp((dx_c, scale))
        out = _fold_layer(m_c, v_c, dlp, j, dec_spec, lay, beta1, beta2,
                          use_pallas, decay, codec, zero, grad_dtype,
                          fold_scale, ok_c, ef_cc, ef_scale)
        if use_ef:
            m_c, v_c, ef_cc, ok_c = out
            return (dxin, denc + denc_j, m_c, v_c, ef_cc, ok_c), None
        if guarded:
            m_c, v_c, ok_c = out
            return (dxin, denc + denc_j, m_c, v_c, ok_c), None
        m_c, v_c = out
        return (dxin, denc + denc_j, m_c, v_c), None

    denc0 = jnp.zeros_like(enc_out)
    nl = jax.tree.leaves(params["blocks"])[0].shape[0]
    dxs = (jnp.arange(nl), params["blocks"], dec_saved)
    if use_ef:
        (dx, denc, m_new, v_new, ef0, ok), _ = lax.scan(
            dbwd, (dx, denc0, m0, v0, ef0, ok), dxs, reverse=True)
    elif guarded:
        (dx, denc, m_new, v_new, ok), _ = lax.scan(
            dbwd, (dx, denc0, m0, v0, ok), dxs, reverse=True)
    else:
        (dx, denc, m_new, v_new), _ = lax.scan(
            dbwd, (dx, denc0, m0, v0), dxs, reverse=True)
    if arena_st:
        m0, v0 = m_new, v_new
    else:
        new_m["blocks"], new_v["blocks"] = m_new, v_new
        m0, v0 = state["m"]["enc_blocks"], state["v"]["enc_blocks"]

    d_rest_encn, d_eN = encn_vjp(denc)

    # encoder backward
    def ebwd(carry, xs):
        ef_cc = None
        if use_ef:
            dx_c, m_c, v_c, ef_cc, ok_c = carry
        elif guarded:
            dx_c, m_c, v_c, ok_c = carry
        else:
            (dx_c, m_c, v_c), ok_c = carry, None
        j, lp, xin = xs
        _, vjp = jax.vjp(jax.named_scope(RECOMPUTE_SCOPE)(
            lambda lp_, xi_: apply_block(cfg, lp_, xi_, epos, kind="dense",
                                         causal=False)), lp, xin)
        dlp, dxin = vjp((dx_c, scale))
        out = _fold_layer(m_c, v_c, dlp, j, enc_spec, lay, beta1, beta2,
                          use_pallas, decay, codec, zero, grad_dtype,
                          fold_scale, ok_c, ef_cc, ef_scale)
        if use_ef:
            m_c, v_c, ef_cc, ok_c = out
            return (dxin, m_c, v_c, ef_cc, ok_c), None
        if guarded:
            m_c, v_c, ok_c = out
            return (dxin, m_c, v_c, ok_c), None
        m_c, v_c = out
        return (dxin, m_c, v_c), None

    ne = jax.tree.leaves(params["enc_blocks"])[0].shape[0]
    exs = (jnp.arange(ne), params["enc_blocks"], enc_saved)
    if use_ef:
        (_, m_new, v_new, ef0, ok), _ = lax.scan(
            ebwd, (d_eN, m0, v0, ef0, ok), exs, reverse=True)
    elif guarded:
        (_, m_new, v_new, ok), _ = lax.scan(
            ebwd, (d_eN, m0, v0, ok), exs, reverse=True)
    else:
        (_, m_new, v_new), _ = lax.scan(
            ebwd, (d_eN, m0, v0), exs, reverse=True)

    (d_rest_pre,) = pre_vjp(dx)
    d_rest = jax.tree.map(lambda a, b_, c: a + b_ + c,
                          d_rest_post, d_rest_encn, d_rest_pre)
    if arena_st:
        out = _fold_rest(m_new, v_new, d_rest, lay, beta1, beta2,
                         decay, codec, zero, grad_dtype, fold_scale, ok,
                         ef_c=ef0, ef_scale=ef_scale)
        m_new, v_new = out[0], out[1]
        new_state = dict(state, m=mc.wrap(lay, m_new), v=vc.wrap(lay, v_new))
        if use_ef:
            new_state = dict(new_state, ef=state["ef"].with_data(out[2]))
            return ce, new_state, out[3]
        if guarded:
            return ce, new_state, out[2]
        return ce, new_state
    new_m["enc_blocks"], new_v["enc_blocks"] = m_new, v_new
    for k in d_rest:
        new_m[k], new_v[k] = _fold_tree(state["m"][k], state["v"][k],
                                        d_rest[k], beta1, beta2, use_pallas)
    return ce, {"m": new_m, "v": new_v, "step": state["step"]}
