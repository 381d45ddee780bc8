"""Faithful §3.3 data-parallel communication schedule, via jax.shard_map.

Three DP variants (benchmarks/fig7_comm.py measures their collective bytes):

  ga     — accumulate local grads over N micro-batches, ONE psum(grads) at
           mini-batch end, then Adam. Comm volume = P per mini-batch.
  naive  — psum each micro-batch's grads before folding into (m, v).
           Comm volume = N*P per mini-batch — the strawman the paper rejects.
  adama  — the paper's schedule: fold LOCAL grads into LOCAL (m, v) each
           micro-batch, pre-scale v by M*beta2 (Eq. 6), one psum of m (/M)
           and v (/M^2) at mini-batch end (Eqs. 7-8). Comm volume = 2*P,
           constant in N, and bit-consistent with single-device AdamA(N*M).

With OptimizerConfig(zero_stage=1, arena=True) the adama variant runs the
ZeRO-1 ROW-RANGE schedule over the flat state arena (the paper's Table-3
"ZeRO-S1 + AdamA" row): device k persistently owns 1/M of EVERY row-indexed
state column (both moments' payloads and any codec scale column, for every
(m_codec, v_codec) pair — see core/state_store.py), each micro-batch's
gradients are psum_scatter'd so the fold runs on 1/M of the state, and the
mini-batch-end apply updates the owned param rows followed by one
all-gather. The one non-row-indexed column (the rowcol codec's (1, LANES)
column sums) is replicated: each shard accumulates its partial with the
decay pre-divided by M, and a single tiny psum per mini-batch restores the
exact global statistic. Optimizer state per device drops to 1/M; the
collectives move from states to gradients, so int8/factored codecs compose
(nothing quantized is ever summed). Comm volume = N*P*(M-1)/M (gradient
reduce-scatters) + P (param all-gather) per mini-batch.

The ZeRO-1 gradient collectives come in two schedules (zero_bucketed):

  BUCKETED (default) — the gradient is reduce-scattered one BUCKET at a
      time (core/buckets.py: per-layer buckets for the stacked regions,
      size-capped buckets for the rest region) and each received slice is
      folded into the owned block with the offset-indexed slice-fold
      kernel. Peak live packed-gradient memory is ONE bucket instead of
      the full arena, and bucket i's collective has no data dependency on
      bucket i+1's fold, so XLA overlaps communication with compute.
      Ownership is slice-k-of-every-bucket, so the RESIDENT sharded state
      is in partition order (buckets.unpermute_state decodes it); params
      and losses are bitwise identical to full-pack for row-local codecs.
  FULL-PACK (zero_bucketed=False, the legacy schedule) — pack the whole
      gradient arena, one monolithic psum_scatter per micro-batch. Simpler,
      but the full gradient arena is live on every device at once and the
      collective serializes the optimizer path.

variant="adama_layerwise" (Algorithm 2 under ZeRO-1, bucketed only): the
per-layer backward streams each layer's packed gradient slab into its
reduce-scatter the moment the VJP emits it — no gradient tree and no
gradient arena ever materialize (see core/layerwise.py's ZeroStream).

Mixed-precision wire (OptimizerConfig.grad_dtype="bf16"): every gradient
slab above — the full-pack arena, each bucket, each layer's layerwise slab
— is PACKED as bf16 and every gradient psum_scatter moves bf16 payloads,
halving both the one-bucket live-gradient peak and the reduce-scatter
volume. The receiving fold kernels upcast to fp32 in-pass, so the (m, v)
accumulation itself is unchanged; a reduction over bf16 payloads matches
the fp32 wire to tolerance, not bitwise — each device's addend is rounded
to bf16 before the collective, and the reduction's own arithmetic is
backend-defined (a ring implementation may round intermediate partial
sums to bf16 at every hop, so the deviation can grow with the DP size;
the declared per-codec tolerances are validated at M=4).

fp8 wire (OptimizerConfig.grad_dtype="fp8_e4m3", bucketed ZeRO-1 +
master_params only): each bucket packs fp32, injects this device's
error-feedback residual into its OWNED rows (state["ef"], row-sharded like
the master region, stored in UNSCALED units), pmax-agrees the per-row
maxima so all M summands quantize under ONE shared scale column (with M
summation headroom inside e4m3's finite range), and the reduce-scatter
moves 1-byte codes — 4x fewer gradient-collective bytes than fp32. The
slice-fold kernels decode in-pass via the `grad_scale` column; the
residual update is predicated on the SAME agreed flag as the fold, so a
skipped micro-batch leaves it bitwise on every shard. The param
all-gather is quantized the same way (encode the emitted working rows,
gather codes + scales, decode on arrival) — total wire bytes land at
~0.26x fp32 for N=4, M=4 (the step-bench ≤0.3x gate). The fp32 master is
the stored truth, so neither quantization ever compounds across steps;
cross-device quantization error on the gradient wire (the part of the
residual only peers could see) is dropped by construction.

Master params (OptimizerConfig.master_params): under ZeRO-1 the state
carries a third row-indexed fp32 region "p" — each device persistently owns
its master rows (partition order under the bucketed schedule), the fused
apply updates them in place and emits bf16 WORKING rows, and the param
all-gather moves those bf16 rows (half the bytes). Params are never
re-packed from the tree: the fp32 truth never leaves the arena.

Async double-buffered bucket pipeline (OptimizerConfig.zero_async, bucketed
ZeRO-1 only): instead of hoping XLA overlaps bucket i's fold with bucket
i+1's reduce-scatter, the schedule is pinned explicitly — bucket i+1's
pack + reduce-scatter is issued while bucket i's received slice folds, and
a lax.optimization_barrier orders bucket i+2's pack AFTER bucket i's fold,
so EXACTLY two gradient buckets are ever live (the serial stream holds
one; an unpinned unroll lets the scheduler hoist every pack up front).
launch/hlo_analysis.py measures both halves of the claim from the
scheduled HLO: `overlap_fraction` (collective payload bytes free to
overlap compute) and `live_peak_reduce-scatter` (the two-bucket high-water
mark launch/dryrun.py gates). The ZeRO-1 param all-gather additionally
moves as a ring of M-1 collective-permutes (`_ring_all_gather`) — same
bytes and BITWISE the same rows as lax.all_gather, but decomposed into
point-to-point hops the scheduler can overlap with the apply epilogue.
Numerics are bitwise identical to the serial bucketed schedule: the
per-bucket psum_scatter and its reduction order are untouched.

Manual axes = the DP axes ("data", and "pod" when multi-pod); the "model"
axis (if present in the mesh) is left to GSPMD (auto) so tensor-parallel
sharding composes (jax.shard_map with axis_names=). Folding the tp axis
into the manual dp product instead — a 2dp x 2tp ("data", "model")
ALL-MANUAL mesh — is bitwise identical to the flat 4-dp mesh, because the
linearized axis product gives the same reduce-scatter ring order. On a
TPU the all-manual form is the one that compiles: XLA cannot partition a
Mosaic kernel along an auto axis, so the arena kernels inside a mixed
manual-dp x auto-tp shard_map run only in interpret mode (CPU). The
linear dp rank used for owned-row indexing and fault
targeting is an iota INPUT sharded over the dp axes (in_spec P(dp_axes)),
not lax.axis_index: axis_index lowers to PartitionId, which GSPMD cannot
partition inside a manual subgroup when auto axes remain.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, OptimizerConfig
from repro.core import adama
from repro.core import arena as arena_mod
from repro.core import buckets as buckets_mod
from repro.core import state_store
from repro.core.accumulation import _fold_decay, _split_micro, make_loss
from repro.core.zero import zero1_bucket_plan
from repro.optim import adam


def _shard_map(f, mesh, *, in_specs, out_specs, manual_axes):
    """jax.shard_map manual over `manual_axes` only, the rest left auto.
    Replication checking is off (psum-of-replicated patterns in the AdamA
    schedule trip it)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=set(manual_axes), check_vma=False)


def _ring_all_gather(x, axis_names, m: int, rank):
    """All-gather of per-device row blocks as a ring of m-1 collective-
    permutes: each step forwards the most recently received block one hop
    down the ring, so after m-1 steps every device holds every block. The
    assembled result is BITWISE lax.all_gather(x, axis=0, tiled=True) —
    blocks move untouched, and the rank-roll restores device order — but
    the transfer is decomposed into point-to-point hops (HLO
    collective-permute) that the scheduler can overlap with compute,
    instead of one blocking gather. `rank` is this device's linear dp
    index (the sharded iota input; see module docstring).

    Each received block is scattered straight into its slot of the
    preallocated result, so the transient footprint stays at the gathered
    array plus ONE in-flight block — a stack + roll-by-`rank` would hold
    the full stack twice (roll of a traced shift lowers to concat +
    dynamic-slice), defeating the memory bound the bucketed schedule
    exists to keep."""
    if m <= 1:
        return x
    axis = axis_names if len(axis_names) > 1 else axis_names[0]
    perm = [(i, (i - 1) % m) for i in range(m)]
    rows = x.shape[0]
    tail0 = (0,) * (x.ndim - 1)
    out = jnp.zeros((m * rows,) + tuple(x.shape[1:]), x.dtype)
    blk = x
    for k in range(m):
        if k:
            blk = lax.ppermute(blk, axis, perm)
        # after k hops this device holds device (rank + k) % m's block,
        # which belongs at block slot (rank + k) % m of the gathered result
        out = lax.dynamic_update_slice(out, blk,
                                       ((rank + k) % m * rows,) + tail0)
    return out


def make_dp_train_step(cfg: ModelConfig, opt: OptimizerConfig, mesh,
                       dp_axes: Tuple[str, ...] = ("data",),
                       variant: str = "adama", *, remat=False,
                       lr_schedule=None, fault=None):
    """Returns (step_fn, opt_init_fn). step_fn(params, opt_state, batch) with
    batch globally (GB, ...) sharded over dp_axes; params/opt replicated over
    dp_axes (tensor sharding over remaining mesh axes passes through).
    `fault` (train/faults.py FaultSpec) injects NaN/Inf/skip faults inside
    the compiled step — with the `device` selector resolving to the linear
    dp index, so one-shard corruption exercises the guard agreement."""
    m_dev = int(math.prod(mesh.shape[a] for a in dp_axes))
    loss = make_loss(cfg, remat=remat)
    n = opt.micro_batches
    b1, b2 = opt.beta1, opt.beta2
    use_arena = opt.use_pallas and opt.arena
    zero1 = opt.zero_stage == 1
    guarded = opt.finite_guard           # config enforces arena=True
    from repro.configs.base import grad_wire_dtype, mesh_capability
    auto_tp = tuple(a for a in mesh.axis_names
                    if a not in dp_axes and mesh.shape[a] > 1)
    tp_shards = int(math.prod(mesh.shape[a] for a in auto_tp)) if auto_tp \
        else 1
    reason = mesh_capability(
        opt, tuple(mesh.shape[a] for a in mesh.axis_names),
        tuple(mesh.axis_names), tp_axis=auto_tp[0] if auto_tp else None,
        engine="shardmap")
    if reason is not None:
        raise ValueError(reason)
    from repro.core.accumulation import is_fp8_wire, use_error_feedback
    wire = grad_wire_dtype(opt.grad_dtype)
    fp8 = is_fp8_wire(opt)
    use_ef = use_error_feedback(opt)
    if opt.work_param_cache:
        raise ValueError(
            "work_param_cache=True is a pjit-engine knob: the shard_map DP "
            "engine's master path already sources params from the owned "
            "arena rows (never re-packing the tree), so there is no "
            "pack/unpack pair to skip — drop work_param_cache or use the "
            "pjit engine")
    if fp8 and not (zero1 and use_arena and
                    (opt.zero_bucketed or variant == "adama_layerwise")):
        raise ValueError(
            "grad_dtype='fp8_e4m3' in the shard_map DP engine requires the "
            "bucketed ZeRO-1 schedule (zero_stage=1, arena=True, "
            "zero_bucketed=True or variant='adama_layerwise'): fp8 codes "
            "ride the per-bucket gradient reduce-scatters under one "
            "pmax-agreed scale column; the replicated schedule psums STATES "
            "(nothing to quantize) and the full-pack scatter has no "
            "per-bucket scale plumbing")
    if fp8 and not opt.master_params:
        raise ValueError(
            "grad_dtype='fp8_e4m3' in the shard_map DP engine requires "
            "master_params=True: the ≤0.3x wire-byte budget only closes "
            "when the param all-gather is quantized too (fp8 grads alone "
            "leave the fp32 gather dominating at ~0.44x), and a quantized "
            "gather needs the fp32 truth resident in the master region so "
            "the wire rounding never compounds across steps")
    if guarded and variant not in ("adama", "adama_layerwise"):
        raise ValueError(
            f"finite_guard=True in the shard_map DP engine is defined for "
            f"the 'adama' and 'adama_layerwise' variants (the guarded fold "
            f"kernels), got variant={variant!r}")
    if zero1 and not use_arena:
        raise ValueError(
            "zero_stage=1 in the shard_map DP engine requires the arena "
            "state store (use_pallas=True, arena=True): ZeRO-1 here shards "
            "the flat arena by row range; the per-leaf ZeRO-1 path lives in "
            "the pjit engine (sharding/rules.opt_pspecs)")
    if zero1 and variant not in ("adama", "adama_layerwise"):
        raise ValueError(
            f"zero_stage=1 row-range sharding is defined for the 'adama' "
            f"and 'adama_layerwise' variants only, got variant={variant!r}")
    if variant == "adama_layerwise" and not (zero1 and use_arena):
        raise ValueError(
            "the shard_map 'adama_layerwise' variant IS the bucketed ZeRO-1 "
            "stream (each layer's gradient reduce-scatters out of the "
            "backward into the owned row range): it requires zero_stage=1 "
            "with the arena state store (arena=True, use_pallas=True). For "
            "replicated-state DP use variant='adama', or run "
            "adama_layerwise in the pjit engine")
    if use_arena and not zero1 and variant == "adama" and \
            (opt.state_codec != "fp32" or opt.m_codec != "fp32"):
        raise ValueError(
            f"m_codec={opt.m_codec!r}/state_codec={opt.state_codec!r} with "
            f"the shard_map DP engine requires zero_stage=1: the "
            f"mini-batch-end state psum (Eqs. 7-8) cannot sum codec-encoded "
            f"moments, while the row-range ZeRO-1 schedule reduce-scatters "
            f"fp32 gradients instead")

    def local_step(params, opt_state, batch, ranks):
        micro = _split_micro(batch, n)
        # linear dp rank of this shard: ranks is the global iota over the
        # dp product, sharded P(dp_axes), so the local block is (1,) and
        # its single element IS the rank (see module docstring for why
        # lax.axis_index cannot be used here)
        dev = ranks[0]

        if variant == "ga":
            def body(carry, mb):
                acc, lsum = carry
                l, g = jax.value_and_grad(lambda p: loss(p, mb))(params)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32) / n, acc, g)
                return (acc, lsum + l), None
            zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                                 params)
            (grads, lsum), _ = lax.scan(body, (zeros, 0.0), micro)
            grads = jax.tree.map(                    # ONE all-reduce of grads
                lambda g: lax.psum(g, dp_axes) / m_dev, grads)
            lr = lr_schedule(opt_state["step"]) if lr_schedule else opt.lr
            params, opt_state = adam.update(grads, opt_state, params, lr=lr,
                                            beta1=b1, beta2=b2, eps=opt.eps,
                                            weight_decay=opt.weight_decay)
            return params, opt_state, {"loss": lax.pmean(lsum / n, dp_axes)}

        if variant in ("adama", "adama_layerwise") and use_arena and zero1:
            # ZeRO-1 row ranges: this device owns 1/M of every ROW-INDEXED
            # state column. Gradients are reduce-scattered per fold (fully-
            # reduced before entering v, so no M*beta2 pre-scale or /M^2
            # correction — the schedule equals single-device AdamA(N) over
            # the full global micro-batch), params all-gathered once.
            # Replicated codec columns (rowcol's column sums) accumulate
            # per-shard partials with their decay pre-divided by M, so ONE
            # tiny psum at mini-batch end restores the exact global
            # statistic (state_store.psum_replicated_state).
            #
            # Bucketed schedule (default): ownership is slice-k-of-every-
            # bucket and each bucket reduce-scatters on its own, streamed
            # into offset-indexed slice folds — peak live packed-gradient
            # memory is ONE bucket, and the collectives overlap the folds.
            # Full-pack (zero_bucketed=False): contiguous row ranges, the
            # whole gradient arena packed before one monolithic scatter.
            lay = opt_state["m"].layout
            rows_own = lay.rows // m_dev
            bucketed = opt.zero_bucketed or variant == "adama_layerwise"
            plan = (zero1_bucket_plan(lay, m_dev, opt.zero_bucket_rows,
                                      tp_shards=tp_shards)
                    if bucketed else None)
            scale = 1.0 / (n * m_dev)
            if guarded:
                from repro.train import faults as fault_mod
                from repro.train import scaler as scaler_mod
                dyn = scaler_mod.is_dynamic(opt)
                gi = opt.scaler_growth_interval

                def fold_micro_g(st, i, mb, good):
                    # step counter not yet advanced: decay shifts to the
                    # first GOOD fold, and the guard verdict is psum-AGREED
                    # before any shard commits — all shards skip or none
                    # do, or the averaged/sharded states would desync
                    sc = st["scaler"]
                    decay = _fold_decay(good, b1, b2, 1)
                    rdecay = (decay[0],
                              jnp.where(good == 0, b2 / m_dev, 1.0))
                    if variant == "adama_layerwise":
                        from repro.core.layerwise import (
                            ZeroStream, layerwise_loss_and_fold)
                        # loss scale rides the VJP seed (slabs carry S on
                        # the wire), un-scaled in-kernel via fold_scale;
                        # nan/inf faults poison the seed (the loss-
                        # originated failure mode); per-layer agreement
                        # rides the reduce-scatter inside layerwise
                        seed = fault_mod.corrupt_loss(
                            fault,
                            jnp.asarray(scale, jnp.float32) * sc["scale"],
                            micro=i, step=st["step"], device=dev)
                        pre = fault_mod.apply_skip(
                            fault, jnp.asarray(True), micro=i,
                            step=st["step"])
                        return layerwise_loss_and_fold(
                            cfg, params, mb, st, beta1=b1, beta2=b2,
                            scale=seed, use_pallas=True, decay=decay,
                            zero=ZeroStream(plan, dp_axes, rdecay,
                                            rank=dev,
                                            zero_async=opt.zero_async),
                            grad_dtype=wire,
                            fold_scale=jnp.float32(1.0) / sc["scale"],
                            guard=pre)
                    l, g = jax.value_and_grad(
                        lambda p: scaler_mod.scale_loss(loss(p, mb),
                                                        sc))(params)
                    g = fault_mod.corrupt_tree(fault, g, micro=i,
                                               step=st["step"], device=dev)
                    kscale = scaler_mod.scale_into_fold(scale, sc)
                    l = l / sc["scale"]
                    if plan is None:
                        g_own = lax.psum_scatter(
                            arena_mod.pack(g, lay, dtype=wire), dp_axes,
                            scatter_dimension=0, tiled=True)
                        # checked POST-reduce-scatter: one corrupt shard
                        # poisons only the slices its elements reduce
                        # into, so the local verdicts differ — agreement
                        # makes the skip collective
                        okl = jnp.isfinite(g_own).all()
                        ok = lax.psum(1.0 - okl.astype(jnp.float32),
                                      dp_axes) == 0
                        ok = fault_mod.apply_skip(fault, ok, micro=i,
                                                  step=st["step"])
                        st, _ = state_store.fold_state(
                            st, g_own, beta1=b1, beta2=b2, scale=kscale,
                            decay=decay, replicated_decay=rdecay,
                            grad_dtype=wire, guard=ok)
                        return l, st, ok
                    # bucketed: reduce-scatter EVERY bucket first (each
                    # received slice is O(rows/M), so the buffered total
                    # is about the owned state size), check the received
                    # slices, and agree ONCE per micro-batch — folding
                    # before the verdict would commit early buckets of a
                    # micro-batch whose later bucket turns out bad.
                    # fp8 wire: pack fp32, inject the owned-row residual,
                    # pmax-agree one scale column per bucket (M summation
                    # headroom), scatter 1-byte codes; the buffered
                    # residual pieces (inj, mine) are pre-sliced to the
                    # owned rows so the live set stays O(owned)
                    from repro.core.layerwise import (_fp8_ef_update,
                                                      _fp8_wire_slab)
                    ef_d = st["ef"].data if use_ef else None
                    ef_scale = sc["scale"] if fp8 else None
                    slabs = []
                    okl = jnp.asarray(True)
                    window = []     # zero_async: own slices not yet checked
                    for bk in plan.grad_buckets():
                        if opt.zero_async and len(window) >= 2:
                            # double-buffered issue: bucket j's pack (and
                            # fp8 encode) may start once bucket j-2's
                            # reduce-scatter has landed — the finiteness
                            # check consumes its result and the barrier
                            # orders the next pack after it, so at most
                            # two buckets (one in flight, one encoding)
                            # are ever live
                            okl = jnp.logical_and(
                                okl, jnp.isfinite(window.pop(0)).all())
                            okl, g = lax.optimization_barrier((okl, g))
                        if fp8:
                            slab = buckets_mod.pack_bucket(
                                g, lay, bk, dtype=jnp.float32)
                            row0 = dev * bk.slice_rows
                            codes, s_own, slab = _fp8_wire_slab(
                                slab, dp_axes, ef_d, ef_scale,
                                bk.own_offset, bk.slice_rows, row0)
                            own = lax.psum_scatter(codes, dp_axes,
                                                   scatter_dimension=0,
                                                   tiled=True)
                            inj = lax.dynamic_slice_in_dim(
                                slab, row0, bk.slice_rows, 0)
                            mine = lax.dynamic_slice_in_dim(
                                codes, row0, bk.slice_rows, 0)
                            slabs.append((own, s_own, inj, mine))
                        else:
                            slab = buckets_mod.pack_bucket(g, lay, bk,
                                                           dtype=wire)
                            own = lax.psum_scatter(slab, dp_axes,
                                                   scatter_dimension=0,
                                                   tiled=True)
                            slabs.append((own, None, None, None))
                        if opt.zero_async:
                            window.append(own)
                        else:
                            okl = jnp.logical_and(okl,
                                                  jnp.isfinite(own).all())
                    for own in window:      # drain the two-slot window
                        okl = jnp.logical_and(okl,
                                              jnp.isfinite(own).all())
                    ok = lax.psum(1.0 - okl.astype(jnp.float32),
                                  dp_axes) == 0
                    ok = fault_mod.apply_skip(fault, ok, micro=i,
                                              step=st["step"])
                    st = state_store.begin_micro_state(st, rdecay,
                                                       guard=ok)
                    for bk, (own, s_own, inj, mine) in zip(
                            plan.grad_buckets(), slabs):
                        st, _ = state_store.fold_slice_state(
                            st, own, bk.own_offset, beta1=b1, beta2=b2,
                            block=bk.fold_block, scale=kscale,
                            decay=decay, grad_dtype=wire,
                            grad_scale=s_own, guard=ok)
                        if use_ef:
                            ef_d = _fp8_ef_update(
                                ef_d, ok, inj, mine, s_own, ef_scale,
                                bk.own_offset, bk.slice_rows, 0, None)
                    if use_ef:
                        st = dict(st, ef=st["ef"].with_data(ef_d))
                    return l, st, ok

                def body(carry, xs):
                    st, lsum, good = carry
                    i, mb = xs
                    sc = st["scaler"]
                    l, st, ok = fold_micro_g(st, i, mb, good)
                    st = dict(st, scaler=scaler_mod.scaler_update(
                        sc, ok, dynamic=dyn, growth_interval=gi))
                    lsum = lsum + jnp.where(ok, l, 0.0)
                    return (st, lsum, good + ok.astype(jnp.int32)), None

                (state, lsum, good), _ = lax.scan(
                    body, (opt_state, 0.0, jnp.zeros((), jnp.int32)),
                    (jnp.arange(n), micro))
                applied = good > 0
                state = dict(state, step=state["step"]
                             + applied.astype(jnp.int32))
            else:
                state = dict(opt_state, step=opt_state["step"] + 1)

                def fold_micro(st, i, mb):
                    decay = _fold_decay(i, b1, b2, 1)
                    rdecay = (decay[0], jnp.where(i == 0, b2 / m_dev, 1.0))
                    if variant == "adama_layerwise":
                        from repro.core.layerwise import (
                            ZeroStream, layerwise_loss_and_fold)
                        return layerwise_loss_and_fold(
                            cfg, params, mb, st, beta1=b1, beta2=b2,
                            scale=scale, use_pallas=True, decay=decay,
                            zero=ZeroStream(plan, dp_axes, rdecay,
                                            rank=dev,
                                            zero_async=opt.zero_async),
                            grad_dtype=wire)
                    l, g = jax.value_and_grad(lambda p: loss(p, mb))(params)
                    if plan is None:
                        g_own = lax.psum_scatter(
                            arena_mod.pack(g, lay, dtype=wire), dp_axes,
                            scatter_dimension=0, tiled=True)
                        return l, state_store.fold_state(
                            st, g_own, beta1=b1, beta2=b2, scale=scale,
                            decay=decay, replicated_decay=rdecay,
                            grad_dtype=wire)
                    st = state_store.begin_micro_state(st, rdecay)
                    bks = list(plan.grad_buckets())

                    def issue(bk):
                        slab = buckets_mod.pack_bucket(g, lay, bk,
                                                       dtype=wire)
                        return lax.psum_scatter(slab, dp_axes,
                                                scatter_dimension=0,
                                                tiled=True)

                    def fold(st, bk, own):
                        return state_store.fold_slice_state(
                            st, own, bk.own_offset, beta1=b1, beta2=b2,
                            block=bk.fold_block, scale=scale, decay=decay,
                            grad_dtype=wire)

                    if opt.zero_async and len(bks) > 1:
                        # double-buffered pipeline: bucket j's pack +
                        # reduce-scatter is issued while bucket j-1's
                        # received slice folds; the barrier pins bucket
                        # j+1's pack AFTER bucket j-1's fold, so exactly
                        # two gradient buckets are ever live. Bitwise
                        # identical to the serial loop below — same
                        # psum_scatters, same folds, only scheduling
                        # freedom changes.
                        pending = issue(bks[0])
                        for bk_prev, bk in zip(bks, bks[1:]):
                            own = issue(bk)
                            st = fold(st, bk_prev, pending)
                            st, g = lax.optimization_barrier((st, g))
                            pending = own
                        st = fold(st, bks[-1], pending)
                    else:
                        for bk in bks:
                            st = fold(st, bk, issue(bk))
                    return l, st

                def body(carry, xs):
                    st, lsum = carry
                    i, mb = xs
                    l, st = fold_micro(st, i, mb)
                    return (st, lsum + l), None

                (state, lsum), _ = lax.scan(body, (state, 0.0),
                                            (jnp.arange(n), micro))
            state = state_store.psum_replicated_state(state, dp_axes)
            lr = lr_schedule(state["step"]) if lr_schedule else opt.lr
            t = state["step"].astype(jnp.float32)
            kw = dict(lr=lr, bc1=1 - b1 ** t, bc2=1 - b2 ** t,
                      eps=opt.eps, weight_decay=opt.weight_decay)
            if guarded:
                kw["guard"] = applied
            if state_store.has_master(state):
                # the device already owns its fp32 master rows (partition
                # order under the bucketed schedule): update them in place
                # and all-gather the emitted bf16 WORKING rows — half the
                # gather bytes, and params are never re-packed
                p_own, state = state_store.apply_master_state(state, **kw)
            else:
                idx = dev
                p_arena = arena_mod.pack(params, lay)
                p_own = (lax.dynamic_slice_in_dim(p_arena, idx * rows_own,
                                                  rows_own, axis=0)
                         if plan is None else
                         buckets_mod.gather_owned_rows(p_arena, plan, idx))
                p_own = state_store.apply_state(p_own, state, **kw)
            def gather_rows(x):
                # zero_async: ring of M-1 collective-permutes — bitwise
                # the same rows as all_gather, decomposed into hops the
                # scheduler can overlap with the apply epilogue
                if opt.zero_async:
                    return _ring_all_gather(x, dp_axes, m_dev, dev)
                return lax.all_gather(x, dp_axes, axis=0, tiled=True)

            if fp8:
                # quantized param all-gather: encode the owned working
                # rows (no summation — headroom 1), move 1-byte codes plus
                # the (rows, 1) fp32 scale column, decode on arrival. The
                # fp32 master rows stay resident, so this rounding is
                # re-derived fresh each step and never compounds
                from repro.kernels.adama_accum import (fp8_decode_rows,
                                                       fp8_encode_rows)
                codes, s_col = fp8_encode_rows(p_own.astype(jnp.float32))
                p_full = fp8_decode_rows(
                    gather_rows(codes), gather_rows(s_col),
                ).astype(p_own.dtype)
            else:
                p_full = gather_rows(p_own)
            if plan is not None:        # partition order -> arena order
                p_full = buckets_mod.unpermute_rows(p_full, plan)
            params = arena_mod.unpack(p_full, lay)
            if guarded:
                from repro.train import scaler as scaler_mod
                loss_m = lsum / jnp.maximum(good, 1).astype(jnp.float32)
                return params, state, {
                    "loss": lax.pmean(loss_m, dp_axes),
                    **scaler_mod.scaler_metrics(state)}
            return params, state, {"loss": lax.pmean(lsum / n, dp_axes)}

        if guarded:                 # variant == "adama", replicated arena
            # Each device folds LOCAL grads, so the verdict must be psum-
            # AGREED before any local fold commits — otherwise the mini-
            # batch-end state psum (Eqs. 7-8) would average folded shards
            # with unfolded ones. The check is on the LOCAL packed slab
            # (pre-reduce: the local gradient is where the NaN is born).
            from repro.train import faults as fault_mod
            from repro.train import scaler as scaler_mod
            dyn = scaler_mod.is_dynamic(opt)
            gi = opt.scaler_growth_interval
            lay = opt_state["m"].layout

            def body(carry, xs):
                st, lsum, good = carry
                i, mb = xs
                sc = st["scaler"]
                l, g = jax.value_and_grad(
                    lambda p: scaler_mod.scale_loss(loss(p, mb),
                                                    sc))(params)
                g = fault_mod.corrupt_tree(fault, g, micro=i,
                                           step=st["step"], device=dev)
                slab = arena_mod.pack(g, lay, dtype=wire)
                okl = jnp.isfinite(slab).all()
                ok = lax.psum(1.0 - okl.astype(jnp.float32), dp_axes) == 0
                ok = fault_mod.apply_skip(fault, ok, micro=i,
                                          step=st["step"])
                st, _ = state_store.fold_state(
                    st, slab, beta1=b1, beta2=b2,
                    scale=scaler_mod.scale_into_fold(1.0 / n, sc),
                    decay=_fold_decay(good, b1, b2, m_dev),
                    grad_dtype=wire, guard=ok)
                st = dict(st, scaler=scaler_mod.scaler_update(
                    sc, ok, dynamic=dyn, growth_interval=gi))
                lsum = lsum + jnp.where(ok, l, 0.0) / sc["scale"]
                return (st, lsum, good + ok.astype(jnp.int32)), None

            (state, lsum, good), _ = lax.scan(
                body, (opt_state, 0.0, jnp.zeros((), jnp.int32)),
                (jnp.arange(n), micro))
            applied = good > 0
            state = dict(state,
                         step=state["step"] + applied.astype(jnp.int32))
            state = adama.allreduce_states(state, dp_axes, m_dev)  # Eqs. 7-8
            lr = lr_schedule(state["step"]) if lr_schedule else opt.lr
            params, state = adama.finalize(params, state, lr=lr, beta1=b1,
                                           beta2=b2, eps=opt.eps,
                                           weight_decay=opt.weight_decay,
                                           use_pallas=True, guard=applied)
            loss_m = lsum / jnp.maximum(good, 1).astype(jnp.float32)
            return params, state, {"loss": lax.pmean(loss_m, dp_axes),
                                   **scaler_mod.scaler_metrics(state)}

        if variant == "naive":
            state = adama.begin_minibatch(opt_state, b1, b2, m_devices=1)

            def body(carry, mb):
                st, lsum = carry
                l, g = jax.value_and_grad(lambda p: loss(p, mb))(params)
                g = jax.tree.map(                    # psum EVERY micro-batch
                    lambda x: lax.psum(x, dp_axes) / (n * m_dev), g)
                st = adama.accumulate(st, g, b1, b2)
                return (st, lsum + l), None
            (state, lsum), _ = lax.scan(body, (state, 0.0), micro)
        elif opt.use_pallas and opt.arena:           # paper's schedule, arena
            state = dict(opt_state, step=opt_state["step"] + 1)

            def body(carry, xs):
                st, lsum = carry
                i, mb = xs
                l, g = jax.value_and_grad(lambda p: loss(p, mb))(params)
                st = adama.accumulate(st, g, b1, b2, scale=1.0 / n,
                                      decay=_fold_decay(i, b1, b2, m_dev),
                                      grad_dtype=wire)
                return (st, lsum + l), None
            (state, lsum), _ = lax.scan(body, (state, 0.0),
                                        (jnp.arange(n), micro))
            state = adama.allreduce_states(state, dp_axes, m_dev)  # Eqs. 7-8
        else:                                        # paper's schedule
            state = adama.begin_minibatch(opt_state, b1, b2, m_devices=m_dev)

            def body(carry, mb):
                st, lsum = carry
                l, g = jax.value_and_grad(lambda p: loss(p, mb))(params)
                g = jax.tree.map(lambda x: x / n, g)  # local scale 1/N (Eq.5)
                st = adama.accumulate(st, g, b1, b2,
                                      use_pallas=opt.use_pallas)
                return (st, lsum + l), None
            (state, lsum), _ = lax.scan(body, (state, 0.0), micro)
            state = adama.allreduce_states(state, dp_axes, m_dev)  # Eqs. 7-8

        lr = lr_schedule(state["step"]) if lr_schedule else opt.lr
        params, state = adama.finalize(params, state, lr=lr, beta1=b1,
                                       beta2=b2, eps=opt.eps,
                                       weight_decay=opt.weight_decay,
                                       use_pallas=opt.use_pallas)
        return params, state, {"loss": lax.pmean(lsum / n, dp_axes)}

    rep = P()
    bspec = P(dp_axes)

    def _zero1_ospec(opt_state):
        """ZeRO-1: every ROW-INDEXED state column (per the codec's declared
        column list) is sharded over the dp axes; the fp32 master-param
        region "p" and the fp8 error-feedback residual "ef" (when present)
        are row-indexed and shard with them;
        replicated codec columns (rowcol's (1, LANES) column sums) and the
        scalar step ride alongside replicated."""
        mask = state_store.row_indexed_mask(opt_state)
        row = P(dp_axes, None)
        return {k: (jax.tree.map(lambda ri: row if ri else rep,
                                 mask[k]) if k in ("m", "v") else
                    row if k in ("p", "ef") else rep)
                for k in opt_state}

    def step(params, opt_state, batch):
        ospec = (_zero1_ospec(opt_state)
                 if zero1 and variant in ("adama", "adama_layerwise")
                 else rep)
        f = _shard_map(local_step, mesh,
                       in_specs=(rep, ospec, bspec, P(dp_axes)),
                       out_specs=(rep, ospec, rep), manual_axes=dp_axes)
        return f(params, opt_state, batch,
                 jnp.arange(m_dev, dtype=jnp.int32))

    def init(params):
        if variant == "ga":
            return adam.init(params)
        if use_arena:
            # the "ef" residual starts at zeros — permutation-invariant, so
            # unlike the master it needs no bucket-order pre-permute
            st = adama.init_arena(params, codec=opt.state_codec,
                                  m_codec=opt.m_codec,
                                  n_shards=m_dev if zero1 else 1,
                                  master_params=opt.master_params,
                                  error_feedback=use_ef,
                                  tp_shards=tp_shards if zero1 else 1)
            if opt.master_params and zero1 and \
                    (opt.zero_bucketed or variant == "adama_layerwise"):
                # the bucketed schedule's resident row order is the
                # PARTITION order (core/buckets.py); m/v start at zero
                # (permutation-invariant) but the master packs real params
                # — pre-permute it so each shard's rows are its owned
                # slices in bucket order
                plan = zero1_bucket_plan(st["m"].layout, m_dev,
                                         opt.zero_bucket_rows,
                                         tp_shards=tp_shards)
                st["p"] = st["p"].with_data(
                    buckets_mod.permute_rows(st["p"].data, plan))
            if opt.finite_guard:
                from repro.train import scaler as scaler_mod
                st["scaler"] = scaler_mod.init_scaler(opt)
            return st
        return adama.init(params)

    return step, init
