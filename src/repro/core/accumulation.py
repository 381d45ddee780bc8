"""Micro-batch accumulation engines — where AdamA meets the training loop.

Three engines, selected by OptimizerConfig.accumulation:

  ga              — baseline gradient accumulation: lax.scan over micro-batches
                    carrying a PARAM-SIZED fp32 gradient accumulator, then one
                    optimizer update. This is the paper's comparison point.
  adama           — optimizer accumulation (Algorithm 1): the scan carries
                    (m, v) instead; each micro-batch's gradient tree is folded
                    immediately and becomes dead inside the scan body. No
                    param-sized accumulator exists in the carry.
  adama_layerwise — Algorithm 2: additionally interleaves the fold with the
                    per-layer backward so at most ONE layer's gradient is live
                    (see core/layerwise.py).

All engines consume a global batch of shape (GB, ...) and reshape it to
(N, GB/N, ...) micro-batches.

With OptimizerConfig(use_pallas=True, arena=True) every engine runs its
optimizer path over the flat state arena (core/arena.py): one fused
`pallas_call` per micro-batch fold (the begin-minibatch decay riding in as
SMEM scalars on the first fold) and one per mini-batch-end apply — O(1)
kernel dispatches per micro-batch instead of O(param leaves).

OptimizerConfig.state_codec / m_codec select the per-moment codecs
(core/state_store.py: v in fp32 | int8 | factored | rowcol, m in fp32 |
int8); both codec transforms are fused into the same kernels, so the
dispatch count is unchanged for every combination. With zero_stage=1 the
arena state is constrained to ZeRO-1 row-range sharding (core/zero.py) —
under a multi-device mesh GSPMD materializes the reduce-scatter/all-gather
schedule; on a single device it is a no-op.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (ACCUMULATE_SCOPE, APPLY_SCOPE,
                                GRAD_PACK_SCOPE, ModelConfig,
                                OptimizerConfig)
from repro.core import adama
from repro.core import arena as arena_mod
from repro.models.model import loss_fn as model_loss_fn
from repro.optim import adafactor, adam, sm3

OPTIMIZERS = {"adam": adam, "adafactor": adafactor, "sm3": sm3}


def _use_arena(opt: OptimizerConfig) -> bool:
    return opt.use_pallas and opt.arena


def _wire_dtype(opt: OptimizerConfig):
    """The gradient wire dtype the arena pack/collectives move
    (OptimizerConfig.grad_dtype); fold kernels upcast in-pass."""
    from repro.configs.base import grad_wire_dtype
    return grad_wire_dtype(opt.grad_dtype)


def is_fp8_wire(opt: OptimizerConfig) -> bool:
    """fp8_e4m3 gradient wire: slabs move as fp8 codes + a per-row fp32
    scale column, decoded inside the fold kernels (`grad_scale`)."""
    return opt.grad_dtype == "fp8_e4m3"


def use_error_feedback(opt: OptimizerConfig) -> bool:
    """Whether the state carries the fp8 error-feedback residual "ef":
    only the fp8 wire quantizes coarsely enough to need one, and
    error_feedback=False ablates it (the fig2 convergence comparison)."""
    return is_fp8_wire(opt) and opt.error_feedback


def _arena_init(opt: OptimizerConfig, state_shards: int = 1):
    """Arena state initializer honouring the configured codec; the layout is
    padded for `state_shards` equal row ranges whenever the caller may shard
    (zero_stage=1 OR a dp-profile launcher passing its dp size) — padding
    rows are zeros that no kernel result depends on, so over-padding is
    always safe while an unpadded layout makes shard_rows refuse.

    With finite_guard the state gains the "scaler" entry (train/scaler.py:
    loss scale + skip counters) — plain scalars that ride through every
    dict(state, ...) site, checkpoint like any leaf, and stay replicated
    under the DP engines because the skip verdicts they fold are
    psum-agreed."""
    base = functools.partial(adama.init_arena, codec=opt.state_codec,
                             m_codec=opt.m_codec,
                             n_shards=max(1, state_shards),
                             master_params=opt.master_params,
                             error_feedback=use_error_feedback(opt),
                             work_param_cache=opt.work_param_cache)
    if not opt.finite_guard:
        return base

    def init(params):
        from repro.train import scaler as scaler_mod
        state = base(params)
        state["scaler"] = scaler_mod.init_scaler(opt)
        return state
    return init


def _zero_constrain(opt: OptimizerConfig, state):
    """ZeRO-1 over the arena in the pjit engine: constrain every ROW-INDEXED
    state column to row-range sharding over the dp axes (replicated codec
    columns — e.g. the rowcol column sums, whose leading dim is 1 — stay
    unconstrained; the fp32 master-param region "p", the fp8 error-feedback
    residual "ef" and the bf16 working-param cache "wp" are row-indexed and
    shard with them). GSPMD then owns the reduce-scatter/all-gather
    schedule; without an installed mesh this is a no-op (single-device
    runs, tests)."""
    if opt.zero_stage != 1 or not _use_arena(opt):
        return state
    from repro.core.state_store import row_indexed_mask
    from repro.sharding.ctx import maybe_shard
    mask = row_indexed_mask(state)
    return {k: (jax.tree.map(
                lambda x, ri: maybe_shard(x, "dp", None) if ri else x,
                v, mask[k]) if k in ("m", "v") else
                (jax.tree.map(lambda x: maybe_shard(x, "dp", None), v)
                 if k in ("p", "ef", "wp") else v))
            for k, v in state.items()}


def _fold_decay(i, beta1: float, beta2: float, m_devices: int = 1):
    """Decay pair for fold i of a mini-batch: the begin-minibatch pass
    (m*=b1, v*=M*b2*v) fused into the FIRST fold, identity afterwards."""
    one = jnp.float32(1.0)
    return (jnp.where(i == 0, jnp.float32(beta1), one),
            jnp.where(i == 0, jnp.float32(m_devices * beta2), one))


def _split_micro(batch: Dict[str, Any], n: int):
    from repro.sharding.ctx import shard_micro_batches

    def r(x):
        gb = x.shape[0]
        assert gb % n == 0, f"global batch {gb} not divisible by micro {n}"
        return shard_micro_batches(x.reshape((n, gb // n) + x.shape[1:]))
    return jax.tree.map(r, batch)


def make_loss(cfg: ModelConfig, *, remat: bool = False) -> Callable:
    return functools.partial(model_loss_fn, cfg, remat=remat)


# ---------------------------------------------------------------------------
# Engine: ga (baseline)
# ---------------------------------------------------------------------------


def make_ga_step(cfg: ModelConfig, opt: OptimizerConfig, *, remat=False,
                 lr_schedule=None, state_shards: int = 1, fault=None):
    loss = make_loss(cfg, remat=remat)
    n = opt.micro_batches
    opt_mod = OPTIMIZERS[opt.name if opt.name != "adama" else "adam"]
    # arena fast path: the Adam update becomes one fused fold (decay in SMEM)
    # + one fused apply over the flat state arena
    # arena + non-adam is rejected at OptimizerConfig construction
    # (configs/base.py::optimizer_capability), so opt_mod is adam here
    use_arena = _use_arena(opt)
    guarded = opt.finite_guard           # config enforces arena=True

    def step(params, opt_state, batch):
        from repro.train import faults as fault_mod
        micro = _split_micro(batch, n)
        layout = opt_state["m"].layout if use_arena else None
        if "wp" in opt_state:    # bf16 working-param cache (see adama)
            params = adama.working_params(opt_state)

        def body(carry, xs):
            acc, lsum = carry
            i, mb = xs
            l, g = jax.value_and_grad(lambda p: loss(p, mb))(params)
            g = fault_mod.corrupt_tree(fault, g, micro=i,
                                       step=opt_state["step"])
            if use_arena:
                with jax.named_scope(GRAD_PACK_SCOPE):
                    slab = arena_mod.pack(g, layout)
                with jax.named_scope(ACCUMULATE_SCOPE):
                    acc = acc + slab / n
            else:
                with jax.named_scope(ACCUMULATE_SCOPE):
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32) / n, acc, g)
            return (acc, lsum + l), None

        zeros = (jnp.zeros((layout.rows, arena_mod.LANES), jnp.float32)
                 if use_arena else
                 jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                              params))
        (grads, lsum), _ = lax.scan(body, (zeros, 0.0),
                                    (jnp.arange(n), micro))
        # ga keeps the ACCUMULATED gradient alive, so the guard is the
        # classic whole-step recipe: one flag over the accumulated slab
        # predicates the single fold + apply (and the step counter).
        # Checked BEFORE grad_clip — a NaN clip scale is discarded with
        # everything else the flag gates.
        ok = None
        if guarded:
            ok = jnp.isfinite(grads).all()
            ok = fault_mod.apply_skip(fault, ok, micro=0,
                                      step=opt_state["step"])
        if opt.grad_clip:
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                              for g in jax.tree.leaves(grads)))
            scale = jnp.minimum(1.0, opt.grad_clip / jnp.maximum(gn, 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        lr = lr_schedule(opt_state["step"]) if lr_schedule else opt.lr
        if use_arena:
            from repro.core import state_store
            step_c = opt_state["step"] + (1 if ok is None
                                          else ok.astype(jnp.int32))
            t = step_c.astype(jnp.float32)
            out = state_store.fold_state(
                dict(opt_state, step=step_c), grads, beta1=opt.beta1,
                beta2=opt.beta2, decay=(opt.beta1, opt.beta2), guard=ok)
            opt_state = out[0] if ok is not None else out
            if ok is not None:
                from repro.train import scaler as scaler_mod
                opt_state = dict(opt_state, scaler=scaler_mod.scaler_update(
                    opt_state["scaler"], ok, dynamic=False,
                    growth_interval=opt.scaler_growth_interval))
            kw = dict(lr=lr, bc1=1 - opt.beta1 ** t, bc2=1 - opt.beta2 ** t,
                      eps=opt.eps, weight_decay=opt.weight_decay, guard=ok)
            with jax.named_scope(APPLY_SCOPE):
                if state_store.has_master(opt_state):
                    work, opt_state = state_store.apply_master_state(
                        opt_state, **kw)
                    if "wp" in opt_state:
                        opt_state = dict(opt_state, wp=opt_state["wp"]
                                         .with_data(work))
                    params = arena_mod.unpack(work, layout)
                else:
                    p_new = state_store.apply_state(
                        arena_mod.pack(params, layout), opt_state, **kw)
                    params = arena_mod.unpack(p_new, layout)
            metrics = {"loss": lsum / n}
            if ok is not None:
                from repro.train.scaler import scaler_metrics
                metrics.update(scaler_metrics(opt_state))
            return params, _zero_constrain(opt, opt_state), metrics
        kw = dict(lr=lr, weight_decay=opt.weight_decay)
        if opt_mod is adam:
            kw.update(beta1=opt.beta1, beta2=opt.beta2, eps=opt.eps)
        with jax.named_scope(APPLY_SCOPE):
            params, opt_state = opt_mod.update(grads, opt_state, params,
                                               **kw)
        return params, opt_state, {"loss": lsum / n}

    def init(params):
        return (_arena_init(opt, state_shards)(params) if use_arena
                else opt_mod.init(params))

    return step, init


# ---------------------------------------------------------------------------
# Engine: adama (Algorithm 1 — fold whole-model grads per micro-batch)
# ---------------------------------------------------------------------------


def make_adama_step(cfg: ModelConfig, opt: OptimizerConfig, *, remat=False,
                    lr_schedule=None, m_devices: int = 1, axis_names=(),
                    state_shards: int = 1, fault=None):
    """m_devices/axis_names are used by the shard_map DP engine (Eqs. 5-8);
    in the pjit engine they stay (1, ()) and gradients arrive pre-reduced."""
    loss = make_loss(cfg, remat=remat)
    n = opt.micro_batches
    b1, b2 = opt.beta1, opt.beta2
    use_arena = _use_arena(opt)
    wire = _wire_dtype(opt)
    fp8 = is_fp8_wire(opt)
    guarded = opt.finite_guard           # config enforces arena=True
    if fp8 and axis_names:
        raise ValueError(
            "grad_dtype='fp8_e4m3' in the replicated shard_map adama "
            "schedule is unsupported: there is no gradient collective to "
            "quantize (states are psum'd, Eqs. 7-8) and a per-device "
            "error-feedback residual would desync the replicated state; "
            "use zero_stage=1 (core/dp_shardmap.py reduce-scatters fp8 "
            "codes) or the pjit engine")

    def step(params, opt_state, batch):
        micro = _split_micro(batch, n)
        if "wp" in opt_state:
            # bf16 working-param cache: the step's model params come from
            # ONE unpack of state["wp"]; the passed-in tree is dead and
            # never re-packed (finalize refreshes the cache from the
            # master apply's emitted work rows)
            params = adama.working_params(opt_state)
        if use_arena and guarded:
            from repro.core import state_store
            from repro.train import faults as fault_mod
            from repro.train import scaler as scaler_mod
            dyn = scaler_mod.is_dynamic(opt)
            gi = opt.scaler_growth_interval
            layout = opt_state["m"].layout
            use_ef = fp8 and "ef" in opt_state
            if fp8:
                from repro.kernels.adama_accum import (fp8_decode_rows,
                                                       fp8_encode_rows)
            # guarded fold scan: the step counter is NOT pre-incremented
            # (it advances only if some fold commits) and the carry tracks
            # `good`, the number of committed folds — the begin-minibatch
            # decay shifts to the first GOOD fold via _fold_decay(good,...)

            def body(carry, xs):
                st, lsum, good = carry
                i, mb = xs
                sc = st["scaler"]
                l, g = jax.value_and_grad(
                    lambda p: scaler_mod.scale_loss(loss(p, mb), sc))(params)
                g = fault_mod.corrupt_tree(fault, g, micro=i,
                                           step=st["step"])
                with jax.named_scope(GRAD_PACK_SCOPE):
                    if fp8:
                        # fp8 wire: pack fp32, inject the error-feedback
                        # residual (stored UNSCALED — the dynamic loss
                        # scale can change between micro-batches, so the
                        # S-scaled slab gets ef*S), then encode codes +
                        # per-row scale. Gradients arrive pre-reduced in
                        # the pjit engine, so the encode needs no summation
                        # headroom (n_summands=1)
                        slab = arena_mod.pack(g, layout, dtype=jnp.float32)
                        if use_ef:
                            slab = slab + st["ef"].data * sc["scale"]
                    else:
                        slab = arena_mod.pack(g, layout, dtype=wire)
                # the flag is computed over the packed slab BEFORE the fold
                # commits (for fp8: pre-encode, residual included — finite
                # inputs always encode to finite codes); under shard_map it
                # is psum-AGREED so all shards skip or none do (a lone
                # folding shard would desync the averaged states);
                # forced-skip faults land on the final verdict, defining
                # "a run that never saw micro-batch i"
                ok = jnp.isfinite(slab).all()
                if axis_names:
                    ok = lax.psum(1.0 - ok.astype(jnp.float32),
                                  axis_names) == 0
                ok = fault_mod.apply_skip(fault, ok, micro=i,
                                          step=st["step"])
                if fp8:
                    with jax.named_scope(GRAD_PACK_SCOPE):
                        codes, gs = fp8_encode_rows(slab)
                    st, _ = state_store.fold_state(
                        st, codes, beta1=b1, beta2=b2,
                        scale=scaler_mod.scale_into_fold(1.0 / n, sc),
                        decay=_fold_decay(good, b1, b2, m_devices),
                        grad_dtype=wire, grad_scale=gs, guard=ok)
                    if use_ef:
                        # e = (g*S + ef*S - decode)/S, back in unscaled
                        # units; predicated on the SAME flag as the fold,
                        # so a skipped micro-batch leaves ef bitwise
                        with jax.named_scope(GRAD_PACK_SCOPE):
                            ef_new = (slab - fp8_decode_rows(codes, gs)) \
                                / sc["scale"]
                            st = dict(st, ef=st["ef"].with_data(
                                jnp.where(ok, ef_new, st["ef"].data)))
                else:
                    st, _ = state_store.fold_state(
                        st, slab, beta1=b1, beta2=b2,
                        scale=scaler_mod.scale_into_fold(1.0 / n, sc),
                        decay=_fold_decay(good, b1, b2, m_devices),
                        grad_dtype=wire, guard=ok)
                st = dict(st, scaler=scaler_mod.scaler_update(
                    sc, ok, dynamic=dyn, growth_interval=gi))
                lsum = lsum + jnp.where(ok, l, 0.0) / sc["scale"]
                return (st, lsum, good + ok.astype(jnp.int32)), None

            (state, lsum, good), _ = lax.scan(
                body, (opt_state, 0.0, jnp.zeros((), jnp.int32)),
                (jnp.arange(n), micro))
            applied = good > 0
            state = dict(state, step=state["step"] + applied.astype(jnp.int32))
        elif use_arena:
            # decay is fused into fold 0 (no standalone state-sized pass);
            # 1/N rides in-kernel as the fold's static scale. The fold is
            # the slab's only reader here, so unless the kernel must split
            # rows over a mesh (or states are psum'd under shard_map) it
            # gathers the gradient from the leaves and no slab is written
            from repro.sharding.ctx import kernel_mesh
            leaves = not axis_names and kernel_mesh() is None and not fp8
            state = dict(opt_state, step=opt_state["step"] + 1)

            def body(carry, xs):
                st, lsum = carry
                i, mb = xs
                l, g = jax.value_and_grad(lambda p: loss(p, mb))(params)
                st = adama.accumulate(st, g, b1, b2, scale=1.0 / n,
                                      decay=_fold_decay(i, b1, b2, m_devices),
                                      grad_dtype=wire, from_leaves=leaves)
                return (st, lsum + l), None

            (state, lsum), _ = lax.scan(body, (state, 0.0),
                                        (jnp.arange(n), micro))
        else:
            state = adama.begin_minibatch(opt_state, b1, b2, m_devices)

            def body(carry, mb):
                st, lsum = carry
                l, g = jax.value_and_grad(lambda p: loss(p, mb))(params)
                g = jax.tree.map(lambda x: x / n, g)    # Alg.1 line 6: g/N
                st = adama.accumulate(st, g, b1, b2,
                                      use_pallas=opt.use_pallas)
                return (st, lsum + l), None

            (state, lsum), _ = lax.scan(body, (state, 0.0), micro)
        if axis_names:
            state = adama.allreduce_states(state, axis_names, m_devices)
        lr = lr_schedule(state["step"]) if lr_schedule else opt.lr
        params, state = adama.finalize(
            params, state, lr=lr, beta1=b1, beta2=b2, eps=opt.eps,
            weight_decay=opt.weight_decay, use_pallas=opt.use_pallas,
            guard=applied if use_arena and guarded else None)
        if use_arena and guarded:
            from repro.train.scaler import scaler_metrics
            # mean over COMMITTED micro-batches (0 good -> report 0, the
            # sum's identity, rather than a NaN from 0/0)
            loss_m = lsum / jnp.maximum(good, 1).astype(jnp.float32)
            metrics = {"loss": (lax.pmean(loss_m, axis_names)
                                if axis_names else loss_m),
                       **scaler_metrics(state)}
            return params, _zero_constrain(opt, state), metrics
        if axis_names:
            lsum = lax.pmean(lsum, axis_names)
        return params, _zero_constrain(opt, state), {"loss": lsum / n}

    return step, (_arena_init(opt, state_shards) if use_arena
                  else adama.init)


# ---------------------------------------------------------------------------
# Engine: adama_layerwise (Algorithm 2 — fold per LAYER inside backward)
# ---------------------------------------------------------------------------


def make_adama_layerwise_step(cfg: ModelConfig, opt: OptimizerConfig, *,
                              remat=False, lr_schedule=None,
                              m_devices: int = 1, axis_names=(),
                              state_shards: int = 1, fault=None):
    from repro.core.layerwise import layerwise_loss_and_fold
    n = opt.micro_batches
    b1, b2 = opt.beta1, opt.beta2
    use_arena = _use_arena(opt)
    wire = _wire_dtype(opt)
    guarded = opt.finite_guard           # config enforces arena=True
    if guarded and axis_names:
        raise ValueError(
            "guarded adama_layerwise under shard_map requires the ZeRO-1 "
            "streaming schedule (core/dp_shardmap.py, zero_stage=1): the "
            "per-layer agreement rides the reduce-scatter there; the "
            "replicated shard_map variant has no per-layer collective to "
            "agree on")

    def step(params, opt_state, batch):
        micro = _split_micro(batch, n)
        if "wp" in opt_state:    # bf16 working-param cache (see adama)
            params = adama.working_params(opt_state)
        if use_arena and guarded:
            from repro.train import faults as fault_mod
            from repro.train import scaler as scaler_mod
            dyn = scaler_mod.is_dynamic(opt)
            gi = opt.scaler_growth_interval

            def body(carry, xs):
                st, lsum, good = carry
                i, mb = xs
                sc = st["scaler"]
                # loss scaling rides the VJP SEED: the backward is seeded
                # with (1/N)*S so every wire slab is S-scaled, and the
                # slice folds un-scale with fold_scale=1/S in-kernel.
                # nan/inf faults land on the seed — the loss-originated
                # failure mode, reaching every layer's slab; skip faults
                # force the external verdict layerwise ANDs in.
                seed = fault_mod.corrupt_loss(
                    fault, jnp.asarray(1.0 / n, jnp.float32) * sc["scale"],
                    micro=i, step=st["step"])
                pre = fault_mod.apply_skip(fault, jnp.asarray(True),
                                           micro=i, step=st["step"])
                l, st, ok = layerwise_loss_and_fold(
                    cfg, params, mb, st, beta1=b1, beta2=b2, scale=seed,
                    use_pallas=True,
                    decay=_fold_decay(good, b1, b2, m_devices),
                    grad_dtype=wire,
                    fold_scale=jnp.float32(1.0) / sc["scale"], guard=pre)
                st = dict(st, scaler=scaler_mod.scaler_update(
                    sc, ok, dynamic=dyn, growth_interval=gi))
                # l is the UNSCALED ce (the scale only seeds the backward)
                lsum = lsum + jnp.where(ok, l, 0.0)
                return (st, lsum, good + ok.astype(jnp.int32)), None

            (state, lsum, good), _ = lax.scan(
                body, (opt_state, 0.0, jnp.zeros((), jnp.int32)),
                (jnp.arange(n), micro))
            applied = good > 0
            state = dict(state, step=state["step"] + applied.astype(jnp.int32))
        elif use_arena:
            # each arena row is folded exactly once per micro-batch (each
            # layer once in the backward scan, the rest region at the
            # boundary), so the begin-minibatch decay fuses into micro-batch
            # 0's per-layer slice folds
            state = dict(opt_state, step=opt_state["step"] + 1)

            def body(carry, xs):
                st, lsum = carry
                i, mb = xs
                l, st = layerwise_loss_and_fold(
                    cfg, params, mb, st, beta1=b1, beta2=b2, scale=1.0 / n,
                    use_pallas=True,
                    decay=_fold_decay(i, b1, b2, m_devices),
                    grad_dtype=wire)
                return (st, lsum + l), None

            (state, lsum), _ = lax.scan(body, (state, 0.0),
                                        (jnp.arange(n), micro))
        else:
            state = adama.begin_minibatch(opt_state, b1, b2, m_devices)

            def body(carry, mb):
                st, lsum = carry
                l, st = layerwise_loss_and_fold(
                    cfg, params, mb, st, beta1=b1, beta2=b2, scale=1.0 / n,
                    use_pallas=opt.use_pallas)
                return (st, lsum + l), None

            (state, lsum), _ = lax.scan(body, (state, 0.0), micro)
        if axis_names:
            state = adama.allreduce_states(state, axis_names, m_devices)
        lr = lr_schedule(state["step"]) if lr_schedule else opt.lr
        params, state = adama.finalize(
            params, state, lr=lr, beta1=b1, beta2=b2, eps=opt.eps,
            weight_decay=opt.weight_decay, use_pallas=opt.use_pallas,
            guard=applied if use_arena and guarded else None)
        if use_arena and guarded:
            from repro.train.scaler import scaler_metrics
            loss_m = lsum / jnp.maximum(good, 1).astype(jnp.float32)
            return params, _zero_constrain(opt, state), \
                {"loss": loss_m, **scaler_metrics(state)}
        if axis_names:
            lsum = lax.pmean(lsum, axis_names)
        return params, _zero_constrain(opt, state), {"loss": lsum / n}

    return step, (_arena_init(opt, state_shards) if use_arena
                  else adama.init)


ENGINES = {
    "ga": make_ga_step,
    "adama": make_adama_step,
    "adama_layerwise": make_adama_layerwise_step,
}


def make_train_step(cfg: ModelConfig, opt: OptimizerConfig, **kw):
    """Returns (step_fn, opt_init_fn) for the configured engine."""
    eng = ENGINES[opt.accumulation]
    if opt.accumulation == "ga":
        kw.pop("m_devices", None)
        kw.pop("axis_names", None)
    return eng(cfg, opt, **kw)
