"""Training loop: metrics, logging, checkpointing, restore — engine-agnostic
(any step_fn from core.accumulation / core.dp_shardmap).

Resilience wiring: `run.inject_fault` (train/faults.py grammar) threads a
FaultSpec into the compiled step (nan/inf/zero/skip) or arms a host-side
`InjectedCrash` after a step's update commits and BEFORE its checkpoint
save — the worst-case kill the auto-resume path must survive. With
`finite_guard=True` the loop surfaces loss_scale / skipped_micro_batches /
consec_skips in the logs and aborts when `scaler_abort_after` consecutive
micro-batches skip (a run that is only skipping is not training).

Host spans (`jax.profiler` annotations, recorded only while a profiler
trace runs, on the device trace's clock): `train.init` (optimizer init,
restore, placement), `train.compile`, and per step `train.step` (step
number `i + 1`) holding `train.batch` (build and place the batch),
`train.dispatch` (the jitted step call), `train.sync` (the host reads the
loss), `train.log` and `train.checkpoint`."""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Dict

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import RunConfig
from repro.core.accumulation import _zero_constrain, make_train_step
from repro.data import make_data
from repro.kernels import fused_step
from repro.models.model import init_params
from repro.train import checkpoint as ckpt
from repro.train import faults as faults_mod


def zero1_shardings(mesh, opt, params, opt_state):
    """Shardings of the ZeRO-1 step on the data mesh, as (params, opt
    state, batch, metrics): params replicated, the arena state laid out
    exactly as the step's own `_zero_constrain` lays out its outputs
    (row-indexed columns row-sharded over "data", the rest replicated),
    each batch split over "data". Pinning the step's in- and out-shardings
    to these keeps step 1 and step 2 one compiled program. `params` and
    `opt_state` may be arrays or shapes; the sharding ctx must hold
    `mesh`."""
    rep = NamedSharding(mesh, P())
    o_sh = jax.jit(functools.partial(_zero_constrain, opt)).lower(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                    sharding=rep),
                     opt_state)).compile().output_shardings
    return (jax.tree.map(lambda _: rep, params), o_sh,
            NamedSharding(mesh, P("data")), rep)


def train(run: RunConfig, *, lr_schedule=None, log_fn=print,
          params=None, data=None) -> Dict[str, Any]:
    """Run `run.steps` training steps. Returns the final params and
    optimizer state, the per-step losses, the last step's metrics,
    `compile_s` (seconds to lower and compile the step, kept out of every
    step time) and `compiled` (that compiled step, or None when no step
    was left to run)."""
    cfg = run.model
    key = jax.random.key(run.seed)
    if params is None:
        params = init_params(cfg, key)
    # ZeRO-1 over the arena in this single-process loop: install a data-only
    # mesh over the local devices and pad the arena layout for that many
    # row-range shards — GSPMD then owns the reduce-scatter/all-gather
    # schedule via _zero_constrain. One device: plain unsharded step.
    opt = run.optimizer
    state_shards = 1
    mesh = None
    mesh_ctx = contextlib.ExitStack()
    if opt.zero_stage == 1 and opt.arena and jax.device_count() > 1:
        from repro.launch.mesh import make_mesh
        from repro.sharding import ctx as shard_ctx
        state_shards = jax.device_count()
        # size-1 "model" axis so the models' activation constraints (which
        # name it) resolve on this data-only mesh
        mesh = make_mesh((state_shards, 1), ("data", "model"))
        mesh_ctx.enter_context(shard_ctx.use_mesh(mesh, ("data",)))
    elif opt.zero_stage == 1 and jax.device_count() > 1:
        log_fn("[train] note: zero_stage=1 without arena=True is a no-op in "
               "this single-process loop (only the arena row-range path is "
               "wired here); per-leaf ZeRO-1 runs via launch/dryrun.py or "
               "a pjit launcher with sharding rules — pass --arena to shard")
    fault = faults_mod.parse_fault(run.inject_fault)
    step_fn, opt_init = make_train_step(cfg, opt, remat=run.remat,
                                        lr_schedule=lr_schedule,
                                        state_shards=state_shards,
                                        fault=fault)
    if data is None:
        data = make_data(cfg, run.shape, seed=run.seed)
    every = run.checkpoint_every or max(run.log_every * 5, 50)
    losses = []
    compiled, compile_s = None, 0.0
    start = 0
    with mesh_ctx:                  # row-range sharding ctx (no-op if empty)
        with TraceAnnotation("train.init"):
            opt_state = opt_init(params)
            if run.checkpoint_dir:
                last = ckpt.latest_step(run.checkpoint_dir)
                if last is not None:
                    tree = {"params": params, "opt": opt_state}
                    tree = ckpt.restore(run.checkpoint_dir, last,
                                        jax.eval_shape(lambda: tree))
                    params, opt_state = tree["params"], tree["opt"]
                    start = last
                    log_fn(f"[train] restored step {last}")
            batch_sharding, jit_kw = None, {}
            if mesh is not None:
                p_sh, o_sh, batch_sharding, rep = zero1_shardings(
                    mesh, opt, params, opt_state)
                params, opt_state = jax.device_put((params, opt_state),
                                                   (p_sh, o_sh))
                jit_kw = dict(in_shardings=(p_sh, o_sh, batch_sharding),
                              out_shardings=(p_sh, o_sh, rep))
        jstep = jax.jit(step_fn, donate_argnums=(0, 1), **jit_kw)
        if start < run.steps:
            # compile ahead of step 1 so no step time includes it; the jit
            # call below reuses this executable
            t0 = time.perf_counter()
            folds = fused_step.FOLD_SOURCES
            folds.clear()
            with TraceAnnotation("train.compile"):
                compiled = jstep.lower(
                    params, opt_state,
                    jax.device_put(data.batch(start), batch_sharding)
                ).compile()
            compile_s = time.perf_counter() - t0
            log_fn(f"[train] step compiled in {compile_s:.2f}s")
            if folds:
                # which gradient source the step's arena folds read
                log_fn(f"[train] fold gradient: leaves={folds['leaves']} "
                       f"slab={folds['slab']}")
        t0 = time.time()
        for i in range(start, run.steps):
            with StepTraceAnnotation("train.step", step_num=i + 1):
                with TraceAnnotation("train.batch"):
                    batch = jax.device_put(data.batch(i), batch_sharding)
                with TraceAnnotation("train.dispatch"):
                    params, opt_state, metrics = jstep(params, opt_state,
                                                       batch)
                with TraceAnnotation("train.sync"):
                    losses.append(float(metrics["loss"]))
                    consec = int(metrics.get("consec_skips", 0))
                if opt.scaler_abort_after and \
                        consec >= opt.scaler_abort_after:
                    raise RuntimeError(
                        f"aborting at step {i + 1}: {consec} consecutive "
                        f"micro-batches skipped non-finite (>= scaler_"
                        f"abort_after={opt.scaler_abort_after}); loss_"
                        f"scale={float(metrics.get('loss_scale', 1.0)):g}"
                        f" — the run is diverging, not merely overflowing")
                if (i + 1) % run.log_every == 0:
                    with TraceAnnotation("train.log"):
                        dt = (time.time() - t0) / (i + 1 - start)
                        extra = ""
                        if "loss_scale" in metrics:
                            skipped = int(metrics["skipped_micro_batches"])
                            extra = (f" scale="
                                     f"{float(metrics['loss_scale']):g}"
                                     f" skipped={skipped}")
                        log_fn(f"[train] step {i+1}/{run.steps} "
                               f"loss={losses[-1]:.4f}{extra} "
                               f"({dt:.2f}s/step)")
                if faults_mod.crash_due(fault, i):
                    # update committed, checkpoint NOT saved: the
                    # auto-resume path above must replay from the last
                    # saved step bitwise
                    raise faults_mod.InjectedCrash(
                        f"injected crash after step {i + 1}'s update, "
                        f"before its save")
                if run.checkpoint_dir and (i + 1) % every == 0:
                    with TraceAnnotation("train.checkpoint"):
                        ckpt.save(run.checkpoint_dir, i + 1,
                                  {"params": params, "opt": opt_state},
                                  keep=run.keep_last_n)
    if run.checkpoint_dir:
        ckpt.save(run.checkpoint_dir, run.steps,
                  {"params": params, "opt": opt_state},
                  keep=run.keep_last_n)
    return {"params": params, "opt_state": opt_state, "losses": losses,
            "metrics": {k: float(v) for k, v in metrics.items()}
            if run.steps > start else {},
            "compile_s": compile_s, "compiled": compiled}
