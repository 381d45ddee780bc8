"""Multi-pod dry-run: prove every (arch x input-shape x mesh) combination
lowers, compiles, and fits — without hardware.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b \
      --shape train_4k [--multi-pod] [--engine pjit|shardmap] \
      [--accum adama|ga|adama_layerwise] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Writes one JSON artifact per combination with memory_analysis, cost_analysis
and the loop-aware collective-byte breakdown (read by benchmarks/roofline.py).
"""
# The next two lines MUST run before any other import (jax locks the device
# count at first init). Do NOT replicate this env var anywhere global —
# smoke tests and benches must see the single real device.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import GRAD_DTYPES, M_CODECS, STATE_CODECS
from repro.configs import (ARCH_IDS, INPUT_SHAPES, OptimizerConfig,
                           get_config, shape_supported)
from repro.core.accumulation import make_train_step
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.sharding import ctx as shard_ctx
from repro.launch.specs import input_specs
from repro.models.decode import prefill, prefill_whisper, serve_step
from repro.models.model import abstract_params
from repro.sharding.rules import Rules


def _cast_tree(tree, dtype):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def _sharded_bytes(tree, spec_tree, mesh) -> int:
    """Per-device bytes of `tree` under `spec_tree` PartitionSpecs: each
    leaf's size divided by the product of its spec's mesh-axis sizes
    (replicated leaves count full-size on every device)."""
    import numpy as np
    leaves = jax.tree.leaves(tree)
    specs = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    total = 0
    for leaf, spec in zip(leaves, specs):
        n = 1
        if isinstance(spec, P):
            for e in spec:
                for a in (e if isinstance(e, tuple) else (e,) if e else ()):
                    n *= mesh.shape[a]
        size = int(np.prod(leaf.shape, dtype=np.int64)) if leaf.shape else 1
        total += size * np.dtype(leaf.dtype).itemsize // n
    return total


def build_lowered(arch: str, shape_name: str, mesh, *, engine="pjit",
                  accum="adama", micro_batches=8, fsdp=True, remat=True,
                  use_pallas=False, optimizer="adama", zero1=False,
                  profile="tp2d", extra_opt=None, retention=3, info=None):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return None, why
    tp = mesh.shape.get("model", 1) if profile != "dp" else 1
    rules = Rules(cfg, mesh, fsdp=fsdp, profile=profile)
    aparams = abstract_params(cfg, tp=tp)
    pspecs = rules.params_pspecs(aparams)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)

    if shape.kind == "train":
        import numpy as np
        opt = OptimizerConfig(name=optimizer, accumulation=accum,
                              micro_batches=micro_batches,
                              use_pallas=use_pallas,
                              **(extra_opt or {}))
        if zero1 and not opt.zero_stage:
            opt = dataclasses.replace(opt, zero_stage=1)
        dp_size = int(np.prod([mesh.shape[a] for a in rules.dp_axes()])) \
            if rules.dp_axes() else 1
        if engine == "shardmap":
            # shard_map splits micro-batches on the PER-DEVICE batch shard
            # (the dp axes are manual), so micro_batches must divide
            # global_batch / dp_size; the pure-DP profile at 256-way leaves
            # one local sample, forcing micro_batches=1. Clamp to the
            # largest feasible count instead of asserting mid-trace.
            local_gb = shape.global_batch // dp_size
            if local_gb == 0:
                return None, (f"global_batch {shape.global_batch} < "
                              f"{dp_size}-way manual DP (no local sample)")
            mb = min(opt.micro_batches, local_gb)
            while local_gb % mb:
                mb -= 1
            if mb != opt.micro_batches:
                print(f"[dryrun] {arch}/{shape_name}: micro_batches "
                      f"{opt.micro_batches} -> {mb} (local batch {local_gb} "
                      f"under {dp_size}-way manual DP must split evenly)")
                opt = dataclasses.replace(opt, micro_batches=mb)
            from repro.core.dp_shardmap import make_dp_train_step
            dp = rules.dp_axes()
            if accum == "ga":
                variant = "ga"
            elif accum == "adama_layerwise" and opt.zero_stage == 1 \
                    and opt.arena:
                # the layer-wise shard_map variant exists only as the
                # bucketed ZeRO-1 stream; otherwise fall back to adama
                variant = "adama_layerwise"
            else:
                variant = "adama"
            step, opt_init = make_dp_train_step(cfg, opt, mesh, dp, variant,
                                                remat=remat)
        else:
            step, opt_init = make_train_step(cfg, opt, remat=remat,
                                             state_shards=dp_size)
        aopt = jax.eval_shape(opt_init, aparams)
        ospecs = rules.opt_pspecs(aopt, aparams, zero1=zero1)
        if info is not None and engine == "shardmap" and \
                opt.zero_stage == 1 and opt.arena:
            # the ZeRO-1 gradient-collective schedule and its peak-live-
            # gradient budget: bucketed = one bucket's slab, full-pack =
            # the whole arena. run_one checks the compiled HLO's largest
            # reduce-scatter operand against this budget.
            from repro.core.zero import zero1_bucket_plan
            from repro.kernels.adama_accum import LANES
            from repro.configs.base import grad_wire_itemsize
            lay = aopt["m"].layout
            wire_bytes = grad_wire_itemsize(opt.grad_dtype)
            # the budget gate is STRICT only when every non-trivial mesh
            # axis is a manual DP axis: with an auto ("model") axis left to
            # GSPMD, the module may contain tensor-parallel reduce-scatters
            # that have nothing to do with the gradient buckets, and the
            # module-wide operand max would flag them spuriously
            auto = set(mesh.axis_names) - set(rules.dp_axes())
            info["grad_peak_strict"] = all(mesh.shape[a] == 1 for a in auto)
            # mirror the engine's schedule resolution: adama_layerwise IS
            # the bucketed stream, regardless of zero_bucketed
            if opt.zero_bucketed or variant == "adama_layerwise":
                plan = zero1_bucket_plan(lay, dp_size, opt.zero_bucket_rows)
                info["zero_schedule"] = ("async_double_buffered"
                                         if opt.zero_async else "bucketed")
                # budget in WIRE bytes: grad_dtype=bf16 halves the slab
                info["grad_peak_budget_bytes"] = \
                    plan.grad_peak_bytes(wire_bytes)
                info["n_grad_buckets"] = len(plan.grad_buckets())
                # LIVE budget: at most TWO buckets of gradient slab may be
                # in flight at once — one folding, one reduce-scattering
                # (the double-buffered pipeline's invariant; the serial
                # stream holds one, an unpinned unroll would let XLA hoist
                # every pack and blow straight past this). Post-opt CPU HLO
                # re-widens bf16 wires to f32, so the budget uses fp32
                # itemsize as the backend-safe upper bound.
                info["grad_live_budget_bytes"] = 2 * plan.grad_peak_bytes(4)
                if opt.grad_dtype == "fp8_e4m3":
                    # per-bucket (rows, 1) fp32 scale columns: the fp8
                    # wire's metadata overhead per micro-batch
                    info["scale_col_bytes"] = sum(
                        bk.rows * 4 for bk in plan.grad_buckets())
            else:
                info["zero_schedule"] = "full_pack"
                info["grad_peak_budget_bytes"] = lay.rows * LANES * wire_bytes
        if info is not None:
            # the mesh the program was built against, so roofline/compare
            # tooling can separate flat-dp artifacts from dp×tp ones
            info["mesh_shape"] = [int(mesh.shape[a])
                                  for a in mesh.axis_names]
            info["mesh_axes"] = list(mesh.axis_names)
            # measured optimizer-state footprint (the Table-3 row): global
            # bytes of the abstract state the engine allocates, and the
            # per-device share computed from the ACTUAL sharding specs —
            # leaves ZeRO-1 leaves unsharded dims full-size (a leaf with no
            # divisible dim stays replicated and costs every device its
            # whole size)
            from repro.core.state_store import optimizer_state_bytes
            info["optimizer_state_bytes"] = optimizer_state_bytes(aopt)
            # per-moment breakdown: a regression in one codec must not hide
            # behind the other moment's bytes in the lump sum
            info["optimizer_state_m_bytes"] = optimizer_state_bytes(
                aopt.get("m", ()))
            info["optimizer_state_v_bytes"] = optimizer_state_bytes(
                aopt.get("v", ()))
            info["optimizer_state_bytes_per_device"] = \
                _sharded_bytes(aopt, ospecs, mesh)
            info["state_codec"] = opt.state_codec
            info["m_codec"] = opt.m_codec
            # mixed-precision AdamA surface: the gradient wire dtype the
            # fold pipeline moves, and the fp32 master-param region's bytes
            # (0 when master_params is off)
            info["grad_wire_dtype"] = opt.grad_dtype
            info["master_param_bytes"] = optimizer_state_bytes(
                aopt.get("p", ()))
            # fp8 wire surface: the error-feedback residual region's bytes
            # (0 when the wire is not fp8 or the residual is ablated)
            info["ef_bytes"] = optimizer_state_bytes(aopt.get("ef", ()))
            # resilience surface: whether the compiled step carries the
            # fused finite guards, the loss-scaling mode riding them, and
            # the checkpoint retention a real launch of this combo would
            # run with (roofline/compare tooling keys off these)
            info["finite_guard"] = bool(opt.finite_guard)
            info["loss_scale"] = str(opt.loss_scale)
            info["checkpoint_retention"] = int(retention)
        osh = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs)
        batch = input_specs(cfg, shape)
        bspecs = rules.batch_pspecs(batch)
        bsh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs)
        # under shard_map the dp axes are manual: activation constraints may
        # only reference the auto ("model") axis — the ctx drops manual
        # axes from every constraint it emits (pure-DP profile: all of them)
        ctx_dp = () if engine == "shardmap" else rules.dp_axes()
        manual = rules.dp_axes() if engine == "shardmap" else ()
        with mesh, shard_ctx.use_mesh(mesh, ctx_dp, manual_axes=manual):
            lowered = jax.jit(
                step,
                in_shardings=(psh, osh, bsh),
                out_shardings=(psh, osh,
                               NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            ).lower(aparams, aopt, batch)
        return lowered, ""

    # serving paths use bf16 weights
    aparams = _cast_tree(aparams, jnp.bfloat16)
    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        bspecs = rules.batch_pspecs(batch)
        bsh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs)
        fn = prefill_whisper if cfg.arch_type == "audio" else prefill
        acache = jax.eval_shape(lambda p, b: fn(cfg, p, b)[1], aparams, batch)
        cspecs = rules.cache_pspecs(acache)
        csh = {k: NamedSharding(mesh, s) for k, s in cspecs.items()}
        dp = rules.dp_axes()
        with mesh, shard_ctx.use_mesh(mesh, dp):
            lowered = jax.jit(
                lambda p, b: fn(cfg, p, b),
                in_shardings=(psh, bsh),
                out_shardings=(NamedSharding(mesh, P(dp)), csh),
            ).lower(aparams, batch)
        return lowered, ""

    # decode
    cache, token, pos = input_specs(cfg, shape)
    cspecs = rules.cache_pspecs(cache)
    csh = {k: NamedSharding(mesh, s) for k, s in cspecs.items()}
    dp = rules.dp_axes()
    import numpy as np
    dpsz = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    bspec = P(dp) if token.shape[0] % max(dpsz, 1) == 0 and dp else P()
    bsh = NamedSharding(mesh, bspec)
    with mesh, shard_ctx.use_mesh(mesh, dp if bspec != P() else ()):
        lowered = jax.jit(
            lambda p, c, t, s_: serve_step(cfg, p, c, t, s_),
            in_shardings=(psh, csh, bsh, bsh),
            out_shardings=(NamedSharding(mesh, bspec), csh),
            donate_argnums=(1,),
        ).lower(aparams, cache, token, pos)
    return lowered, ""


def run_one(arch, shape_name, multi_pod, outdir, **kw):
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_tag}"
    for k, v in kw.items():
        if k in ("engine", "accum") and v not in ("pjit", "adama"):
            tag += f"__{k}-{v}"
        if k == "profile" and v != "tp2d":
            tag += f"__{k}-{v}"
        if k == "use_pallas" and v:
            tag += "__pallas"
        if k == "extra_opt" and v and v.get("arena"):
            tag += f"__arena-{v.get('state_codec', 'fp32')}"
            if v.get("m_codec", "fp32") != "fp32":
                tag += f"__m-{v['m_codec']}"
        if k == "extra_opt" and v and not v.get("zero_bucketed", True):
            tag += "__fullpack"
        if k == "extra_opt" and v and v.get("zero_async"):
            tag += "__async"
        if k == "extra_opt" and v and v.get("grad_dtype", "fp32") != "fp32":
            tag += f"__wire-{v['grad_dtype']}"
            if v["grad_dtype"] == "fp8_e4m3" and \
                    not v.get("error_feedback", True):
                tag += "__noef"
        if k == "extra_opt" and v and v.get("master_params"):
            tag += "__master"
        if k == "extra_opt" and v and v.get("finite_guard"):
            tag += "__guard"
            if v.get("loss_scale", "off") != "off":
                tag += f"-{v['loss_scale']}"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    info = {}
    try:
        lowered, why = build_lowered(arch, shape_name, mesh,
                                     info=info, **kw)
    except Exception as e:
        traceback.print_exc()
        return {"tag": tag, "status": "LOWER_FAIL", "error": f"{type(e).__name__}: {e}"}
    if lowered is None:
        rec = {"tag": tag, "status": "SKIP", "reason": why}
        _write(outdir, tag, rec)
        print(f"[dryrun] {tag}: SKIP ({why})")
        return rec
    t_lower = time.time() - t0
    try:
        compiled = lowered.compile()
    except Exception as e:
        traceback.print_exc()
        rec = {"tag": tag, "status": "COMPILE_FAIL",
               "error": f"{type(e).__name__}: {e}"}
        _write(outdir, tag, rec)
        return rec
    t_compile = time.time() - t0 - t_lower
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    hlo = analyze_hlo(txt)
    coll = {k[5:]: v for k, v in hlo.items() if k.startswith("coll_")}
    coll["total"] = hlo.get("coll_total", 0.0)
    # measured peak gradient live bytes: the largest single reduce-scatter
    # operand the step ever holds, read from the PRE-optimization HLO —
    # the program's wire dtypes (a bf16 gradient wire is bf16 there on
    # every backend; CPU's float normalization re-widens it post-opt). For
    # the bucketed ZeRO-1 schedule this must be O(max bucket), NOT
    # O(arena) — the point of the bucketed schedule; a violation fails the
    # dryrun. The wire-level collective total rides along for the
    # mixed-precision comm accounting.
    hlo_wire = analyze_hlo(lowered.as_text(dialect="hlo"))
    coll["wire_total"] = hlo_wire.get("coll_total", 0.0)
    # shard_map programs carry explicit collectives pre-opt (wire dtypes);
    # pjit programs get theirs from GSPMD during compilation, so the wire
    # parse is empty there — fall back to the post-opt (backend) peak
    rs_peak = hlo_wire.get("maxop_reduce-scatter", 0.0) or \
        hlo.get("maxop_reduce-scatter", 0.0)
    info["grad_rs_peak_bytes"] = rs_peak
    # schedule-level overlap metric (post-opt HLO is scheduled): fraction
    # of collective payload bytes the schedule lets run concurrently with
    # compute — the async pipeline's raison d'être (step_bench gates it >0)
    info["overlap_fraction"] = round(hlo.get("overlap_fraction", 0.0), 4)
    info["grad_rs_live_peak_bytes"] = hlo.get("live_peak_reduce-scatter", 0.0)
    bucketed_run = info.get("zero_schedule") in ("bucketed",
                                                 "async_double_buffered")
    budget = info.get("grad_peak_budget_bytes")
    if bucketed_run and budget is not None \
            and info.get("grad_peak_strict") and rs_peak > budget:
        rec = {"tag": tag, "status": "GRAD_PEAK_FAIL",
               "error": (f"bucketed ZeRO-1 reduce-scatter operand peak "
                         f"{rs_peak:.0f} B exceeds the max-bucket budget "
                         f"{budget} B — the schedule is packing more than "
                         f"one bucket at a time")}
        _write(outdir, tag, rec)
        return rec
    live_budget = info.get("grad_live_budget_bytes")
    live_peak = info["grad_rs_live_peak_bytes"]
    # the two-bucket LIVE gate polices the async pipeline's barrier pinning
    # only: the SERIAL bucketed schedule's packs are deliberately unpinned
    # (XLA is free to hoist them), so a valid serial config can legitimately
    # hold more than two buckets — the metric is still recorded for it above
    if info.get("zero_schedule") == "async_double_buffered" \
            and live_budget is not None \
            and info.get("grad_peak_strict") and live_peak > live_budget:
        rec = {"tag": tag, "status": "GRAD_PEAK_FAIL",
               "error": (f"scheduled live reduce-scatter operand peak "
                         f"{live_peak:.0f} B exceeds the two-bucket budget "
                         f"{live_budget} B — more than two gradient "
                         f"buckets are in flight at once (the pipeline's "
                         f"barrier pinning is not holding)")}
        _write(outdir, tag, rec)
        return rec
    n_dev = 512 if multi_pod else 256
    rec = {
        "tag": tag, "status": "OK", "arch": arch, "shape": shape_name,
        "mesh": mesh_tag, "devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes +
                                      ma.output_size_in_bytes +
                                      ma.temp_size_in_bytes -
                                      ma.alias_size_in_bytes),
            # train shapes only: measured optimizer-state footprint
            # (global + ZeRO-1 per-device share) and its codec
            **info,
        },
        "cost": {"flops": ca.get("flops", 0.0),
                 "bytes_accessed": ca.get("bytes accessed", 0.0),
                 # loop-aware (trip-count-multiplied) parses — use these
                 "flops_loop_aware": hlo.get("flops", 0.0),
                 "bytes_loop_aware": hlo.get("bytes", 0.0)},
        "collectives": coll,
        "options": {k: str(v) for k, v in kw.items()},
    }
    _write(outdir, tag, rec)
    gb = 1 << 30
    print(f"[dryrun] {tag}: OK peak/device={rec['memory']['peak_bytes_per_device']/gb:.2f} GiB "
          f"flops={rec['cost']['flops']:.3e} coll={coll.get('total', 0)/gb:.3f} GiB "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def _write(outdir, tag, rec):
    if outdir:
        Path(outdir).mkdir(parents=True, exist_ok=True)
        with open(Path(outdir) / f"{tag}.json", "w") as f:
            json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--engine", default="pjit", choices=["pjit", "shardmap"])
    ap.add_argument("--accum", default="adama",
                    choices=["ga", "adama", "adama_layerwise"])
    ap.add_argument("--optimizer", default="adama",
                    choices=["adam", "adama", "adafactor", "sm3"])
    ap.add_argument("--micro-batches", type=int, default=8)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--profile", default="tp2d", choices=["tp2d", "dp"])
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--arena", action="store_true",
                    help="flat optimizer-state arena (implies --use-pallas)")
    ap.add_argument("--state-codec", default="fp32",
                    choices=list(STATE_CODECS),
                    help="second-moment codec over the arena")
    ap.add_argument("--m-codec", default="fp32", choices=list(M_CODECS),
                    help="first-moment codec over the arena")
    ap.add_argument("--zero-full-pack", action="store_true",
                    help="legacy full-arena pack+scatter ZeRO-1 schedule in "
                         "the shard_map engine (default: bucketed)")
    ap.add_argument("--zero-bucket-rows", type=int, default=0,
                    help="rest-region bucket cap in arena rows for the "
                         "bucketed ZeRO-1 schedule (0 = default)")
    ap.add_argument("--zero-async", action="store_true",
                    help="explicit double-buffered bucket pipeline: bucket "
                         "i+1's pack+reduce-scatter issued while bucket i "
                         "folds, barrier-pinned to two live buckets "
                         "(bitwise-identical numerics; requires the "
                         "bucketed ZeRO-1 schedule)")
    ap.add_argument("--grad-dtype", default="fp32", choices=list(GRAD_DTYPES),
                    help="gradient WIRE dtype of the arena fold pipeline: "
                         "bf16 halves the packed slab and every gradient "
                         "collective (fold kernels upcast in-kernel); "
                         "fp8_e4m3 moves 1-byte codes + per-row scale "
                         "columns with an error-feedback residual "
                         "(requires --finite-guard; in the shard_map "
                         "engine also bucketed ZeRO-1 + --master-params); "
                         "requires --arena")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="ablate the fp8 error-feedback residual "
                         "(state['ef']) — the fig2 convergence-gap "
                         "comparison; only meaningful with --grad-dtype "
                         "fp8_e4m3")
    ap.add_argument("--master-params", action="store_true",
                    help="fp32 master params in the arena + bf16 working "
                         "params emitted by the fused apply (AMP contract); "
                         "requires --arena")
    ap.add_argument("--finite-guard", action="store_true",
                    help="fused non-finite guards in the compiled step "
                         "(train/scaler.py); implies --arena")
    ap.add_argument("--loss-scale", default="off",
                    help="'off', 'dynamic', or a positive float — loss "
                         "scaling fused into the guarded fold kernels; "
                         "implies --finite-guard and --arena, requires "
                         "--grad-dtype bf16 or fp8_e4m3")
    ap.add_argument("--keep-last-n", type=int, default=3,
                    help="checkpoint retention recorded in the artifact "
                         "(the dryrun itself saves nothing)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    extra_opt = None
    guard = args.finite_guard or args.loss_scale != "off"
    if args.arena or args.state_codec != "fp32" or args.m_codec != "fp32" \
            or args.grad_dtype != "fp32" or args.master_params or guard:
        extra_opt = {"arena": True, "state_codec": args.state_codec,
                     "m_codec": args.m_codec,
                     "grad_dtype": args.grad_dtype,
                     "master_params": args.master_params,
                     "finite_guard": guard,
                     "loss_scale": args.loss_scale,
                     "error_feedback": not args.no_error_feedback}
    if args.zero_full_pack or args.zero_bucket_rows:
        extra_opt = dict(extra_opt or {},
                         zero_bucketed=not args.zero_full_pack,
                         zero_bucket_rows=args.zero_bucket_rows)
    if args.zero_async:
        # zero_async is only defined over the bucketed ZeRO-1 schedule, so
        # the flag implies zero_stage=1 + arena (config validation refuses
        # the combo otherwise)
        extra_opt = dict(extra_opt or {}, arena=True, zero_async=True,
                         zero_stage=1)
    kw = dict(engine=args.engine, accum=args.accum,
              micro_batches=args.micro_batches, fsdp=not args.no_fsdp,
              remat=not args.no_remat, zero1=args.zero1,
              use_pallas=args.use_pallas or args.arena or
              extra_opt is not None,
              optimizer=args.optimizer,
              profile=args.profile, extra_opt=extra_opt,
              retention=args.keep_last_n)
    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        combos.append((args.arch, args.shape))

    fails = 0
    for arch, shape in combos:
        mesh_tag = "pod2x16x16" if args.multi_pod else "pod16x16"
        tag = f"{arch}__{shape}__{mesh_tag}"
        p = Path(args.out) / f"{tag}.json"
        if args.skip_existing and p.exists():
            st = json.loads(p.read_text()).get("status")
            if st in ("OK", "SKIP"):
                print(f"[dryrun] {tag}: cached {st}")
                continue
        rec = run_one(arch, shape, args.multi_pod, args.out, **kw)
        if rec["status"] not in ("OK", "SKIP"):
            fails += 1
            print(f"[dryrun] {tag}: {rec['status']}: {rec.get('error')}")
    if fails:
        raise SystemExit(f"{fails} combinations failed")
    print("[dryrun] all combinations OK")


if __name__ == "__main__":
    main()
