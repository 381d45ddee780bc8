"""Training launcher.

Runs on whatever backend JAX finds: a TPU at published widths (bert_large
at global batch 4 x seq 512 fits one v5e chip; see chip_smoke.py), or the
CPU with `--reduced` widths and the Pallas kernels in interpret mode.
`launch/dryrun.py` compiles production meshes without running them.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --reduced \
      --steps 50 --accumulation adama --micro-batches 4
"""
from __future__ import annotations

import argparse

from repro.configs import InputShape, OptimizerConfig, RunConfig, get_config
from repro.configs.base import GRAD_DTYPES, M_CODECS, STATE_CODECS
from repro.launch.compile_cache import use_compile_cache
from repro.optim import schedule as sched
from repro.train.loop import train


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale variant of the arch family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--micro-batches", type=int, default=4)
    ap.add_argument("--accumulation", default="adama",
                    choices=["ga", "adama", "adama_layerwise"])
    ap.add_argument("--optimizer", default="adama",
                    choices=["adam", "adama", "adafactor", "sm3"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--arena", action="store_true",
                    help="flat optimizer-state arena: O(1) kernel dispatches "
                         "per micro-batch (implies --use-pallas)")
    ap.add_argument("--state-codec", default="fp32",
                    choices=list(STATE_CODECS),
                    help="second-moment codec over the arena "
                         "(core/state_store.py); requires --arena")
    ap.add_argument("--m-codec", default="fp32", choices=list(M_CODECS),
                    help="first-moment codec over the arena "
                         "(core/state_store.py); requires --arena")
    ap.add_argument("--zero-stage", type=int, default=0, choices=[0, 1],
                    help="ZeRO-1 optimizer-state sharding; with --arena the "
                         "state shards by row range (no-op on one device)")
    ap.add_argument("--zero-full-pack", action="store_true",
                    help="legacy full-arena pack+scatter ZeRO-1 gradient "
                         "schedule instead of the default bucketed "
                         "reduce-scatter stream (consulted by the shard_map "
                         "DP engine: launch/dryrun.py, benchmarks/"
                         "step_bench.py; inert in this pjit loop)")
    ap.add_argument("--zero-bucket-rows", type=int, default=0,
                    help="rest-region bucket cap in arena rows for the "
                         "bucketed ZeRO-1 schedule (0 = default cap)")
    ap.add_argument("--zero-async", action="store_true",
                    help="async double-buffered bucket pipeline: bucket "
                         "i+1's pack + reduce-scatter issued while bucket "
                         "i folds, pinned to two live buckets (consulted "
                         "by the shard_map DP engine like --zero-full-pack;"
                         " inert in this pjit loop); requires --zero-stage "
                         "1 --arena and the bucketed schedule")
    ap.add_argument("--grad-dtype", default="fp32", choices=list(GRAD_DTYPES),
                    help="gradient WIRE dtype of the arena fold pipeline "
                         "(bf16 halves the packed gradient slab and every "
                         "gradient collective; fp8_e4m3 packs 1-byte codes "
                         "+ per-row scale columns and recovers accuracy "
                         "with an error-feedback residual, requires "
                         "--finite-guard; fold kernels decode/upcast "
                         "in-kernel); requires --arena, not 'ga'")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="ablate the fp8 error-feedback residual "
                         "(state['ef']) — convergence degrades to raw fp8 "
                         "rounding; only meaningful with --grad-dtype "
                         "fp8_e4m3")
    ap.add_argument("--master-params", action="store_true",
                    help="fp32 master params packed in the arena; the fused "
                         "apply emits bf16 working params (AMP contract); "
                         "requires --arena")
    ap.add_argument("--work-param-cache", action="store_true",
                    help="bf16 working-param cache in the arena "
                         "(state['wp']): pjit engines source step params "
                         "from it, skipping the per-step pack/unpack pair; "
                         "requires --master-params")
    ap.add_argument("--finite-guard", action="store_true",
                    help="fused non-finite guards: each micro-batch's packed "
                         "gradient is checked before the fold commits and a "
                         "bad micro-batch is skipped as a bitwise no-op "
                         "(train/scaler.py); requires --arena")
    ap.add_argument("--loss-scale", default="off",
                    help="'off', 'dynamic', or a positive float: loss "
                         "scaling fused into the fold kernels' upcast; "
                         "implies --finite-guard, requires --grad-dtype "
                         "bf16 or fp8_e4m3 and a non-'ga' accumulation")
    ap.add_argument("--scaler-abort-after", type=int, default=0,
                    help="abort after N CONSECUTIVE skipped micro-batches "
                         "(0 = never abort)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="save every N steps (0 = the 5*log-every heuristic)")
    ap.add_argument("--keep-last-n", type=int, default=3,
                    help="checkpoint retention: keep only the newest N steps")
    ap.add_argument("--inject-fault", default=None,
                    help="fault-injection spec (train/faults.py grammar), "
                         "e.g. nan@micro=1 | inf@micro=0,step=2 | "
                         "crash@step=3")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def build_run(args: argparse.Namespace):
    """(RunConfig, lr schedule) for parsed CLI arguments."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = InputShape("cli", args.seq_len, args.global_batch, "train")
    run = RunConfig(
        model=cfg,
        optimizer=OptimizerConfig(
            name=args.optimizer, accumulation=args.accumulation,
            micro_batches=args.micro_batches, lr=args.lr,
            use_pallas=args.use_pallas or args.arena, arena=args.arena,
            state_codec=args.state_codec, m_codec=args.m_codec,
            zero_stage=args.zero_stage,
            zero_bucketed=not args.zero_full_pack,
            zero_bucket_rows=args.zero_bucket_rows,
            zero_async=args.zero_async,
            grad_dtype=args.grad_dtype,
            error_feedback=not args.no_error_feedback,
            master_params=args.master_params,
            work_param_cache=args.work_param_cache,
            finite_guard=args.finite_guard or args.loss_scale != "off",
            loss_scale=args.loss_scale,
            scaler_abort_after=args.scaler_abort_after),
        shape=shape, seed=args.seed, steps=args.steps,
        log_every=args.log_every, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        keep_last_n=args.keep_last_n, inject_fault=args.inject_fault)
    return run, sched.warmup_cosine(args.lr, args.warmup, args.steps)


def main(argv=None):
    run, lr_fn = build_run(parse_args(argv))
    use_compile_cache()
    out = train(run, lr_schedule=lr_fn)
    print(f"[train] done; final loss {out['losses'][-1]:.4f} "
          f"(first {out['losses'][0]:.4f})")


if __name__ == "__main__":
    main()
