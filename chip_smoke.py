"""Smoke test of AdamA training on a TPU.

    python chip_smoke.py             # one chip: kernel phase + training phase
    python chip_smoke.py --chips 4   # four chips: the ZeRO-1 phase only

Everything runs in this one process, which holds the chip(s); it starts no
other process.

kernel phase    `arena_fold` (fp32 state; guarded bf16 wire) and
                `arena_apply` (fp32 master + bf16 work params) compiled for
                the chip over a 16,384-row slab of bert_large's arena
                layout, checked against the jnp oracles in kernels/ref.py.
training phase  full-width bert_large (24 x 1024, 16 heads, d_ff 4096,
                vocab 30522, random weights from --seed) trained through
                repro.train.loop.train with the RunConfig that
                launch/train.py builds: AdamA, --arena, 4 micro-batches,
                global batch 4 x seq 512. The compiled step must hold the
                two Pallas kernels (fold + apply) as `tpu_custom_call`s,
                which proves no kernel ran in interpret mode. The same seed
                is then trained on the XLA reference path (no --arena, no
                Pallas) and the two loss trajectories must agree.
ZeRO-1 phase    (--chips 4) `--zero-stage 1 --arena` over a 4-chip data
                mesh at global batch 16 x seq 128 (one sequence per chip
                per micro-batch), against the same job at zero_stage 0 on
                chip 0. Each chip must hold 1/4 of every m/v row-indexed
                column, the batch must arrive split over "data", and the
                per-chip program's attention tensors must hold one
                sequence, never the whole micro-batch of four.

Exits non-zero, with no result line, when JAX finds no TPU, when the repo's
sources are missing, or when any phase fails. The last line of a passing
run is one JSON object: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "bert-large"
SLAB_ROWS = 16_384            # >= 16k arena rows: ~1.3 layers of bert_large
BETA1, BETA2 = 0.9, 0.999
EPS32 = 2.0 ** -23            # fp32 unit in the last place, relative
EPS_BF16 = 2.0 ** -7          # bf16 unit in the last place, relative

# Kernel-vs-oracle tolerances, as multiples of max|oracle| (one ulp there
# bounds one rounding anywhere in the array):
# - fold: two products and a sum per element; Mosaic and XLA may contract
#   them into FMAs differently, each rounding at most half an ulp.
FOLD_TOL_ULPS = 4
# - apply master: p - lr*u. sqrt and divide on the TPU are refined
#   approximations a few ulps off, but they move u, and lr*u is ~1e-2 of p
#   here, so p itself sees at most about one rounding of its own.
APPLY_TOL_ULPS = 4
# - apply work: the bf16 cast of the master; one fp32 ulp of master drift
#   can cross a bf16 rounding boundary, i.e. one bf16 ulp.
WORK_TOL = EPS_BF16

# Loss-trajectory tolerance, relative to the loss: both paths run the same
# bf16 forward/backward, and programs fused differently round bf16
# activations at different points, so one bf16 rounding of the loss
# (2^-8 relative, ~0.04 at bert_large's initial ln(30522) ~ 10.3) bounds a
# benign difference. The kernel phase is the tight check of fold and apply;
# this one catches a path that trains differently end to end.
LOSS_RTOL = 2.0 ** -8

# Training job (launch/train.py arguments). lr 1e-4 is BERT's published
# pre-training peak; warmup 2 so the last steps update at full lr.
TRAIN_ARGS = ["--arch", ARCH, "--accumulation", "adama", "--lr", "1e-4",
              "--warmup", "2", "--log-every", "1"]
ONE_CHIP_JOB = ["--global-batch", "4", "--micro-batches", "4",
                "--seq-len", "512"]
FOUR_CHIP_JOB = ["--global-batch", "16", "--micro-batches", "4",
                 "--seq-len", "128"]
EXPECTED_CUSTOM_CALLS = 2     # adama + arena: one fold, one apply


def check(cond, msg):
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def _version(pkg):
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found; JAX's first device is on "
                 f"platform {dev.platform!r} ({dev.device_kind}). This "
                 f"script never falls back to the CPU.")
    return dev


def custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def program_bytes(compiled) -> str:
    """The compiler's own count for one program: arguments + temporaries."""
    ma = compiled.memory_analysis()
    return (f"arguments {ma.argument_size_in_bytes} + temporaries "
            f"{ma.temp_size_in_bytes} = "
            f"{ma.argument_size_in_bytes + ma.temp_size_in_bytes} bytes")


def max_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def compare(name, out, ref, tol_rel):
    ref = np.asarray(ref, np.float32)
    tol = tol_rel * float(np.max(np.abs(ref)))
    d = max_diff(out, ref)
    print(f"[kernel] {name}: max|kernel - oracle| = {d!r} "
          f"(tolerance {tol!r})")
    check(d <= tol, f"{name} differs from its oracle by {d} > {tol}")


def kernel_phase(cfg, seed, slab_rows):
    """Fold and apply on a slab of the model's arena, against kernels/ref."""
    from repro.core import arena
    from repro.kernels import fused_step as fs
    from repro.kernels import ref
    from repro.kernels.adama_accum import LANES
    from repro.models.model import init_params

    layout = arena.build_layout(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(seed))))
    rows = min(slab_rows, layout.rows)
    print(f"[kernel] arena layout: {layout.rows} rows x {LANES} lanes; "
          f"slab rows [0, {rows})")
    k = jax.random.split(jax.random.key(seed), 4)
    shape = (rows, LANES)
    m = 1e-3 * jax.random.normal(k[0], shape)
    v = 1e-6 * (1.0 + jax.random.uniform(k[1], shape))
    g = 1e-2 * jax.random.normal(k[2], shape)
    p = 0.02 * jax.random.normal(k[3], shape)
    scale = 0.25                   # 1/N for 4 micro-batches
    fold_kw = dict(beta1=BETA1, beta2=BETA2, scale=scale,
                   decay=(BETA1, BETA2))

    fold = jax.jit(lambda m, v, g: fs.arena_fold(m, v, g, **fold_kw))
    oracle = jax.jit(lambda m, v, g: ref.adama_accum_ref(
        BETA1 * m, BETA2 * v, g, beta1=BETA1, beta2=BETA2, scale=scale))
    mo, vo = fold(m, v, g)
    mr, vr = oracle(m, v, g)
    compare("arena_fold fp32 m", mo, mr, FOLD_TOL_ULPS * EPS32)
    compare("arena_fold fp32 v", vo, vr, FOLD_TOL_ULPS * EPS32)

    g16 = g.astype(jnp.bfloat16)
    gfold = jax.jit(lambda m, v, g: fs.arena_fold(
        m, v, g, grad_dtype=jnp.bfloat16, guard=True, **fold_kw))
    mo, vo, ok = gfold(m, v, g16)
    mr, vr = oracle(m, v, g16.astype(jnp.float32))
    check(bool(ok), "guarded bf16 fold flagged a finite slab")
    compare("arena_fold guarded bf16 wire m", mo, mr, FOLD_TOL_ULPS * EPS32)
    compare("arena_fold guarded bf16 wire v", vo, vr, FOLD_TOL_ULPS * EPS32)
    mo, vo, ok = gfold(m, v, g16.at[rows // 2, 7].set(jnp.nan))
    check(not bool(ok), "guarded bf16 fold passed a slab holding a NaN")
    check(max_diff(mo, m) == 0.0 and max_diff(vo, v) == 0.0,
          "guarded fold of a NaN slab changed the state")
    print("[kernel] arena_fold guarded bf16 wire, NaN slab: skipped, "
          "state bitwise unchanged")

    lr, bc1, bc2 = 1e-3, 1.0 - BETA1, 1.0 - BETA2
    apply = jax.jit(lambda p, m, v: fs.arena_apply(
        p, m, v, lr=lr, bc1=bc1, bc2=bc2, work_dtype=jnp.bfloat16))
    apply_ref = jax.jit(lambda p, m, v: ref.adam_apply_ref(
        p, m, v, lr=lr, bc1=bc1, bc2=bc2))
    po, wo = apply(p, m, v)
    pr = apply_ref(p, m, v)
    check(wo.dtype == jnp.bfloat16, f"work params are {wo.dtype}, not bf16")
    compare("arena_apply master p", po, pr, APPLY_TOL_ULPS * EPS32)
    compare("arena_apply bf16 work p", wo, pr.astype(jnp.bfloat16), WORK_TOL)


def train_job(argv, log_prefix):
    """Train through launch/train.py's RunConfig; returns train()'s dict."""
    from repro.launch.train import build_run, parse_args
    from repro.train.loop import train

    run, lr_fn = build_run(parse_args(argv))
    t0 = time.perf_counter()
    out = train(run, lr_schedule=lr_fn,
                log_fn=lambda s: print(f"{log_prefix} {s}"))
    wall = time.perf_counter() - t0
    losses = out["losses"]
    print(f"{log_prefix} losses {losses}")
    print(f"{log_prefix} compile {out['compile_s']!r} s; train() wall "
          f"{wall!r} s")
    print(f"{log_prefix} compiled step: {program_bytes(out['compiled'])}")
    check(len(losses) == run.steps and all(np.isfinite(losses)),
          f"{log_prefix} non-finite or missing losses: {losses}")
    return out


def compare_losses(name, a, b):
    diffs = [abs(x - y) for x, y in zip(a, b)]
    tol = LOSS_RTOL * max(abs(x) for x in b)
    print(f"[{name}] per-step |loss difference| {diffs} (tolerance {tol!r})")
    check(max(diffs) <= tol, f"{name}: loss trajectories differ by "
          f"{max(diffs)} > {tol}")


def training_phase(dev, steps, extra=()):
    argv = TRAIN_ARGS + ONE_CHIP_JOB + ["--steps", str(steps), *extra]
    out = train_job(argv + ["--arena"], "[train pallas]")
    n = custom_calls(out["compiled"])
    print(f"[train pallas] tpu_custom_calls in the compiled step: {n}")
    check(n == EXPECTED_CUSTOM_CALLS,
          f"compiled step holds {n} tpu_custom_calls, expected "
          f"{EXPECTED_CUSTOM_CALLS}")
    print(f"[train pallas] peak_bytes_in_use {peak_bytes(dev)}; "
          f"memory_stats {dev.memory_stats()}")
    losses = out["losses"]
    del out
    ref = train_job(argv, "[train xla]")
    print(f"[train xla] peak_bytes_in_use (process peak so far) "
          f"{peak_bytes(dev)}")
    compare_losses("train", losses, ref["losses"])


def zero1_phase(steps, extra=()):
    from jax.sharding import PartitionSpec as P

    n_dev = jax.device_count()
    check(n_dev == 4, f"--chips 4 needs 4 devices, found {n_dev}")
    argv = TRAIN_ARGS + FOUR_CHIP_JOB + ["--steps", str(steps), "--arena",
                                         *extra]
    ref_losses = train_job(argv + ["--zero-stage", "0"],
                           "[zero0 chip0]")["losses"]
    out = train_job(argv + ["--zero-stage", "1"], "[zero1 4 chips]")
    compare_losses("zero1 vs zero0", out["losses"], ref_losses)

    for name in ("m", "v"):
        for x in jax.tree.leaves(out["opt_state"][name]):
            shards = x.addressable_shards
            rows = [s.data.shape[0] for s in shards]
            print(f"[zero1] {name} {x.shape}: rows per chip {rows}")
            check(len({s.device for s in shards}) == n_dev
                  and rows == [x.shape[0] // n_dev] * n_dev,
                  f"{name} is not row-sharded 1/{n_dev} per chip: {rows}")
    batch_sh = out["compiled"].input_shardings[0][2]
    specs = {tuple(s.spec) for s in jax.tree.leaves(batch_sh)}
    print(f"[zero1] batch input specs {specs}")
    check(specs == {tuple(P("data"))}, f"batch is not split over data: "
          f"{specs}")
    # attention operands (B, H, S, d) in the per-chip program: each chip
    # holds micro_batch / n_dev sequences, never the whole micro-batch
    from repro.launch.train import build_run, parse_args
    run = build_run(parse_args(argv))[0]
    mb = run.shape.global_batch // run.optimizer.micro_batches
    heads, seq = run.model.n_heads, run.shape.seq_len
    hlo = out["compiled"].as_text()
    local = f"[{mb // n_dev},{heads},{seq},"
    whole = f"[{mb},{heads},{seq},"
    print(f"[zero1] per-chip attention tensors {local}...]: "
          f"{hlo.count(local)}; whole-micro-batch {whole}...]: "
          f"{hlo.count(whole)}")
    check(hlo.count(local) > 0 and hlo.count(whole) == 0,
          "attention operands are not batch-sharded over data")
    for d in jax.devices():
        print(f"[zero1] {d} peak_bytes_in_use {peak_bytes(d)}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    print(f"[smoke] platform {dev.platform}; device_kind {dev.device_kind}; "
          f"devices {jax.device_count()}")
    print(f"[smoke] jax {jax.__version__}; jaxlib {_version('jaxlib')}; "
          f"libtpu {_version('libtpu')}")
    print(f"[smoke] compile cache {cache}")
    seed = ["--seed", str(args.seed)]
    if args.chips == 4:
        zero1_phase(args.steps, seed)
    else:
        kernel_phase(get_config(ARCH), args.seed, SLAB_ROWS)
        training_phase(dev, args.steps, seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
