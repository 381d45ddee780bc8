"""Readings that set the upper ends of a cell's limits: the control and the
faults, with the reference put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed it follows the cell's first three steps with the float32
reference (spread over the cell's chips, as the benchmark's runs spread
it), notes the time it took and each chip's memory peak after it (the
peak of the first seed is the reference's own), then reads the numbers
the benchmark compares (bench/benchkit/check.py) for:

  control          the reference computed with every matrix product in
                   float8 e4m3 (one step below the bf16 the configurations
                   state);
  half_batch       the reference with the second half of each step's batch
                   left out (its labels dropped), the mean taken over the
                   rest;
  no_exchange      (cells on several chips) the reference seeing only the
                   rows the first chip holds of each micro-batch, as if the
                   gradients were never exchanged;
  state_unchanged  a step that returns its state unchanged: the losses
                   repeat step 1's and the moments and parameters do not
                   move (worked out, not run).

Prints one JSON object per seed. The benchmark's own runs never run this;
bench/tests/test_bench_control.py runs it at a CPU-scale size.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def drop_rows(batch, keep):
    """The batch with the labels of every row not in `keep` dropped."""
    import jax.numpy as jnp
    rows = batch["labels"].shape[0]
    mask = jnp.isin(jnp.arange(rows), jnp.asarray(sorted(keep)))
    return dict(batch, labels=jnp.where(mask[:, None], batch["labels"], -1))


def readings(cell, seed: int, *, reduced: bool = False):
    import jax
    from benchkit import harness, spec
    from benchkit.data import make_stream
    from benchkit.weights import leaves_of, program_shapes
    run = spec.build_run(cell, seed, reduced=reduced)
    stream = make_stream(cell, run.model, seed)
    leaves = leaves_of(program_shapes(run.model))
    t = cell.traffic
    gb, n = t["global_batch"], t["micro_batches"]
    t0 = time.perf_counter()
    ref = harness.reference_readings(cell, run, seed, stream, leaves)
    # the reference alone has run in this process so far: the peak is its
    out = {"seed": seed, "reference_s": time.perf_counter() - t0,
           "reference_peak_bytes": [
               (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:cell.chips]]}

    def numbers(r):
        got = harness.compare(r, ref)
        return {k: got[k] for k in ("loss_gap", "grad_gap", "change_gap")}

    out["control"] = numbers(harness.reference_readings(
        cell, run, seed, stream, leaves, mode="fp8"))
    out["half_batch"] = numbers(harness.reference_readings(
        cell, run, seed, stream, leaves,
        batch_filter=lambda b: drop_rows(b, range(gb // 2))))
    if cell.chips > 1:
        per = gb // n
        first = [i * per + j for i in range(n)
                 for j in range(per // cell.chips)]
        out["no_exchange"] = numbers(harness.reference_readings(
            cell, run, seed, stream, leaves,
            batch_filter=lambda b: drop_rows(b, first)))
    ref_losses, ref_m, ref_change = ref
    zero = {k: v * 0.0 for k, v in ref_m.items()}
    out["state_unchanged"] = numbers(
        ([ref_losses[0]] * len(ref_losses), zero,
         {k: v * 0.0 for k, v in ref_change.items()}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from benchkit import harness, spec
    cell = spec.load_cell(args.workload)
    harness.require_devices(cell.chips)
    harness.use_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = readings(cell, seed)
        r["seconds"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
