"""The chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations and metrics are named in BENCHMARK.json at the
checkout's root; each has files of its own under bench/ (see
bench/benchkit/spec.py). The run trains the cell's model through
`repro.train.loop.train` on the chips JAX finds, measures `--seconds` of
steps, checks the first steps against the float32 reference, and prints one
JSON object as the last line of standard output. With `--trace 0` its
metrics are the end-to-end ones, with `--trace 1` the per-layer ones, read
from a profiler trace of the window. The numbers compared for `correct`
are the last lines of standard error and the line's last key.

Exits non-zero with no result when JAX finds no TPU or fewer chips than the
cell needs, when the device kind has no entry in the table of peaks, or
when the program's sources (src/repro) are not beside bench/.
"""
from __future__ import annotations

import os
import time


def _process_start() -> float:
    """perf_counter() reading at the moment this process started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - start_ticks / os.sysconf("SC_CLK_TCK"))
        return now - max(age, 0.0)
    except (OSError, ValueError, IndexError, AttributeError):
        return now


T_PROC = _process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program's sources are not at {ROOT / 'src'}",
              file=sys.stderr)
        return 3
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from benchkit import harness, peaks
    harness.use_cache()
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_proc=T_PROC)
    except (harness.NoAccelerator, peaks.UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
