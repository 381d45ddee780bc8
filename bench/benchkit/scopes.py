"""The training step's time by phase, from the names the program gives them.

Device phases come from `jax.named_scope`s in the program. Each reaches
the compiled step's HLO as the instruction's `metadata={op_name=...}`, a
path such as "jit(step)/while/body/closed_call/transpose(jvp(model))/dot".
A fusion carries the op_name of its root. The phases, by the benchmark's
own strings:

  forward    under `model` (or `jvp(model)`), with no `transpose(` and no
             `rematted_computation` below it;
  backward   under `model` with a `transpose(` (as `transpose(jvp(model))`)
             or a `rematted_computation` (remat recompute) at or below it,
             or under `model.recompute` (a layer's forward that the
             layer-wise engine recomputes inside its backward);
  grad_pack  under `optimizer.grad_pack`, the gradient tree packed into an
             arena slab;
  optimizer  under `optimizer.fold`, `optimizer.accumulate` or
             `optimizer.apply`.

The innermost optimizer scope of a path wins, even inside the backward
(the layer-wise engine folds there). Everything else (loop control, the
loss average, copies the compiler adds outside any scope) belongs to no
phase. `device_ms` sums each phase's operations' self time on each chip
(`Reduced.op_seconds`), per step of the window, mean over chips.

Host waits come from the program's host spans (`train/loop.py`):
`wait_ms` is the device's idle time that overlaps the named spans, per
step of the window, mean over chips. The overlap is taken interval by
interval, so a gap that spans several spans is shared out among them.

The host's and a chip's clocks in one trace can disagree by a few ms (on
a v5e a step's first operation has been seen to start 0.7 ms before the
host called the step). So before the overlap each chip's operations are
shifted by the smallest amount that makes them causal against the
synchronous loop: a step runs from its `train.dispatch` span's start to
the end of its `train.sync` span (the loss read returned), and at no
other time does the chip run anything. When no shift of less than half a
step satisfies that (a loop that is not synchronous, a trace with no such
spans), the clocks are taken as they are.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchkit.trace import measure, subtract, union

MODEL = "model"
RECOMPUTE = "model.recompute"
GRAD_PACK = "optimizer.grad_pack"
OPTIMIZER = ("optimizer.fold", "optimizer.accumulate", "optimizer.apply")
INPUT_SPANS = ("train.batch",)
LOOP_SPANS = ("train.dispatch", "train.sync", "train.log",
              "train.checkpoint")
DISPATCH, SYNC = "train.dispatch", "train.sync"

_MODEL_PART = re.compile(r"(?:[\w.]+\()*" + re.escape(MODEL) + r"\)*")
_RECOMPUTE_PART = re.compile(r"(?:[\w.]+\()*" + re.escape(RECOMPUTE)
                             + r"\)*")
_NAME = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of every instruction of a compiled
    program's HLO text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        if m:
            o = _OP_NAME.search(line)
            if o:
                out[m.group(1)] = o.group(1)
    return out


def classify(op_name: str) -> Optional[str]:
    """"forward", "backward", "grad_pack", "optimizer" or None. An
    instruction the compiler merged from several carries their op_names
    joined by ";": the first one decides."""
    parts = op_name.split(";")[0].split("/")
    for p in reversed(parts):
        if p == GRAD_PACK:
            return "grad_pack"
        if p in OPTIMIZER:
            return "optimizer"
    for k, p in enumerate(parts):
        if _RECOMPUTE_PART.fullmatch(p):
            return "backward"
        if _MODEL_PART.fullmatch(p):
            below = parts[k:]
            if any(q.startswith("transpose(") for q in below) \
                    or "rematted_computation" in below:
                return "backward"
            return "forward"
    return None


def device_ms(ctx) -> Dict[str, float]:
    """{phase: device self ms per step of the window, mean over chips};
    empty when the run was not traced or the program names no phase."""
    if ctx.trace is None or ctx.info.compiled is None or not ctx.info.steps:
        return {}
    names = op_names(ctx.info.compiled.as_text())
    chips = sorted(ctx.trace.ops)
    tot: Dict[str, float] = collections.defaultdict(float)
    for chip in chips:
        for inst, secs in ctx.trace.op_seconds(chip).items():
            phase = classify(names.get(inst, ""))
            if phase is not None:
                tot[phase] += secs
    return {p: 1e3 * s / len(chips) / ctx.info.steps for p, s in tot.items()}


def _steps_run(red) -> List[Tuple[int, int]]:
    """Host intervals in which the synchronous loop may keep the device
    busy: each `train.dispatch` span's start to the end of the
    `train.sync` span after it (the loss read returned)."""
    ends = sorted(b for n, _, b, _ in red.host if n == SYNC)
    runs = []
    for n, a, _, _ in red.host:
        if n == DISPATCH:
            k = bisect.bisect_left(ends, a)
            runs.append((a, ends[k] if k < len(ends) else red.hi))
    return runs


def clock_shift(busy: Sequence[Tuple[int, int]],
                runs: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """The shift in ns, nearest 0, that moves the chip's `busy` intervals
    (on its own clock) inside the host's `runs` in the window [lo, hi]
    (the harness opens and closes it between steps), by less than half
    the shortest run, so that no step's work lands in another's; 0 when
    there is none."""
    if not busy or not runs:
        return 0
    quiet = subtract([(lo, hi)], union(runs))
    limit = min(b - a for a, b in runs) / 2
    inf = float("inf")
    gaps = [(-inf, busy[0][0])] + [(busy[k][1], busy[k + 1][0])
                                   for k in range(len(busy) - 1)] \
        + [(busy[-1][1], inf)]
    ok: List[Tuple[float, float]] = [(-limit, limit)]
    for qa, qb in quiet:
        # quiet fits in gap g when g.a + shift <= qa and qb <= g.b + shift
        fits = union((qb - gb, qa - ga) for ga, gb in gaps
                     if gb - ga >= qb - qa)
        ok = subtract(ok, subtract(ok, fits))
        if not ok:
            return 0
    a, b = min(ok, key=lambda r: max(r[0], 0, -r[1]))
    return int(min(max(0, a), b))


def wait_ms(ctx, names: Iterable[str]) -> Optional[float]:
    """Device idle ms per step of the window that overlaps the host spans
    of these names, mean over chips, after each chip's clock shift; None
    when the run was not traced or the trace holds no such span."""
    if ctx.trace is None or not ctx.info.steps:
        return None
    red, names = ctx.trace, set(names)
    spans = union((a, b) for n, a, b, _ in red.host if n in names)
    if not spans:
        return None
    runs = _steps_run(red)
    total = 0
    for busy in red.busy.values():
        d = clock_shift(busy, runs, red.lo, red.hi)
        moved = union((max(a + d, red.lo), min(b + d, red.hi))
                      for a, b in busy)
        idle = subtract([(red.lo, red.hi)], moved)
        total += measure(idle) - measure(subtract(idle, spans))
    return 1e3 * total / 1e9 / len(red.busy) / ctx.info.steps
