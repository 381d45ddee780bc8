"""One run of one training cell.

Set-up builds the weights from the seed on the device (one jitted call) and
hands them, with the cell's RunConfig and the benchmark's token stream, to
`repro.train.loop.train`, the function `launch/train.py` calls. That one
call compiles the step, builds the optimizer state and runs every step of
the run:

  steps 1-3  the checked steps. After each, the harness reads the loss the
             loop recorded; after step 1 the optimizer state, after step 3
             the parameters step 4 will receive (it reads them from the
             loop's frame, through `log_fn`, before the next step donates
             them).
  step 4     the last step of set-up.
  steps 5..  the measured window: it closes at the first step that ends
             `seconds` or more after step 4 ended, and the harness stops
             the loop there by raising from `log_fn`.

The loop logs every step (`log_every=1`) and reads each step's loss
already, so the harness adds no synchronisation inside the window. Once
the window has closed and the device memory peak has been read, the loop's
state is freed and the float32 reference follows the same three steps from
the same weights and batches (bench/benchkit/check.py says what is
compared).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import math
import re
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchkit import check, flops, peaks, spec
from benchkit.data import make_stream
from benchkit.weights import make_init, program_shapes, seed_key

CHECKED_STEPS = 3
WINDOW_FROM = 4                 # the window starts when this step ends
STEP_RE = re.compile(r"\[train\] step (\d+)/\d+ loss=(\S+)")
TRACE_DIR = spec.ROOT / ".bench_trace"


class WindowClosed(Exception):
    """Raised from the loop's log_fn to end the run when the window has
    closed."""


class NoAccelerator(Exception):
    pass


@dataclasses.dataclass
class RunInfo:
    """What one run observed, before the metrics are derived from it."""
    steps: int = 0                       # steps inside the window
    window_s: float = 0.0
    setup_s: float = 0.0
    capture_s: float = 0.0
    compile_s: float = float("nan")
    window_compiles: int = 0
    losses: List[float] = dataclasses.field(default_factory=list)
    window_losses: List[float] = dataclasses.field(default_factory=list)
    m_sumsq: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    change_sumsq: Dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)
    arena_rows: int = 0                  # whole arena, 0 without one
    state_shards: int = 1
    compiled: Any = None
    trace_path: Optional[str] = None


def _loop_frame():
    from repro.train import loop
    f = sys._getframe(2)
    while f is not None and f.f_code is not loop.train.__code__:
        f = f.f_back
    if f is None:
        raise RuntimeError("log_fn was not called from "
                           "repro.train.loop.train")
    return f


def _moment_row_sumsq(moment):
    """Per-row sums of squares of an arena-backed moment (decoded)."""
    data = moment.decode() if hasattr(moment, "decode") else moment.data
    return jnp.sum(jnp.square(data.astype(jnp.float32)), axis=1)


class _Counter:
    """Counts backend compilations (to prove none falls in the window)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


class Observer:
    """The loop's log_fn: reads the checked steps, opens and closes the
    window, and starts and stops the profiler around it."""

    def __init__(self, *, seconds: float, t_proc: float, leaves, key,
                 n_layers: int, trace: bool, counter: _Counter):
        self.seconds = seconds
        self.t_proc = t_proc
        self.leaves = leaves
        self.key = key
        self.n_layers = n_layers
        self.trace = trace
        self.counter = counter
        self.info = RunInfo()
        self.t_start = None
        self.compiles_at_start = 0
        self._span = None

    def __call__(self, msg: str) -> None:
        m = STEP_RE.match(msg)
        if not m:
            return
        k = int(m.group(1))
        now = time.perf_counter()
        if k <= CHECKED_STEPS:
            self._capture(k, _loop_frame())
            self.info.capture_s += time.perf_counter() - now
        elif k == WINDOW_FROM:
            self._open(now)
        elif k > WINDOW_FROM:
            self.info.window_losses.append(float(m.group(2)))
            if now - self.t_start >= self.seconds:
                self._close(now, k)

    def _capture(self, k: int, frame) -> None:
        loc = frame.f_locals
        info = self.info
        info.losses.append(float(loc["losses"][-1]))
        if k == 1:
            info.compiled = loc["compiled"]
            info.compile_s = float(loc["compile_s"])
            info.state_shards = int(loc["state_shards"])
            m = loc["opt_state"]["m"]
            if hasattr(m, "layout"):
                info.arena_rows = int(m.layout.rows)
                rows = np.asarray(jax.device_get(
                    jax.jit(_moment_row_sumsq)(m)), np.float64)
                info.m_sumsq = check.arena_sumsq(rows, m.layout, self.leaves)
            else:
                info.m_sumsq = check.tree_sumsq(m, self.leaves)
        if k == CHECKED_STEPS:
            info.change_sumsq = check.change_sumsq(
                loc["params"], self.key, self.leaves, self.n_layers)

    def _open(self, now: float) -> None:
        self.info.setup_s = now - self.t_proc - self.info.capture_s
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        self.compiles_at_start = self.counter.n
        self.t_start = time.perf_counter()

    def _close(self, now: float, k: int) -> None:
        info = self.info
        info.steps = k - WINDOW_FROM
        info.window_s = now - self.t_start
        info.window_compiles = self.counter.n - self.compiles_at_start
        if self._span is not None:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            found = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
            info.trace_path = str(found[-1]) if found else None
        raise WindowClosed


class TracedStream:
    """The token stream as the loop sees it: each batch() inside a
    `bench.batch` profiler span."""

    def __init__(self, stream):
        self.stream = stream

    def batch(self, index: int):
        with jax.profiler.TraceAnnotation("bench.batch"):
            return self.stream.batch(index)


def require_devices(chips: int) -> List[Any]:
    """The cell's chips, or NoAccelerator: a run never falls back to the
    CPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def use_cache() -> str:
    """The persistent compilation cache, at a fixed path inside the
    checkout, for every compilation (the eager set-up ops included). The
    entry points call it; it changes JAX's configuration for the whole
    process."""
    path = spec.ROOT / ".jax_cache"
    path.mkdir(exist_ok=True)
    path = str(path)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit: with one, JAX keeps an access-time file beside each
    # entry, and a missing one fails every later write to the directory
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def _load_module(path: Path):
    s = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def make_weights(shapes, model, seed: int, devices):
    """The run's weights, made on the device in one jitted call; on
    several chips replicated, as the ZeRO-1 loop places them."""
    init, leaves = make_init(shapes, model.num_layers)
    out_sh = None
    if len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(devices).reshape(len(devices), 1),
                    ("data", "model"))
        out_sh = NamedSharding(mesh, P())
    params = jax.jit(init, out_shardings=out_sh)(seed_key(seed))
    return jax.block_until_ready(params), leaves


def reference_readings(cell, run, seed: int, stream, leaves, *,
                       mode: str = "fp32", batch_filter=None):
    """Follow the first three steps with the reference. Returns (losses,
    first-moment sums of squares after step 1, change sums of squares
    after step 3) per (leaf, layer). `mode` and `batch_filter` put the
    reference in the program's place for the control and its faults
    (bench/control.py). On a cell of several chips the reference's weights,
    moments, gradients and micro-batches are spread over them
    (`reference.optim.Spread`), made there from the seed; on one chip it
    runs on the first device, as the program's weights do."""
    family = importlib.import_module(f"reference.{cell.config['reference']}")
    from reference import optim as ref_optim
    model = run.model
    hp = cell.config["optimizer"]
    state = optimizer_state(cell)
    shapes = program_shapes(model)
    init, _ = make_init(shapes, model.num_layers)
    key = seed_key(seed)
    devices = jax.devices()[:cell.chips]
    spread = ref_optim.Spread(devices) if len(devices) > 1 else None
    if spread is None:
        weights = jax.jit(init)(key)
    else:
        where = jax.tree.unflatten(jax.tree.structure(shapes), [
            spread.leaf(lf.shape, lf.stacked) for lf in leaves])
        weights = jax.jit(init, out_shardings=where)(key)
    batches = [jax.tree.map(jnp.asarray, stream.batch(i))
               for i in range(CHECKED_STEPS)]
    out = {}

    def on_step(s, w, m, v, loss):
        if s == 0:
            out["m"] = check.tree_sumsq(m, leaves)
        if s == len(batches) - 1:
            out["change"] = check.change_sumsq(w, key, leaves,
                                               model.num_layers)

    def loss_fn(w, mb):
        return family.loss(w, mb, cell.config["model"], mode)

    with jax.default_matmul_precision("highest"):
        losses = ref_optim.run_steps(
            loss_fn, weights, batches,
            n_micro=cell.traffic["micro_batches"],
            engine=cell.traffic["reference_engine"], lr=hp["lr"],
            beta1=hp["beta1"], beta2=hp["beta2"], eps=hp["eps"],
            on_step=on_step, batch_filter=batch_filter,
            leaf_groups=cell.traffic.get("reference_leaf_groups", 1),
            stacked=[lf.stacked for lf in leaves],
            m_codec=state["m_codec"], v_codec=state["state_codec"],
            wire=state["grad_dtype"], spread=spread)
    return losses, out["m"], out["change"]


def compare(prog, ref) -> Dict[str, float]:
    """The numbers compared, from (losses, first-moment sums of squares,
    change sums of squares) of the run and of the reference."""
    losses, m_sq, change_sq = prog
    ref_losses, ref_m, ref_change = ref
    grad_gap, grad_leaf = check.norm_gap(m_sq, ref_m)
    keep = check.moving(ref_m)
    change_gap, change_leaf = check.norm_gap(change_sq, ref_change,
                                             keep=keep)
    return {"loss_gap": check.loss_gap(losses, ref_losses),
            "grad_gap": grad_gap, "change_gap": change_gap,
            "_grad_leaf": grad_leaf, "_change_leaf": change_leaf,
            "_left_out": sorted(set(check.flat(ref_m)) - keep)}


def step_hbm_bytes(compiled) -> int:
    """Arguments + temporaries + outputs not aliased to an argument, of the
    compiled step, per chip (the compiler's own budget)."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def memory_peak(devices) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_))


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: Any
    model: Any
    run: Any
    info: RunInfo
    peaks: Dict[str, Any]
    chips: int
    tokens_per_s: float
    trace: Any                            # benchkit.trace.Reduced or None
    flops: Any = flops


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_proc: float, reduced: bool = False,
             require_tpu: bool = True) -> Dict[str, Any]:
    """Run one cell; returns the result line's object. `reduced` and
    `require_tpu=False` are for the CPU tests of the harness only."""
    cell = spec.load_cell(cell_name)
    if require_tpu:
        devices = require_devices(cell.chips)
        chip_peaks = peaks.peaks_for(devices[0].device_kind)
    else:
        devices = jax.devices()[:cell.chips]
        chip_peaks = peaks.PEAKS["TPU v5 lite"]
    counter = _Counter()
    run = spec.build_run(cell, seed, reduced=reduced)
    run = dataclasses.replace(run, steps=10**9, log_every=1,
                              checkpoint_dir=None)
    model = run.model
    check_optimizer(cell, run)
    shapes = program_shapes(model)
    params, leaves = make_weights(shapes, model, seed, devices)
    stream = make_stream(cell, model, seed)
    obs = Observer(seconds=seconds, t_proc=t_proc, leaves=leaves,
                   key=seed_key(seed), n_layers=model.num_layers,
                   trace=trace, counter=counter)
    from repro.train.loop import train
    try:
        train(run, lr_schedule=None, log_fn=obs, params=params,
              data=TracedStream(stream))
        raise RuntimeError("the loop ended before the window closed")
    except WindowClosed:
        pass
    finally:
        counter.close()
    del params
    info = obs.info
    peak = memory_peak(devices)
    hbm = step_hbm_bytes(info.compiled)
    gc.collect()
    held = max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in devices)
    t_ref = time.perf_counter()
    ref = reference_readings(cell, run, seed, stream, leaves)
    ref_s = time.perf_counter() - t_ref
    numbers = compare((info.losses, info.m_sumsq, info.change_sumsq), ref)
    correct = check.verdict(numbers, cell.limits)
    tokens_per_s = info.steps * cell.tokens_per_step / info.window_s
    failed = sum(1 for x in info.window_losses if not math.isfinite(x))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": info.steps, "failed": failed}
    if trace:
        from benchkit import trace as trace_mod
        reduced_trace = trace_mod.reduce_file(info.trace_path, len(devices))
        device["busy_s"] = reduced_trace.busy_s
        device["window_s"] = reduced_trace.window_s
        ctx = Context(cell=cell, model=model, run=run, info=info,
                      peaks=chip_peaks, chips=len(devices),
                      tokens_per_s=tokens_per_s, trace=reduced_trace)
        result["metrics"] = read_per_layer(cell, ctx)
        result["breakdown"] = reduced_trace.breakdown()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        values = {"train_tokens_per_s": tokens_per_s,
                  "step_hbm_gb": hbm / 1e9, "setup_s": info.setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.metrics_for(trace=False)}
    result["device"] = device
    result["notes"] = {"window_compiles": info.window_compiles,
                       "losses": info.losses,
                       "grad_leaf": numbers["_grad_leaf"],
                       "change_leaf": numbers["_change_leaf"],
                       "left_out": numbers["_left_out"],
                       "compile_s": info.compile_s,
                       "step_hbm_bytes": hbm,
                       "window_s": info.window_s,
                       "setup_s": info.setup_s, "reference_s": ref_s,
                       "held_before_reference_bytes": held}
    # the numbers compared, each beside its limit: the line's last key
    result["checks"] = {k: {"value": numbers[k],
                            "limit": cell.limits[k]["limit"]}
                        for k in check.NUMBERS}
    return result


def optimizer_state(cell) -> Dict[str, str]:
    """How the cell keeps its optimizer state (the traffic mix's
    `optimizer_state` block, OptimizerConfig field names): m and v codecs
    and the gradient wire."""
    return dict({"m_codec": "fp32", "state_codec": "fp32",
                 "grad_dtype": "fp32"}, **cell.traffic.get("optimizer_state",
                                                           {}))


def check_optimizer(cell, run) -> None:
    """The reference's hyper-parameters (the configuration file's
    `optimizer` block) and state precision (the traffic mix's
    `optimizer_state`) are the ones the program runs with."""
    opt = run.optimizer
    for key, want in dict(cell.config["optimizer"],
                          **optimizer_state(cell)).items():
        if getattr(opt, key) != want:
            raise spec.SpecError(f"{cell.config_name}: optimizer {key}="
                                 f"{getattr(opt, key)!r} in the program, "
                                 f"{want!r} in the configuration file")


def read_per_layer(cell, ctx) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of the cell, from its own reader
    (bench/metrics/<name>.py, `read(ctx)`); a reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for m in cell.metrics_for(trace=True):
        mod = _load_module(spec.BENCH_DIR / "metrics" / f"{m['name']}.py")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
