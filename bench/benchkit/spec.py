"""Cells, configurations, traffic mixes and limits, found by name.

BENCHMARK.json (at the checkout's root) names each cell with its
configuration and traffic mix. A configuration is `bench/configs/<name>.json`
(the `file` of its BENCHMARK.json entry), a traffic mix is
`bench/traffic/<name>.json`, and the limits that decide a cell's `correct`
are `bench/limits/<cell>.json`. Adding a cell adds files; none is edited.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A cell, configuration, traffic mix or limit that cannot be found or
    does not hold together."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]          # bench/configs/<config>.json
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    limits: Dict[str, Any]          # bench/limits/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def train_argv(self) -> List[str]:
        """`launch/train.py` arguments: the configuration's, then the
        traffic mix's (batch shape and engine flags)."""
        t = self.traffic
        return (list(self.config["train_args"]) + list(t["train_args"])
                + ["--seq-len", str(t["seq_len"]),
                   "--global-batch", str(t["global_batch"]),
                   "--micro-batches", str(t["micro_batches"])])

    @property
    def tokens_per_step(self) -> int:
        return int(self.traffic["seq_len"]) * int(self.traffic["global_batch"])

    def metrics_for(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports: end-to-end ones with trace off,
        per-layer ones with it on; a metric with a `workloads` list only in
        the cells it names."""
        pool = self.per_layer if trace else self.end_to_end
        return [m for m in pool
                if "workloads" not in m or self.name in m["workloads"]]


def _load_json(path: Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {path.relative_to(ROOT)}") from None


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _load_json(root / "BENCHMARK.json")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(BENCH_DIR / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                limits=limits, end_to_end=list(bench["end_to_end"]),
                per_layer=list(bench["per_layer"]))


def build_run(cell: Cell, seed: int, *, reduced: bool = False):
    """The RunConfig `launch/train.py::build_run` makes from the cell's
    arguments, with the configuration's and traffic mix's overrides
    (`model_overrides`, `run_overrides`) applied, and the ModelConfig. A
    nested override (`"moe": {"n_experts": 8}`) replaces those keys of the
    nested block. `reduced` runs the same cell at the CPU-scale widths of the family
    (tests only)."""
    from repro.launch.train import build_run as program_build_run
    from repro.launch.train import parse_args
    argv = cell.train_argv + ["--seed", str(seed % 2**31)]
    if reduced:
        argv.append("--reduced")
    run, _ = program_build_run(parse_args(argv))
    model = run.model
    over = dict(cell.config.get("model_overrides", {}))
    for key, value in over.items():
        if isinstance(value, dict):        # a nested block, such as `moe`
            over[key] = dataclasses.replace(getattr(model, key), **value)
    if over:
        model = dataclasses.replace(model, **over)
    run = dataclasses.replace(run, model=model,
                              **cell.traffic.get("run_overrides", {}))
    if not reduced:
        check_config(cell, model)
    return run


def check_config(cell: Cell, model) -> None:
    """The configuration file holds the sizes as run: every number in its
    `model` block must equal the program's ModelConfig, and every number
    in a nested block (such as `moe`) the field of the nested config."""
    _check_block(cell.config_name, cell.config["model"], model, "")


def _check_block(config_name: str, block: Dict[str, Any], obj,
                 prefix: str) -> None:
    for key, want in block.items():
        if not hasattr(obj, key):
            raise SpecError(f"{config_name}: the program has no setting "
                            f"{prefix}{key}")
        got = getattr(obj, key)
        if isinstance(want, dict):
            if got is None:
                raise SpecError(f"{config_name}: {prefix}{key} is None in "
                                f"the program, a block in the "
                                f"configuration file")
            _check_block(config_name, want, got, f"{prefix}{key}.")
        elif got != want:
            raise SpecError(f"{config_name}: {prefix}{key}={got!r} in the "
                            f"program, {want!r} in the configuration file")
