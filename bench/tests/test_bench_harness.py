"""The harness's path of one run, end to end on the CPU at the family's
CPU-scale widths (`reduced()`) with a short sequence: the run's result line,
`correct` on a sound run, and `correct` false with the timed path broken
underneath it. Also: the command exits non-zero, printing no result, where
JAX finds no TPU and where the program's sources are missing."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchkit import harness, spec  # noqa: E402

CELL = "bert_large.adama"
SEED = 2**33 + 77             # wider than 32 bits: all 64 are used


@pytest.fixture
def small_cell(monkeypatch):
    """The cell as committed, with 64-token sequences."""
    orig = spec.load_cell

    def load(name, root=spec.ROOT):
        c = orig(name, root)
        return dataclasses.replace(c, traffic=dict(c.traffic, seq_len=64))
    monkeypatch.setattr(spec, "load_cell", load)


def _run(seconds=0.5):
    return harness.run_cell(CELL, SEED, seconds, False,
                            t_proc=time.perf_counter(), reduced=True,
                            require_tpu=False)


def _break_step(monkeypatch, fault):
    """Wrap the engine's step so that the timed path carries `fault`."""
    from repro.train import loop
    make = loop.make_train_step

    def broken(*a, **kw):
        step, init = make(*a, **kw)

        def state_unchanged(params, state, batch):
            _, _, metrics = step(params, state, batch)
            return params, state, metrics

        def half_batch(params, state, batch):
            half = batch["labels"].shape[0] // 2
            labels = batch["labels"].at[half:].set(-1)
            return step(params, state, dict(batch, labels=labels))

        return {"state_unchanged": state_unchanged,
                "half_batch": half_batch}[fault], init
    monkeypatch.setattr(loop, "make_train_step", broken)


def test_one_run_end_to_end_on_cpu(small_cell):
    r = _run()
    assert list(r)[:3] == ["correct", "attempted", "failed"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "step_hbm_gb",
                                 "setup_s"}
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert r["notes"]["window_compiles"] == 0
    assert set(r["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    json.loads(json.dumps(r))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(small_cell, monkeypatch, fault):
    _break_step(monkeypatch, fault)
    r = _run()
    assert r["correct"] is False, r["checks"]


def _command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_tpu():
    p = _command(ROOT, {"PYTHONPATH": str(ROOT / "src")})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
