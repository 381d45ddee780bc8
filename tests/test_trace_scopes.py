"""The training step's phases carry their names into the compiled program,
and the loop's phases into a profiler trace.

Device side: each engine's step is compiled at reduced widths on the CPU
and every instruction's `metadata={op_name=...}` is read back. The names
the program declares (configs/base.py) must appear there, and the
benchmark's classifier (bench/benchkit/scopes.py, which keeps its own
copy of the strings) must find each phase the engine runs. Host side: two
steps of `train()` under `jax.profiler.trace` must leave the loop's spans
on the host plane.
"""
import glob
import sys
from pathlib import Path

import jax
import pytest

from conftest import batch_for, tiny
from repro.configs import OptimizerConfig, RunConfig
from repro.configs.base import (ACCUMULATE_SCOPE, APPLY_SCOPE, FOLD_SCOPE,
                                GRAD_PACK_SCOPE, MODEL_SCOPE,
                                RECOMPUTE_SCOPE, InputShape)
from repro.core.accumulation import make_train_step
from repro.models.model import init_params

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from benchkit import scopes  # noqa: E402

ARENA = dict(use_pallas=True, arena=True)
# step -> (OptimizerConfig fields, remat, scope names its HLO must carry)
STEPS = {
    "adama_arena_fp32": (dict(accumulation="adama", **ARENA), False,
                         {MODEL_SCOPE, GRAD_PACK_SCOPE, FOLD_SCOPE,
                          APPLY_SCOPE}),
    "adama_arena_int8_bf16_wire": (
        dict(accumulation="adama", state_codec="int8", m_codec="int8",
             grad_dtype="bf16", **ARENA), True,
        {MODEL_SCOPE, GRAD_PACK_SCOPE, FOLD_SCOPE, APPLY_SCOPE}),
    "ga_per_leaf": (dict(accumulation="ga"), False,
                    {MODEL_SCOPE, ACCUMULATE_SCOPE, APPLY_SCOPE}),
    "layerwise_arena": (dict(accumulation="adama_layerwise", **ARENA), False,
                        {MODEL_SCOPE, RECOMPUTE_SCOPE, GRAD_PACK_SCOPE,
                         FOLD_SCOPE, APPLY_SCOPE}),
}


def _compiled_op_names(fields, remat):
    cfg = tiny("bert_large")
    opt = OptimizerConfig(micro_batches=2, **fields)
    step, opt_init = make_train_step(cfg, opt, remat=remat)
    params = init_params(cfg, jax.random.key(0))
    batch = batch_for(cfg, 4, 16)
    hlo = jax.jit(step).lower(params, opt_init(params), batch).compile()
    return scopes.op_names(hlo.as_text())


def test_every_declared_scope_is_exercised():
    declared = {MODEL_SCOPE, RECOMPUTE_SCOPE, GRAD_PACK_SCOPE, FOLD_SCOPE,
                ACCUMULATE_SCOPE, APPLY_SCOPE}
    assert set().union(*(s for _, _, s in STEPS.values())) == declared
    # the benchmark reads the same strings the program writes
    assert scopes.MODEL == MODEL_SCOPE
    assert scopes.RECOMPUTE == RECOMPUTE_SCOPE
    assert scopes.GRAD_PACK == GRAD_PACK_SCOPE
    assert set(scopes.OPTIMIZER) == {FOLD_SCOPE, ACCUMULATE_SCOPE,
                                     APPLY_SCOPE}


@pytest.mark.parametrize("name", sorted(STEPS))
def test_step_hlo_names_its_phases(name):
    fields, remat, want = STEPS[name]
    names = _compiled_op_names(fields, remat)
    parts = {p for o in names.values() for p in o.split("/")}
    for scope in want:
        assert any(scope in p for p in parts), (name, scope)
    phases = {scopes.classify(o) for o in names.values()}
    expect = {"forward", "backward", "optimizer"}
    if GRAD_PACK_SCOPE in want:
        expect.add("grad_pack")
    else:
        assert "grad_pack" not in phases
    assert expect <= phases, (name, phases)


def test_train_loop_writes_host_spans(tmp_path):
    from repro.train.loop import train
    cfg = tiny("bert_large")
    run = RunConfig(model=cfg,
                    optimizer=OptimizerConfig(accumulation="adama",
                                              micro_batches=2),
                    shape=InputShape("t", 16, 4, "train"), steps=2,
                    log_every=1, checkpoint_dir=str(tmp_path / "ck"),
                    checkpoint_every=1)
    with jax.profiler.trace(str(tmp_path / "trace")):
        train(run, log_fn=lambda *_: None)
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = [e for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events]
    names = [e.name for e in host]
    for span in ("train.init", "train.compile", "train.batch",
                 "train.dispatch", "train.sync", "train.log",
                 "train.checkpoint"):
        assert span in names, span
    steps = [dict(e.stats)["step_num"] for e in host
             if e.name == "train.step"]
    assert steps == [1, 2]
