"""The reduction of a trace to the step's phases and the host's waits, on
hand-written HLO and hand traces with answers worked by hand, and on a
slice recorded on the chip."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit import scopes, trace  # noqa: E402

DATA = BENCH / "tests" / "data"
W = "jit(step)/while/body/closed_call"

HLO = f"""HloModule jit_step, entry_computation_layout={{(f32[8]{{0}})->f32[8]{{0}}}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  ROOT %multiply.3 = f32[8]{{0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{W}/jvp(model)/mul"}}
}}

ENTRY %main.9 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{W}/jvp(model)/mul" stack_frame_id=3}}
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{W}/transpose(jvp(model))/while/body/closed_call/checkpoint/rematted_computation/dot_general"}}
  %concatenate.4 = f32[16]{{0}} concatenate(%fusion.2, %fusion.2), dimensions={{0}}, metadata={{op_name="{W}/optimizer.grad_pack/concatenate"}}
  %arena_fold.5 = f32[8]{{0}} custom-call(%concatenate.4), custom_call_target="tpu_custom_call", metadata={{op_name="{W}/optimizer.fold/pallas_call"}}
  %fusion.6 = f32[8]{{0}} fusion(%arena_fold.5), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{W}/transpose(jvp(model))/while/body/optimizer.fold/add"}}
  %reshape_reshape.7 = f32[8]{{0}} reshape(%fusion.6), metadata={{op_name="jit(step)/optimizer.apply/reshape;jit(step)/optimizer.apply/reshape"}}
  %add.8 = f32[8]{{0}} add(%p, %p), metadata={{op_name="jit(step)/while/body/add"}}
  %copy-start.9 = (f32[8]{{0}}, f32[8]{{0}}, u32[]) copy-start(%add.8)
  ROOT %arena_apply.10 = f32[8]{{0}} custom-call(%reshape_reshape.7), custom_call_target="tpu_custom_call", metadata={{op_name="jit(step)/optimizer.apply/pallas_call"}}
}}
"""


def test_op_names_reads_every_instruction_with_metadata():
    names = scopes.op_names(HLO)
    assert names["fusion.1"] == f"{W}/jvp(model)/mul"
    assert names["multiply.3"] == f"{W}/jvp(model)/mul"
    assert names["arena_apply.10"] == "jit(step)/optimizer.apply/pallas_call"
    assert "copy-start.9" not in names and "p" not in names


@pytest.mark.parametrize("inst,phase", [
    ("fusion.1", "forward"),              # a fusion, named by its root
    ("fusion.2", "backward"),             # remat recompute in the backward
    ("concatenate.4", "grad_pack"),
    ("arena_fold.5", "optimizer"),
    ("fusion.6", "optimizer"),            # a fold nested in transpose(
    ("reshape_reshape.7", "optimizer"),   # merged op_names: the first
    ("add.8", None),                      # loop control
    ("arena_apply.10", "optimizer"),
])
def test_classify(inst, phase):
    assert scopes.classify(scopes.op_names(HLO)[inst]) == phase


def test_classify_model_scope_spellings():
    assert scopes.classify("jit(step)/model/while/body/dot") == "forward"
    assert scopes.classify(
        "jit(step)/model/while/body/transpose(jvp())/dot") == "backward"
    assert scopes.classify(
        f"{W}/jvp(model)/while/body/rematted_computation/tanh") == "backward"
    assert scopes.classify(f"{W}/jvp(model_x)/dot") is None
    # the layer-wise engine's recompute inside its backward, both ways
    assert scopes.classify(
        f"{W}/while/body/jvp(model.recompute)/dot") == "backward"
    assert scopes.classify(
        f"{W}/while/body/transpose(jvp(model.recompute))/dot") == "backward"
    assert scopes.classify("") is None


def _hand():
    # window [0, 1000]; chip 0 busy [0, 590] then idle; 1 step
    ops = [["fusion.1", 0, 100, "fusion"], ["fusion.2", 100, 300, "fusion"],
           ["concatenate.4", 300, 350, "concatenate"],
           ["arena_fold.5", 350, 450, "custom-call"],
           ["fusion.6", 450, 470, "fusion"],
           ["reshape_reshape.7", 470, 480, "reshape"],
           ["add.8", 480, 490, "add"],
           ["arena_apply.10", 490, 590, "custom-call"]]
    red = trace.from_events({"devices": {"0": ops},
                             "host": [["bench.window", 0, 1000, "python3"]]},
                            chips=1)
    return SimpleNamespace(trace=red, info=SimpleNamespace(
        steps=1, compiled=SimpleNamespace(as_text=lambda: HLO)))


def test_device_ms_by_phase():
    ms = scopes.device_ms(_hand())
    assert ms == pytest.approx({"forward": 100e-6, "backward": 200e-6,
                                "grad_pack": 50e-6,
                                "optimizer": 230e-6})


def test_device_ms_without_a_trace_or_names():
    ctx = _hand()
    ctx.info.compiled = SimpleNamespace(as_text=lambda: "")
    assert scopes.device_ms(ctx) == {}
    assert scopes.device_ms(SimpleNamespace(trace=None, info=ctx.info)) == {}


# Two steps of a synchronous loop in the window [0, 1000] (ns), opened
# and closed between steps as the harness does; bench.batch nests in
# train.batch.
LOOP = [["train.step", 0, 480, "python3"],
        ["train.batch", 0, 50, "python3"],
        ["bench.batch", 5, 45, "python3"],
        ["train.dispatch", 50, 90, "python3"],
        ["train.sync", 90, 430, "python3"],
        ["train.log", 430, 480, "python3"],
        ["train.step", 480, 1000, "python3"],
        ["train.batch", 480, 530, "python3"],
        ["train.dispatch", 530, 570, "python3"],
        ["train.sync", 570, 960, "python3"],
        ["train.log", 960, 1000, "python3"]]


def _waits(host, devices):
    red = trace.from_events({
        "devices": {str(c): [["fusion.1", a, b, "fusion"] for a, b in busy]
                    for c, busy in enumerate(devices)},
        "host": [["bench.window", 0, 1000, "python3"]] + host},
        chips=len(devices))
    return SimpleNamespace(trace=red, info=SimpleNamespace(steps=2))


def test_waits_share_one_gap_among_several_spans():
    # chip 0 is idle over [400, 550], under sync, log, the next step's
    # batch and dispatch in turn; both chips' clocks agree with the host's
    ctx = _waits(LOOP, [[(60, 400), (550, 900)], [(80, 420), (560, 950)]])
    for busy in ctx.trace.busy.values():
        assert scopes.clock_shift(busy, scopes._steps_run(ctx.trace),
                                  0, 1000) == 0
    # chip 0: batch 50 + 50; loop 10 + 30 + 50 + 20 + 60 + 40 = 210 ns
    # chip 1: batch 50 + 50; loop 30 + 10 + 50 + 30 + 10 + 40 = 170 ns
    assert scopes.wait_ms(ctx, scopes.INPUT_SPANS) == pytest.approx(
        (100 + 100) / 2 / 2 / 1e6)
    assert scopes.wait_ms(ctx, scopes.LOOP_SPANS) == pytest.approx(
        (210 + 170) / 2 / 2 / 1e6)
    # with no gap left out, the two waits are the whole idle time
    idle_ms = (1 - ctx.trace.busy_s / ctx.trace.window_s) * 1000e-6 / 2
    assert scopes.wait_ms(ctx, scopes.INPUT_SPANS) + \
        scopes.wait_ms(ctx, scopes.LOOP_SPANS) == pytest.approx(idle_ms)


def test_waits_shift_a_chip_clock_that_runs_behind():
    # the chip's clock reads 40 ns behind the host's: its first operation
    # seems to start 30 ns before the step was dispatched. The smallest
    # shift that makes it causal is 30; the device is then idle under the
    # whole of each train.batch, as it must be in a synchronous loop.
    ctx = _waits(LOOP, [[(20, 360), (510, 860)]])
    red = ctx.trace
    assert scopes.clock_shift(red.busy[0], scopes._steps_run(red),
                              0, 1000) == 30
    batch_ms = 1e3 * sum(red.host_spans("train.batch")) / 2
    assert scopes.wait_ms(ctx, scopes.INPUT_SPANS) == pytest.approx(
        batch_ms) == pytest.approx(50 / 1e6)
    # loop: 0 + 40 + 50 + 10 + 70 + 40 = 210 ns over 2 steps
    assert scopes.wait_ms(ctx, scopes.LOOP_SPANS) == pytest.approx(
        210 / 2 / 1e6)


def test_clock_shift_gives_up_on_a_loop_that_is_not_synchronous():
    # a step in flight when the window opens, and work after the loss
    # read: no shift under half a step fits, so the clock stays
    red = _waits(LOOP, [[(0, 300), (500, 990)]]).trace
    assert scopes.clock_shift(red.busy[0], scopes._steps_run(red),
                              0, 1000) == 0
    assert scopes.clock_shift([], [(50, 430)], 0, 1000) == 0
    assert scopes.clock_shift([(60, 400)], [], 0, 1000) == 0


def test_waits_without_the_spans():
    ctx = _waits([["bench.batch", 420, 470, "python3"]],
                 [[(60, 400), (550, 900)]])
    assert scopes.wait_ms(ctx, scopes.INPUT_SPANS) is None
    assert scopes.wait_ms(ctx, scopes.LOOP_SPANS) is None


def test_recorded_v5e_step_by_phase():
    """One whole step of bert_large.adama recorded on a v5e, with the
    op_names of its operations from the compiled step: the phases cover
    nearly all of the busy time, the fold kernel runs once per
    micro-batch under `optimizer.fold`, and the two waits share out the
    idle time between the loss read and the next dispatch. The chip's
    clock runs behind the host's there (its first operation starts 0.71
    ms before `train.dispatch`); once shifted, the device is idle under
    the whole `train.batch`, as the synchronous loop requires."""
    rec = json.loads((DATA / "trace_v5e_scoped.json").read_text())
    red = trace.from_events(rec, chips=1)
    ctx = SimpleNamespace(trace=red, info=SimpleNamespace(
        steps=1, compiled=SimpleNamespace(as_text=lambda: rec["hlo"])))
    ms = scopes.device_ms(ctx)
    assert ms == pytest.approx({"forward": 79.704828, "backward": 99.093075,
                                "grad_pack": 89.553833,
                                "optimizer": 117.590045})
    busy_ms = 1e3 * red.busy_s
    assert busy_ms == pytest.approx(395.238631)
    assert sum(ms.values()) >= 0.95 * busy_ms
    names = scopes.op_names(rec["hlo"])
    assert scopes.classify(names["arena_fold.9"]) == "optimizer"
    assert red.op_counts(0)["arena_fold.9"] == 8
    assert scopes.classify(names["concatenate.87"]) == "grad_pack"
    assert scopes.classify(names["arena_apply.1"]) == "optimizer"
    idle_ms = 1e3 * (red.window_s - red.busy_s)
    inp = scopes.wait_ms(ctx, scopes.INPUT_SPANS)
    loop = scopes.wait_ms(ctx, scopes.LOOP_SPANS)
    assert scopes.clock_shift(red.busy[0], scopes._steps_run(red),
                              red.lo, red.hi) == 712891
    assert inp >= 1e3 * sum(red.host_spans("train.batch"))
    assert inp == pytest.approx(1.53704)
    assert loop == pytest.approx(1.824291)
    assert idle_ms == pytest.approx(3.38666)
    assert 0.99 * idle_ms <= inp + loop <= idle_ms
