"""The reduction from trace to numbers, on recorded traces with answers
worked by hand."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit import trace  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.fixture
def hand():
    return trace.from_events(json.loads((DATA / "trace_hand.json")
                                        .read_text()), chips=2)


def test_union_and_subtract():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.measure([(0, 2), (3, 5)]) == 4


def test_window_and_busy(hand):
    # window [50, 1000]; chip 0 busy [100,600] + [700,800] + [900,950]
    # = 650 ns (fusion.6 lies outside), chip 1 [120,320] + [400,600] = 400
    assert hand.window_s == pytest.approx(950e-9)
    assert hand.busy_s == pytest.approx(525e-9)
    assert hand.idle_share() == pytest.approx(1 - 525 / 950)


def test_kernel_time_and_counts(hand):
    assert hand.op_seconds(0)["custom-call.7"] == pytest.approx(150e-9)
    assert hand.op_counts(0)["fusion.1"] == 1
    assert "fusion.6" not in hand.op_counts(0)


def test_self_time_leaves_out_nested_operations(hand):
    # while.9 [100,600] holds fusion.1, custom-call.7 and fusion.2 whole
    assert hand.op_seconds(0)["while.9"] == pytest.approx(0.0)
    # all-gather.3 [700,800] holds fusion.4 [750,760]
    assert hand.op_seconds(0)["all-gather.3"] == pytest.approx(90e-9)
    assert trace.self_times([("a", 0, 10, ""), ("b", 2, 4, ""),
                             ("c", 4, 9, ""), ("d", 5, 6, "")]) \
        == [3, 2, 4, 1]


def test_parse_op_names_an_instruction_and_its_opcode():
    text = ('%closed_call.191 = (f32[357888,1024]{1,0:T(8,128)}, '
            'f32[357888,1024]{1,0:T(8,128)}) custom-call(f32[2]{0:T(128)'
            'S(1)} %pad_maximum_fusion.5), custom_call_target="tpu_custom_'
            'call"')
    assert trace.parse_op(text) == ("closed_call.191", "custom-call")
    assert trace.parse_op("%while.388 = (s32[]{:T(128)}, f32[8]{0}) "
                          "while((s32[]{:T(128)}) %tuple.308)") \
        == ("while.388", "while")
    assert trace.parse_op("%fusion.3 = bf16[16,512]{1,0} fusion(%a)") \
        == ("fusion.3", "fusion")


def test_exposed_collectives(hand):
    # all-gather [700,800] less fusion.4 [750,760]; all-reduce alone
    assert hand.exposed_collective_s(0) == pytest.approx(90e-9)
    assert hand.exposed_collective_s(1) == pytest.approx(200e-9)


def test_idle_gaps_by_host_span(hand):
    # gaps of chip 0: [50,100] (no span), [600,700] (innermost at 650 on
    # the window's thread: bench.batch; the other thread's span does not
    # count), [800,900] and [950,1000] (np.asarray)
    gaps = hand.idle_gaps(0)
    assert gaps["no host span"] == pytest.approx(50e-9)
    assert gaps["bench.batch"] == pytest.approx(100e-9)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(150e-9)
    b = hand.breakdown()
    assert b["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert b["device_ops"][0] == ["fusion.1 fusion", pytest.approx(200e-9)]


def test_host_spans_clipped_to_window(hand):
    assert hand.host_spans("bench.batch") == [pytest.approx(60e-9)]


def test_custom_call_names():
    hlo = ('  %closed_call.191 = (f32[8,1024]) custom-call(%a), '
           'custom_call_target="tpu_custom_call", x\n'
           '  ROOT %step.1 = f32[8,1024] custom-call(%b), '
           'custom_call_target="tpu_custom_call"\n'
           '  %fusion.2 = f32[8] fusion(%c)\n')
    assert trace.custom_calls(hlo) == ["closed_call.191", "step.1"]


def test_recorded_v5e_step_boundary():
    """A slice of a real trace (bert_large.adama on a v5e): the device
    goes idle at 1.5 ms, the host is still reading the loss until 4.07 ms,
    builds the next batch (1.10 ms), places it and dispatches the step,
    which starts at 5.53 ms. Answers worked from the slice's seven
    operations by hand."""
    rec = json.loads((DATA / "trace_v5e_step_boundary.json").read_text())
    r = trace.from_events(rec, chips=1)
    assert r.window_s == pytest.approx(6_530_557e-9)
    # operations clipped to the window: 338317 + 753437 + 407316 + 926
    # + 324 + 918252 + 81421 ns
    assert r.busy_s == pytest.approx(2_499_993e-9)
    gaps = r.idle_gaps()
    # the 4,030,557 ns gap and three of 1-2 ns lie inside the loss read
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(4_030_561e-9)
    assert gaps["shard_args"] == pytest.approx(1e-9)
    assert gaps["PjitFunction(step)"] == pytest.approx(2e-9)
    assert r.host_spans("bench.batch") == [pytest.approx(1_102_760e-9)]
    assert r.op_seconds(0)["convert.367"] == pytest.approx(918_252e-9)
