"""The control and the reference-side faults of bench/control.py, at a
size a test run can hold (the family's reduced() widths, 64-token
sequences): the float8 control reads well above the sound program on at
least one compared number, and the faults fail the cell's limits."""
import dataclasses
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
from benchkit import check, harness, spec  # noqa: E402

CELL = "bert_large.adama"
SEED = 2**33 + 91


@pytest.fixture(scope="module")
def small_cell():
    c = spec.load_cell(CELL)
    return dataclasses.replace(c, traffic=dict(c.traffic, seq_len=64))


@pytest.fixture(scope="module")
def readings(small_cell):
    return control.readings(small_cell, SEED, reduced=True)


def test_control_reads_far_above_the_program(small_cell, readings,
                                             monkeypatch):
    monkeypatch.setattr(spec, "load_cell", lambda name, root=None:
                        small_cell)
    prog = harness.run_cell(CELL, SEED, 0.5, False,
                            t_proc=time.perf_counter(), reduced=True,
                            require_tpu=False)["checks"]
    ratios = {k: readings["control"][k] / max(prog[k]["value"], 1e-12)
              for k in check.NUMBERS}
    assert max(ratios.values()) >= 3.0, (ratios, prog, readings)


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_faults_fail_the_limits(small_cell, readings, fault):
    assert not check.verdict(readings[fault], small_cell.limits), \
        readings[fault]
