"""The reference spread over a cell's chips (`reference.optim.Spread`), on
four virtual CPU devices, each case in a subprocess of its own (the device
count is fixed when JAX starts): the four-chip ZeRO-1 cell
`stablelm_1_6b.zero1_int8` (its configuration and traffic files) runs end
to end and reads correct, and reads not correct with its timed path
broken; the control reads far above it and the faults fail; every weight
and moment leaf of 1 MiB or more is split over the four devices; and the
spread reference reads what the one-device reference reads. On one chip
the reference keeps its placement and its readings."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchkit import harness, spec  # noqa: E402
from benchkit.data import make_stream  # noqa: E402
from benchkit.weights import leaves_of, program_shapes  # noqa: E402

CELL = "stablelm_1_6b.zero1_int8"
SEED = 2**33 + 131

# The four-chip cell at the family's reduced() widths with 64-token
# sequences; `wide_run` widens the vocabulary and MLP so that the
# embedding, the head and the MLP leaves pass 1 MiB and are split.
_SETUP = f"""
import dataclasses, json, sys, time
sys.path[:0] = [{str(BENCH)!r}]
import jax
import numpy as np
from benchkit import check, harness, spec
from benchkit.data import make_stream
from benchkit.weights import leaves_of, program_shapes
from reference import optim

# The four-chip cell from its files, at 64-token sequences. BENCHMARK.json
# leaves it out until it has been measured on the chip, so it has no limits
# of its own yet: the stage's stand for them at this size.
def four_chip_cell():
    bench = spec.load_benchmark()
    read = lambda part, name: json.loads(
        (spec.BENCH_DIR / part / (name + ".json")).read_text())
    traffic = dict(read("traffic", "zero1_int8_16x2048"), seq_len=64)
    return spec.Cell(name=CELL, chips=4, config_name="stablelm_1_6b",
                     traffic_name="zero1_int8_16x2048",
                     config=read("configs", "stablelm_1_6b"),
                     traffic=traffic,
                     limits=read("limits", "stablelm_1_6b_l8.adama_int8"),
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])

CELL, SEED = {CELL!r}, {SEED}
cell = four_chip_cell()

def wide_run(c):
    run = spec.build_run(c, SEED, reduced=True)
    return dataclasses.replace(run, model=dataclasses.replace(
        run.model, vocab_size=4096, d_ff=1024))
"""


def _sub(code: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c",
                        _SETUP + textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound_and_control():
    """A sound run of the four-chip cell, and bench/control.py's readings
    of the control and the reference-side faults at the same size."""
    return _sub("""
        import control
        spec.load_cell = lambda name, root=None: cell
        r = harness.run_cell(CELL, SEED, 0.5, False,
                             t_proc=time.perf_counter(), reduced=True,
                             require_tpu=False)
        print(json.dumps({"run": r,
                          "control": control.readings(cell, SEED,
                                                      reduced=True)}))
    """)


def test_four_chip_cell_runs_end_to_end_and_reads_correct(
        sound_and_control):
    r = sound_and_control["run"]
    assert r["correct"] is True, r["checks"]
    assert r["device"]["count"] == 4
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["notes"]["window_compiles"] == 0


def test_four_chip_control_reads_far_above_the_program(sound_and_control):
    prog = sound_and_control["run"]["checks"]
    control = sound_and_control["control"]["control"]
    ratios = {k: control[k] / max(prog[k]["value"], 1e-12) for k in prog}
    assert max(ratios.values()) >= 3.0, (ratios, prog, control)


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange",
                                   "state_unchanged"])
def test_four_chip_reference_faults_fail_the_limits(sound_and_control,
                                                    fault):
    from benchkit import check
    got = sound_and_control["control"][fault]
    limits = spec.load_cell("stablelm_1_6b_l8.adama_int8").limits
    assert not check.verdict(got, limits), got


@pytest.fixture(scope="module")
def broken_runs():
    """The four-chip cell's run with the timed path broken underneath it,
    once for each fault the cell can have."""
    return _sub("""
        from repro.train import loop
        spec.load_cell = lambda name, root=None: cell
        make = loop.make_train_step
        t = cell.traffic
        per = t["global_batch"] // t["micro_batches"]

        def drop(batch, keep):
            rows = batch["labels"].shape[0]
            mask = np.isin(np.arange(rows), keep)[:, None]
            return dict(batch, labels=jax.numpy.where(
                mask, batch["labels"], -1))

        def broken(fault):
            def make_broken(*a, **kw):
                step, init = make(*a, **kw)

                def state_unchanged(params, state, batch):
                    _, _, metrics = step(params, state, batch)
                    return params, state, metrics

                def half_batch(params, state, batch):
                    half = batch["labels"].shape[0] // 2
                    return step(params, state,
                                drop(batch, np.arange(half)))

                # each micro-batch's gradient from the first chip's rows
                # alone: what every chip folds when no gradient crosses
                def no_exchange(params, state, batch):
                    rows = batch["labels"].shape[0]
                    keep = [i + j for i in range(0, rows, per)
                            for j in range(per // 4)]
                    return step(params, state, drop(batch, keep))
                return {"state_unchanged": state_unchanged,
                        "half_batch": half_batch,
                        "no_exchange": no_exchange}[fault], init
            return make_broken

        out = {}
        for fault in ("state_unchanged", "half_batch", "no_exchange"):
            loop.make_train_step = broken(fault)
            r = harness.run_cell(CELL, SEED, 0.5, False,
                                 t_proc=time.perf_counter(), reduced=True,
                                 require_tpu=False)
            out[fault] = {"correct": r["correct"], "checks": r["checks"]}
        print(json.dumps(out))
    """)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_four_chip_cell_with_a_broken_step_is_not_correct(broken_runs,
                                                          fault):
    assert broken_runs[fault]["correct"] is False, broken_runs[fault]


@pytest.fixture(scope="module")
def spread_vs_one():
    """The harness's reference on the four-chip cell, widened, spread over
    the four devices and on one device, same seed; with where each leaf of
    the spread weights and moments lay after every step."""
    return _sub("""
        run = wide_run(cell)
        stream = make_stream(cell, run.model, SEED)
        leaves = leaves_of(program_shapes(run.model))
        placed = []
        real = optim.run_steps

        def watched(*a, **kw):
            on_step = kw["on_step"]

            def spy(s, w, m, v, loss):
                for name, tree in (("w", w), ("m", m), ("v", v)):
                    for lf, x in zip(leaves, jax.tree.leaves(tree)):
                        shard = x.addressable_shards[0].data.shape
                        placed.append([name, lf.name, x.nbytes,
                                       len(x.sharding.device_set),
                                       x.sharding.is_fully_replicated,
                                       list(x.shape), list(shard)])
                on_step(s, w, m, v, loss)
            return real(*a, **dict(kw, on_step=spy))
        optim.run_steps = watched

        def plain(r):
            losses, m, change = r
            return {"losses": losses, "m": check.flat(m),
                    "change": check.flat(change)}
        fp32 = dict(cell.traffic, optimizer_state={
            "m_codec": "fp32", "state_codec": "fp32", "grad_dtype": "fp32"})
        out = {}
        for state, c in (("int8", cell),
                         ("fp32", dataclasses.replace(cell, traffic=fp32))):
            start = len(placed)
            spread = harness.reference_readings(c, run, SEED, stream, leaves)
            mid = len(placed)
            one = harness.reference_readings(dataclasses.replace(c, chips=1),
                                             run, SEED, stream, leaves)
            out[state] = {"spread": plain(spread), "one": plain(one),
                          "placed": placed[start:mid],
                          "placed_one": placed[mid:]}
        print(json.dumps(out))
    """)


@pytest.mark.parametrize("state", ["int8", "fp32"])
def test_spread_reference_splits_every_large_leaf(spread_vs_one, state):
    got = spread_vs_one[state]
    big = [p for p in got["placed"] if p[2] >= 1 << 20]
    names = {p[1] for p in big}
    assert {"embed", "lm_head", "blocks/w_up"} <= names, names
    for name, leaf, nbytes, devs, replicated, shape, shard in big:
        assert devs == 4 and not replicated, (name, leaf)
        assert int(np.prod(shard)) * 4 == int(np.prod(shape)), (name, leaf)
    # on one device nothing is split
    for p in got["placed_one"]:
        assert p[3] == 1 and p[5] == p[6], p


# Only the order of the partitioned sums differs. The losses and, with
# fp32 moments, the first moments are held to 1e-6. Int8 moments can also
# flip a code where a moment lies at a rounding boundary, a step of 1/127
# of its row's largest element (1.4e-6 of a leaf's sum of squares seen).
# The change is Adam's update, which scales every element to about lr
# whatever its gradient's size: elements whose gradient cancels to near
# nothing, and so carries the largest relative round-off, weigh as much as
# any other there (2.2e-6 of a norm scale's sum of squares seen).
@pytest.mark.parametrize("state,m_rtol", [("fp32", 1e-6), ("int8", 1e-5)])
def test_spread_reference_reads_what_one_device_reads(spread_vs_one, state,
                                                      m_rtol):
    a, b = spread_vs_one[state]["spread"], spread_vs_one[state]["one"]
    np.testing.assert_allclose(a["losses"], b["losses"], rtol=1e-6)
    for key, rtol in (("m", m_rtol), ("change", 1e-5)):
        assert set(a[key]) == set(b[key])
        for k in b[key]:
            np.testing.assert_allclose(a[key][k], b[key][k], rtol=rtol,
                                       err_msg=f"{state} {key} {k}")


ONE_CHIP_CELL = "stablelm_1_6b_l8.adama_int8"
ONE_CHIP_SEED = 2**33 + 5


def test_one_chip_reference_keeps_its_placement_and_readings(monkeypatch):
    """On one chip nothing is spread: every array of the reference lies on
    one device, and the readings are those the reference gave before it
    could spread (recorded in data/reference_one_chip.json from that
    commit, at the same size and seed; the digits agreed there, the
    tolerance leaves room for another CPU's vector code only)."""
    from reference import optim
    c = spec.load_cell(ONE_CHIP_CELL)
    c = dataclasses.replace(c, traffic=dict(c.traffic, seq_len=64))
    run = spec.build_run(c, ONE_CHIP_SEED, reduced=True)
    stream = make_stream(c, run.model, ONE_CHIP_SEED)
    leaves = leaves_of(program_shapes(run.model))
    devices = set()
    real = optim.run_steps

    def watched(loss_fn, weights, *a, **kw):
        assert kw["spread"] is None
        on_step = kw["on_step"]

        def spy(s, w, m, v, loss):
            import jax
            for x in jax.tree.leaves((w, m, v)):
                devices.update(x.sharding.device_set)
            on_step(s, w, m, v, loss)
        return real(loss_fn, weights, *a, **dict(kw, on_step=spy))
    monkeypatch.setattr(optim, "run_steps", watched)
    losses, m, change = harness.reference_readings(
        c, run, ONE_CHIP_SEED, stream, leaves)
    assert len(devices) == 1
    want = json.loads((BENCH / "tests" / "data"
                       / "reference_one_chip.json").read_text())
    from benchkit import check
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-6)
    for key, got in (("m", check.flat(m)), ("change", check.flat(change))):
        assert set(got) == set(want[key])
        for k, x in want[key].items():
            np.testing.assert_allclose(got[k], x, rtol=1e-6,
                                       err_msg=f"{key} {k}")
