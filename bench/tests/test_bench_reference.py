"""The benchmark's plain float32 references against the program, at the
CPU-scale widths of each family (`reduced()`), with the program computing in
float32 too: where the program departs from the reference, it shows here
first."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit.data import TokenStream  # noqa: E402
from benchkit.weights import make_init, program_shapes, seed_key  # noqa: E402
from reference import bert, optim, stablelm  # noqa: E402
from repro.configs import OptimizerConfig, get_config  # noqa: E402
from repro.core.accumulation import make_train_step  # noqa: E402
from repro.models.model import loss_fn  # noqa: E402

FAMILIES = {"bert_large": bert, "stablelm_1_6b": stablelm}


def _setup(arch, seed=3, batch=4, seq=32):
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              compute_dtype="float32")
    init, leaves = make_init(program_shapes(cfg), cfg.num_layers)
    weights = jax.jit(init)(seed_key(seed))
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=seq,
                         global_batch=batch, seed=seed,
                         encoder=cfg.arch_type == "encoder")
    return cfg, weights, stream, leaves


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


@pytest.mark.parametrize("arch", sorted(FAMILIES))
def test_reference_loss_and_grads_match_program(arch):
    cfg, w, stream, leaves = _setup(arch)
    batch = jax.tree.map(jnp.asarray, stream.batch(0))
    model = {"rope_theta": cfg.rope_theta}
    ref = FAMILIES[arch]
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(
            lambda p: ref.loss(p, batch, model))(w)
        lp, gp = jax.value_and_grad(lambda p: loss_fn(cfg, p, batch))(w)
    assert abs(float(lr) - float(lp)) <= 1e-5 * abs(float(lr))
    for leaf, a, b in zip(leaves, jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert _rel(a, b) < 1e-4, leaf.name


@pytest.mark.parametrize("engine", ["adama", "ga"])
def test_reference_optimizer_matches_program_engine(engine):
    """Two steps of the reference's AdamA (Algorithm 1) / Adam on summed
    gradients against the program's per-leaf engine, both in float32."""
    cfg, w, stream, leaves = _setup("stablelm_1_6b", seed=5)
    n = 2
    opt = OptimizerConfig(accumulation=engine, micro_batches=n, lr=1e-3)
    step, opt_init = make_train_step(cfg, opt)
    batches = [jax.tree.map(jnp.asarray, stream.batch(i)) for i in range(2)]
    model = {"rope_theta": cfg.rope_theta}
    with jax.default_matmul_precision("highest"):
        params, state = w, opt_init(w)
        prog_losses = []
        for b in batches:
            params, state, met = jax.jit(step)(params, state, b)
            prog_losses.append(float(met["loss"]))
        got = {}
        ref_losses = optim.run_steps(
            lambda p, mb: stablelm.loss(p, mb, model),
            jax.tree.map(jnp.copy, w), batches, n_micro=n, engine=engine,
            lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            on_step=lambda s, p, m, v, l: got.update(p=p, m=m, v=v))
    np.testing.assert_allclose(prog_losses, ref_losses, rtol=1e-5)
    for leaf, a, b, p0 in zip(leaves, jax.tree.leaves(params),
                              jax.tree.leaves(got["p"]), jax.tree.leaves(w)):
        # elements whose gradient is near eps move by round-off alone, so
        # the changes are compared as norms, not element by element
        assert _rel(a - p0, b - p0) < 1e-3, leaf.name
    for k in ("m", "v"):
        for leaf, a, b in zip(leaves, jax.tree.leaves(state[k]),
                              jax.tree.leaves(got[k])):
            assert _rel(a, b) < 1e-4, (k, leaf.name)


def test_fp8_control_differs_from_fp32():
    cfg, w, stream, _ = _setup("bert_large")
    batch = jax.tree.map(jnp.asarray, stream.batch(0))
    model = {"rope_theta": cfg.rope_theta}
    l32 = float(bert.loss(w, batch, model, "fp32"))
    l8 = float(bert.loss(w, batch, model, "fp8"))
    assert np.isfinite(l8) and l8 != l32
    g = jax.grad(lambda p: bert.loss(p, batch, model, "fp8"))(w)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))


def _two_steps_reference(cfg, w, batches, leaves, n, **kw):
    model = {"rope_theta": cfg.rope_theta}
    got = {}
    losses = optim.run_steps(
        lambda p, mb: stablelm.loss(p, mb, model), jax.tree.map(jnp.copy, w),
        batches, n_micro=n, engine="adama", lr=1e-3, beta1=0.9, beta2=0.999,
        eps=1e-8, stacked=[lf.stacked for lf in leaves],
        on_step=lambda s, p, m, v, l: got.update(p=p, m=m, v=v), **kw)
    return losses, got


def test_reference_int8_moments_and_bf16_wire_match_program():
    """The configuration's int8 m/v (per-row, m toward zero, v up) and bf16
    gradient wire, as the reference models them, against the program's
    arena kernels."""
    cfg, w, stream, leaves = _setup("stablelm_1_6b", seed=9)
    n = 2
    opt = OptimizerConfig(accumulation="adama", micro_batches=n, lr=1e-3,
                          use_pallas=True, arena=True, m_codec="int8",
                          state_codec="int8", grad_dtype="bf16")
    step, opt_init = make_train_step(cfg, opt)
    batches = [jax.tree.map(jnp.asarray, stream.batch(i)) for i in range(2)]
    with jax.default_matmul_precision("highest"):
        params, state = w, opt_init(w)
        for b in batches:
            params, state, _ = jax.jit(step)(params, state, b)
        _, got = _two_steps_reference(cfg, w, batches, leaves, n,
                                      m_codec="int8", v_codec="int8",
                                      wire="bf16")
    m_prog = state["m"].to_tree()
    for leaf, a, b, p0, pr in zip(leaves, jax.tree.leaves(m_prog),
                                  jax.tree.leaves(got["m"]),
                                  jax.tree.leaves(w),
                                  jax.tree.leaves(params)):
        # a code can flip at a rounding boundary: one step is 1/127 of
        # its row's largest moment
        assert _rel(a, b) < 1e-2, ("m", leaf.name)
        pr_ref = jax.tree.leaves(got["p"])[leaf.index]
        assert _rel(pr - p0, pr_ref - p0) < 1e-2, ("p", leaf.name)


def test_reference_leaf_groups_change_nothing():
    cfg, w, stream, leaves = _setup("stablelm_1_6b", seed=4)
    batches = [jax.tree.map(jnp.asarray, stream.batch(i)) for i in range(2)]
    with jax.default_matmul_precision("highest"):
        l1, g1 = _two_steps_reference(cfg, w, batches, leaves, 2)
        l3, g3 = _two_steps_reference(cfg, w, batches, leaves, 2,
                                      leaf_groups=3)
    np.testing.assert_allclose(l1, l3, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1["p"]), jax.tree.leaves(g3["p"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
