"""The gathered fold: arena_fold reading a micro-batch's gradient straight
from the gradient tree's sources (core/arena.py::gather_sources) must leave
m and v bitwise equal to packing the tree into a slab and folding that —
for every codec column, on both gradient wires, with and without the fused
begin-minibatch decay, with a static and a traced scale — and must keep
the rows and lanes no leaf covers at zero.

The layout holds every kind of source: leaves read in place (a stacked
(16, 1024) leaf, a (2, 8, 1024) leaf, a bf16 leaf, an embedding-like rest
leaf), leaves that need a relayout (last dim != 1024), 1-row leaves grouped
into one copy, leaves with tail lanes, the rest region, region padding and
the arena's tail padding rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arena
from repro.kernels import fused_step as fs

B1, B2 = 0.9, 0.999
LAYERS = 3


def _tree(key):
    shapes = {
        "blocks": {"a_scale": ((LAYERS, 1024), jnp.float32),
                   "b_bias": ((LAYERS, 1024), jnp.float32),
                   "c_direct": ((LAYERS, 16, 1024), jnp.float32),
                   "d_relayout": ((LAYERS, 64, 48), jnp.float32),
                   "e_tail": ((LAYERS, 700), jnp.float32),
                   "f_deep": ((LAYERS, 2, 8, 1024), jnp.float32),
                   "g_half": ((LAYERS, 16, 1024), jnp.bfloat16)},
        "bias": ((5,), jnp.float32),
        "embed": ((24, 1024), jnp.float32),
        "head": ((1024, 40), jnp.float32),
    }
    leaves, tdef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple) and isinstance(x[0], tuple))
    keys = jax.random.split(key, len(leaves))
    return tdef.unflatten([jax.random.normal(k, s, jnp.float32).astype(d)
                           for k, (s, d) in zip(keys, leaves)])


def _layout():
    lay = arena.build_layout(_tree(jax.random.key(0)))
    # the cases this file claims: region padding and arena tail padding
    s = lay.stacks[0]
    assert s.layer_rows > sum(x.rows for x in s.leaves)
    assert lay.rows > lay.rest.row + lay.rest.rows
    return lay


def test_plan_has_every_source_kind():
    lay = _layout()
    for wire in (jnp.float32, jnp.bfloat16):
        plan = arena.grad_plan(lay, wire)
        direct = {(s.stack, s.leaves) for s in plan.sources if s.direct}
        # c_direct, f_deep, g_half in place; the rest's embed in place
        assert direct == {(0, (2,)), (0, (5,)), (0, (6,)), (-1, (1,))}
        # sub-tile neighbours share one copy: the 1-row norms, and the
        # 3-row relayout leaf with the tail-lane leaf; the rest's head is
        # a relayout copy of its own
        groups = [(s.stack, s.leaves) for s in plan.sources
                  if len(s.leaves) > 1]
        assert groups == [(0, (0, 1)), (0, (3, 4))]
        assert any(s.stack == -1 and s.leaves == (2,) and not s.direct
                   for s in plan.sources)
        # every arena row a leaf covers belongs to exactly one run
        covered = np.zeros(lay.rows, int)
        for r in plan.runs:
            covered[r.start:r.start + r.rows] += 1
        packed = np.asarray(arena.pack(jax.tree.map(jnp.ones_like,
                                                    _tree(jax.random.key(0))),
                                       lay))
        assert np.array_equal(covered, (packed.any(axis=1)).astype(int))


def exact(fn, *args):
    """Run `fn` jitted with XLA:CPU rounding every product. Interpret mode
    runs the kernel bodies through XLA:CPU, which contracts `a*b + c*d`
    into a fused multiply-add wherever its fusion puts both in one loop;
    two equal programs can then differ in the last bit of an int8 codec's
    row scale. Without backend optimization each product is rounded, as
    the kernels state it."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})(*args)


def _fold(m, v, g, *, codec, wire, decay, scale):
    return fs.arena_fold(m, v, g, beta1=B1, beta2=B2, scale=scale,
                         decay=decay, m_codec=codec, v_codec=codec,
                         grad_dtype=wire)


def _state(codec, lay, wire):
    """A non-zero codec state: one slab fold from zeros (padding stays 0)."""
    z = lambda moment: tuple(
        jnp.zeros((lay.rows if c.row_indexed else 1, c.width), c.dtype)
        for c in fs.kernel_codec(moment, codec).cols)
    g0 = arena.pack(_tree(jax.random.key(7)), lay, dtype=wire)
    return _fold(z("m"), z("v"), g0, codec=codec, wire=wire, decay=None,
                 scale=1.0)


@pytest.mark.parametrize("traced_scale", [False, True],
                         ids=["static_scale", "traced_scale"])
@pytest.mark.parametrize("decay", [None, (B1, B2)],
                         ids=["no_decay", "decay"])
@pytest.mark.parametrize("wire", [jnp.float32, jnp.bfloat16],
                         ids=["fp32_wire", "bf16_wire"])
@pytest.mark.parametrize("codec", ["fp32", "int8"])
def test_gathered_fold_is_bitwise_slab_fold(codec, wire, decay,
                                            traced_scale):
    lay = _layout()
    m, v = _state(codec, lay, wire)
    tree = _tree(jax.random.key(11))

    def fold(gradient):
        def fn(m, v, *s):
            return _fold(m, v, gradient(), codec=codec, wire=wire,
                         decay=decay, scale=s[0] if s else 0.25)
        return fn

    s = (jnp.float32(0.25),) if traced_scale else ()
    fs.FOLD_SOURCES.clear()
    ref = exact(fold(lambda: arena.pack(tree, lay, dtype=wire)), m, v, *s)
    out = exact(fold(lambda: arena.gather_sources(tree, lay, wire)), m, v,
                *s)
    assert fs.FOLD_SOURCES == {"slab": 1, "leaves": 1}
    covered = np.asarray(arena.pack(jax.tree.map(jnp.ones_like, tree),
                                    lay)) != 0
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8))
        # rows and lanes no leaf covers keep their zeros
        mask = covered if b.shape[1] == covered.shape[1] \
            else covered.any(axis=1, keepdims=True)
        assert not np.any(b[~mask])
        assert np.any(b[mask])


def test_gathered_fold_refuses_guard_and_mesh_paths():
    lay = _layout()
    m, v = _state("fp32", lay, jnp.float32)
    g = arena.gather_sources(_tree(jax.random.key(3)), lay)
    with pytest.raises(ValueError, match="finite guard"):
        fs.arena_fold(m, v, g, beta1=B1, beta2=B2, guard=True)
    with pytest.raises(TypeError, match="declared grad_dtype"):
        fs.arena_fold(m, v, g, beta1=B1, beta2=B2,
                      grad_dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# The engines: where the gathered fold engages, and that it changes nothing
# ---------------------------------------------------------------------------

# the benchmark's two AdamA jobs (bench/traffic/), at reduced widths
ENGINE_JOBS = {"bert_large": {},
               "stablelm_1_6b": dict(state_codec="int8", m_codec="int8",
                                     grad_dtype="bf16")}


def _adama(**kw):
    from repro.configs import OptimizerConfig
    return OptimizerConfig(name="adama", accumulation="adama",
                           micro_batches=2, use_pallas=True, arena=True,
                           **kw)


def _traced_folds(cfg, opt, params, batch):
    """FOLD_SOURCES of one trace of the engine's step (nothing compiled)."""
    from repro.core.accumulation import make_train_step
    step, init = make_train_step(cfg, opt)
    fs.FOLD_SOURCES.clear()
    jax.jit(step).trace(params, jax.eval_shape(init, params), batch)
    return dict(fs.FOLD_SOURCES)


@pytest.mark.parametrize("arch", sorted(ENGINE_JOBS))
def test_engine_gathered_fold_is_bitwise_slab_path(arch, monkeypatch):
    """Two pjit AdamA steps with the gradient gathered from the leaves
    give the params, m and v of the same steps with the slab packed."""
    from conftest import batch_for
    from repro.configs import get_config
    from repro.core import adama
    from repro.core.accumulation import make_train_step
    from repro.models.model import init_params
    cfg = get_config(arch).reduced()
    opt = _adama(**ENGINE_JOBS[arch])
    params = init_params(cfg, jax.random.key(0))
    batch = batch_for(cfg, 4, 16)

    def two_steps():
        step, init = make_train_step(cfg, opt)
        fs.FOLD_SOURCES.clear()
        state = init(params)
        run = jax.jit(step).lower(params, state, batch).compile(
            compiler_options={"xla_backend_optimization_level": 0})
        folds = dict(fs.FOLD_SOURCES)
        p = params
        for _ in range(2):
            p, state, _ = run(p, state, batch)
        return p, state, folds

    p1, s1, folds1 = two_steps()
    packed = adama.accumulate
    monkeypatch.setattr(adama, "accumulate", lambda *a, **k: packed(
        *a, **dict(k, from_leaves=False)))
    p0, s0, folds0 = two_steps()
    assert folds1 == {"leaves": 1} and folds0 == {"slab": 1}
    for a, b in zip(jax.tree.leaves((p0, s0["m"], s0["v"])),
                    jax.tree.leaves((p1, s1["m"], s1["v"]))):
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8))


@pytest.mark.parametrize("job", [
    dict(),
    dict(finite_guard=True),
    dict(finite_guard=True, grad_dtype="fp8_e4m3"),
    dict(accumulation="adama_layerwise"),
], ids=["unguarded", "guarded", "fp8_wire", "layerwise"])
def test_fold_source_counter_by_engine(job):
    """The trace-time counter reads `leaves` on the unguarded pjit AdamA
    step and `slab` where the slab has another reader (the finite check,
    the fp8 encode and error feedback) or the fold is per layer."""
    import dataclasses
    from conftest import batch_for
    from repro.configs import get_config
    from repro.models.model import init_params
    cfg = get_config("bert_large").reduced()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    folds = _traced_folds(cfg, dataclasses.replace(_adama(), **job), params,
                          batch_for(cfg, 4, 16))
    assert list(folds) == (["leaves"] if not job else ["slab"])


def test_fold_source_counter_reads_slab_under_zero1_mesh():
    """ZeRO-1 over a two-device mesh splits the fold's rows over the mesh:
    the engine keeps the slab there."""
    from test_distributed import run_sub
    out = run_sub("""
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.core.accumulation import make_train_step
        from repro.kernels import fused_step as fs
        from repro.models.model import init_params
        from repro.sharding import ctx
        cfg = get_config('bert_large').reduced()
        opt = OptimizerConfig(name='adama', accumulation='adama',
                              micro_batches=2, use_pallas=True, arena=True,
                              zero_stage=1)
        step, init = make_train_step(cfg, opt, state_shards=2)
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
        batch = {k: jax.ShapeDtypeStruct((4, 16), 'int32')
                 for k in ('tokens', 'labels')}
        mesh = Mesh(np.array(jax.devices()).reshape(2, 1), ('data', 'model'))
        with ctx.use_mesh(mesh, ('data',)):
            jax.jit(step).trace(params, jax.eval_shape(init, params), batch)
        print('FOLDS', dict(fs.FOLD_SOURCES))
    """, devices=2)
    assert "FOLDS {'slab': 1}" in out


def test_train_logs_the_fold_gradient_source():
    from repro.launch.train import build_run, parse_args
    from repro.train.loop import train
    run, _ = build_run(parse_args(
        ["--arch", "bert-large", "--reduced", "--steps", "1",
         "--accumulation", "adama", "--arena", "--micro-batches", "2",
         "--global-batch", "2", "--seq-len", "16"]))
    lines = []
    train(run, log_fn=lines.append)
    assert "[train] fold gradient: leaves=1 slab=0" in lines
