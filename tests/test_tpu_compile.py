"""Compile the arena kernels and the bert_large AdamA step for a TPU v5e that
is described, not attached (jax.experimental.topologies). Nothing runs:
these catch what interpret mode cannot — tiling and VMEM refusals in the
kernels, and a step that does not fit the chip's HBM — at no chip time.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given this
file loads the TPU compiler. The persistent compilation cache is off around
the compiles: entries written for a described chip cannot be read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import arena
from repro.kernels import fused_step as fs
from repro.kernels.adama_accum import LANES
from repro.models.model import init_params

HBM_LIMIT = 15.75e9           # what XLA lets one v5e program hold (16 GB chip)
B1, B2 = 0.9, 0.999


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def layout():
    cfg = get_config("bert_large")
    return arena.build_layout(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _cols(moment, codec, rows, sharding):
    cols = fs.kernel_codec(moment, codec).cols
    return tuple(_spec((rows if c.row_indexed else 1, c.width), c.dtype,
                       sharding) for c in cols)


def _fold(m_codec, v_codec, wire, guarded):
    """(fn, operand builder) for one whole-arena fold variant."""
    def fn(m, v, g, *gs):
        return fs.arena_fold(m, v, g, beta1=B1, beta2=B2, scale=0.25,
                             decay=(B1, B2), m_codec=m_codec,
                             v_codec=v_codec, grad_dtype=wire,
                             grad_scale=gs[0] if gs else None,
                             guard=True if guarded else None,
                             interpret=False)

    def operands(rows, sh):
        ops = [_cols("m", m_codec, rows, sh), _cols("v", v_codec, rows, sh),
               _spec((rows, LANES), wire, sh)]
        if wire == jnp.float8_e4m3fn:
            ops.append(_spec((rows, 1), jnp.float32, sh))
        return ops
    return fn, operands


def _fold_slice(layout):
    spec = layout.stacks[0]
    block = layout.slice_block(spec)

    def fn(m, v, g, off):
        return fs.arena_fold_slice(m, v, g, off, beta1=B1, beta2=B2,
                                   block=block, scale=0.25, interpret=False)

    def operands(rows, sh):
        return [_spec((rows, LANES), jnp.float32, sh),
                _spec((rows, LANES), jnp.float32, sh),
                _spec((spec.layer_rows, LANES), jnp.float32, sh),
                _spec((), jnp.int32, sh)]
    return fn, operands


def _fold_gathered(m_codec, v_codec, wire):
    """The whole-arena fold gathering its gradient from bert_large's
    gradient tree (arena.gather_sources) instead of a slab."""
    grads = jax.eval_shape(lambda: init_params(get_config("bert_large"),
                                               jax.random.key(0)))
    lay = arena.build_layout(grads)

    def fn(m, v, g):
        return fs.arena_fold(m, v, arena.gather_sources(g, lay, wire),
                             beta1=B1, beta2=B2, scale=0.25, decay=(B1, B2),
                             m_codec=m_codec, v_codec=v_codec,
                             grad_dtype=wire, interpret=False)

    def operands(rows, sh):
        return [_cols("m", m_codec, rows, sh), _cols("v", v_codec, rows, sh),
                jax.tree.map(lambda x: _spec(x.shape, x.dtype, sh), grads)]
    return fn, operands


def _apply_master():
    def fn(p, m, v):
        return fs.arena_apply(p, m, v, lr=1e-3, bc1=0.1, bc2=1e-3,
                              work_dtype=jnp.bfloat16, interpret=False)

    def operands(rows, sh):
        return [_spec((rows, LANES), jnp.float32, sh)] * 3
    return fn, operands


KERNELS = {
    "fold_fp32": lambda lay: _fold("fp32", "fp32", jnp.float32, False),
    "fold_int8_int8": lambda lay: _fold("int8", "int8", jnp.float32, False),
    "fold_guarded_bf16_wire": lambda lay: _fold("fp32", "fp32",
                                                jnp.bfloat16, True),
    "fold_guarded_fp8_wire": lambda lay: _fold("fp32", "fp32",
                                               jnp.float8_e4m3fn, True),
    "fold_gathered_int8_bf16_wire": lambda lay: _fold_gathered(
        "int8", "int8", jnp.bfloat16),
    "fold_slice": _fold_slice,
    "apply_master_bf16_work": lambda lay: _apply_master(),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_arena_kernel_compiles_for_v5e(name, layout, one_chip,
                                       no_compile_cache):
    fn, operands = KERNELS[name](layout)
    compiled = jax.jit(fn).lower(*operands(layout.rows, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _bert_large_step(monkeypatch, job, state_shards=1):
    """(step, lr-scheduled RunConfig, abstract params, abstract state) of
    the bert_large AdamA + arena job `job` (launch/train.py arguments),
    with the kernels compiled, not interpreted."""
    from repro.core.accumulation import make_train_step
    from repro.launch.train import build_run, parse_args
    monkeypatch.setattr(fs, "_interpret", lambda: False)
    run, lr_fn = build_run(parse_args(
        ["--arch", "bert-large", "--arena", "--accumulation", "adama",
         "--micro-batches", "4", *job]))
    step, opt_init = make_train_step(run.model, run.optimizer,
                                     lr_schedule=lr_fn,
                                     state_shards=state_shards)
    params = jax.eval_shape(lambda: init_params(run.model,
                                                jax.random.key(0)))
    return step, run, params, jax.eval_shape(opt_init, params)


def _check_fits(compiled):
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2    # fold + apply
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_LIMIT, f"{used / 1e9:.2f} GB >= {HBM_LIMIT / 1e9} GB"


def test_bert_large_adama_arena_step_fits_one_v5e(one_chip, no_compile_cache,
                                                  monkeypatch):
    """Full-width bert_large, AdamA + arena, 4 micro-batches of 1 x 512 (the
    chip_smoke.py training job): one program with the fold and apply kernels
    whose arguments and temporaries fit one chip's HBM."""
    step, _, params, state = _bert_large_step(
        monkeypatch, ["--global-batch", "4", "--seq-len", "512"])
    place = lambda t: jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    batch = {k: _spec((4, 512), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    _check_fits(jax.jit(step, donate_argnums=(0, 1)).lower(
        place(params), place(state), batch).compile())


def test_bert_large_zero1_step_compiles_for_v5e_2x2(topo, no_compile_cache,
                                                    monkeypatch):
    """The chip_smoke.py --chips 4 job: ZeRO-1 over a 4-chip data mesh at
    global batch 16 x 128. XLA cannot partition a Mosaic kernel, so this
    compiles only because the arena kernels run per row shard in a
    shard_map (kernels/fused_step.py::_on_mesh); m/v are 1/4 per chip."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.sharding import ctx
    from repro.train.loop import zero1_shardings
    step, run, params, state = _bert_large_step(
        monkeypatch, ["--global-batch", "16", "--seq-len", "128",
                      "--zero-stage", "1"], state_shards=4)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    batch = {k: jax.ShapeDtypeStruct((16, 128), jnp.int32)
             for k in ("tokens", "labels")}
    with ctx.use_mesh(mesh, ("data",)):
        p_sh, o_sh, b_sh, rep = zero1_shardings(mesh, run.optimizer, params,
                                                state)
        compiled = jax.jit(step, donate_argnums=(0, 1),
                           in_shardings=(p_sh, o_sh, b_sh),
                           out_shardings=(p_sh, o_sh, rep)).lower(
            params, state, batch).compile()
    assert tuple(o_sh["m"].data.spec) == ("data",)
    _check_fits(compiled)


def _computations(hlo: str):
    """{name: body text} of an HLO module's computations."""
    comps, name, lines = {}, None, []
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name, lines = line.split()[1 if line.startswith("ENTRY") else 0], []
        elif line == "}" and name is not None:
            comps[name] = "\n".join(lines)
            name = None
        elif name is not None:
            lines.append(line)
    return comps


def test_bert_large_adama_step_writes_no_gradient_slab(one_chip,
                                                       no_compile_cache,
                                                       monkeypatch):
    """The benchmark's bert_large AdamA job (8 micro-batches of 1 x 512):
    the fold gathers each micro-batch's gradient from the leaves, so the
    micro-batch scan body holds one Mosaic kernel, the fold, and no other
    instruction of the (rows, 1024) fp32 slab's shape; and the step needs
    no more temporaries than the slab path did (v5e compile of the slab
    path: 7,284,355,584 B)."""
    step, _, params, state = _bert_large_step(
        monkeypatch, ["--micro-batches", "8", "--global-batch", "8",
                      "--seq-len", "512"])
    place = lambda t: jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    batch = {k: _spec((8, 512), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    fs.FOLD_SOURCES.clear()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        place(params), place(state), batch).compile()
    assert fs.FOLD_SOURCES == {"leaves": 1}
    slab = f"f32[{state['m'].layout.rows},{LANES}]"
    bodies = [c for c in _computations(compiled.as_text()).values()
              if "arena_fold" in c and "tpu_custom_call" in c]
    assert len(bodies) == 1
    body = bodies[0]
    assert body.count('custom_call_target="tpu_custom_call"') == 1
    for line in body.splitlines():
        lhs, _, rhs = line.partition(" = ")
        if rhs.startswith(slab):
            op = rhs.split("} ", 1)[1].split("(", 1)[0]
            assert op in ("parameter", "get-tuple-element"), line[:160]
    assert compiled.memory_analysis().temp_size_in_bytes <= 7_284_355_584
