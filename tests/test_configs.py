"""OptimizerConfig capability matrix (configs/base.py): the full
codec x zero_stage x engine x arena grid either constructs or refuses with
an ACTIONABLE message — never a silent misconfiguration. This replaces the
old blanket `arena x zero_stage=1` ValueError (row-range sharding lifted
that ban; see core/zero.py::shard_rows)."""
import dataclasses
import itertools

import pytest

from repro.configs.base import (ACCUM_ENGINES, GRAD_DTYPES, M_CODECS,
                                STATE_CODECS, ZERO_STAGES, OptimizerConfig,
                                mesh_capability, optimizer_capability,
                                validate_optimizer_config)


def _mk(**kw):
    """Construct WITHOUT __post_init__ validation, so tests can probe
    optimizer_capability on invalid points of the grid."""
    opt = object.__new__(OptimizerConfig)
    base = OptimizerConfig()
    for f in dataclasses.fields(OptimizerConfig):
        object.__setattr__(opt, f.name, kw.get(f.name, getattr(base, f.name)))
    return opt


def test_default_config_is_valid():
    assert optimizer_capability(OptimizerConfig()) is None


def test_matrix_dimensions_are_exported():
    assert set(STATE_CODECS) == {"fp32", "int8", "factored", "rowcol"}
    assert set(M_CODECS) == {"fp32", "int8"}
    assert set(ZERO_STAGES) == {0, 1}
    assert set(ACCUM_ENGINES) == {"ga", "adama", "adama_layerwise"}
    assert set(GRAD_DTYPES) == {"fp32", "bf16", "fp8_e4m3"}


def test_matrix_matches_state_store_registry():
    """The config-level codec tuples and the state_store registries are the
    same sets — a codec registered in one place but not the other is a bug."""
    from repro.core.state_store import M_CODECS as M_REG, V_CODECS as V_REG
    assert set(STATE_CODECS) == set(V_REG)
    assert set(M_CODECS) == set(M_REG)


@pytest.mark.parametrize("m_codec", M_CODECS)
@pytest.mark.parametrize("codec", STATE_CODECS)
@pytest.mark.parametrize("zero", ZERO_STAGES)
@pytest.mark.parametrize("engine", ACCUM_ENGINES)
def test_full_matrix_arena(m_codec, codec, zero, engine):
    """With the arena on (use_pallas implied), EVERY m_codec x v_codec x
    zero x engine cell is supported for the adama optimizer — the whole
    point of row-range sharding and row-indexed codec state."""
    opt = OptimizerConfig(name="adama", accumulation=engine, arena=True,
                          use_pallas=True, state_codec=codec,
                          m_codec=m_codec, zero_stage=zero)
    assert optimizer_capability(opt) is None


@pytest.mark.parametrize("m_codec", M_CODECS)
@pytest.mark.parametrize("codec", STATE_CODECS)
@pytest.mark.parametrize("zero", ZERO_STAGES)
@pytest.mark.parametrize("engine", ACCUM_ENGINES)
def test_full_matrix_no_arena(m_codec, codec, zero, engine):
    """Without the arena: fp32 everywhere; compressed codecs refuse (they
    are arena columns) and the message says how to fix it."""
    opt = _mk(name="adama", accumulation=engine, arena=False,
              use_pallas=False, state_codec=codec, m_codec=m_codec,
              zero_stage=zero)
    reason = optimizer_capability(opt)
    if codec == "fp32" and m_codec == "fp32":
        assert reason is None
    elif codec != "fp32":
        assert "arena=True" in reason and "state_codec" in reason
    else:
        assert "arena=True" in reason and "m_codec" in reason


def test_matrix_exhaustive_never_crashes():
    """optimizer_capability is total over the declared grid (plus the
    arena/use_pallas/master booleans): it returns None or a str, never
    raises."""
    for codec, m_codec, zero, engine, arena, pallas, gdt, master in \
            itertools.product(STATE_CODECS, M_CODECS, ZERO_STAGES,
                              ACCUM_ENGINES, (False, True), (False, True),
                              GRAD_DTYPES, (False, True)):
        reason = optimizer_capability(_mk(
            name="adama", accumulation=engine, state_codec=codec,
            m_codec=m_codec, zero_stage=zero, arena=arena,
            use_pallas=pallas, grad_dtype=gdt, master_params=master))
        assert reason is None or isinstance(reason, str)


@pytest.mark.parametrize("m_codec", M_CODECS)
@pytest.mark.parametrize("codec", STATE_CODECS)
@pytest.mark.parametrize("zero", ZERO_STAGES)
@pytest.mark.parametrize("engine", ("adama", "adama_layerwise"))
def test_full_matrix_bf16_wire_with_master(m_codec, codec, zero, engine):
    """grad_dtype=bf16 + master_params composes with every codec pair, both
    zero stages, and both AdamA fold engines over the arena — the
    mixed-precision wire is a pack/collective dtype, orthogonal to the
    codec transforms (which run on the in-kernel fp32 upcast)."""
    opt = OptimizerConfig(name="adama", accumulation=engine, arena=True,
                          use_pallas=True, state_codec=codec,
                          m_codec=m_codec, zero_stage=zero,
                          grad_dtype="bf16", master_params=True)
    assert optimizer_capability(opt) is None


def test_bf16_wire_refusals_name_the_fix():
    assert "arena=True" in optimizer_capability(_mk(grad_dtype="bf16"))
    reason = optimizer_capability(_mk(grad_dtype="bf16", accumulation="ga",
                                      arena=True, use_pallas=True))
    assert "ga" in reason and "adama" in reason
    assert "expected one of" in optimizer_capability(
        _mk(grad_dtype="fp16", arena=True, use_pallas=True))
    assert "arena=True" in optimizer_capability(_mk(master_params=True))


@pytest.mark.parametrize("m_codec", M_CODECS)
@pytest.mark.parametrize("codec", STATE_CODECS)
@pytest.mark.parametrize("zero", ZERO_STAGES)
@pytest.mark.parametrize("engine", ("adama", "adama_layerwise"))
def test_full_matrix_fp8_wire(m_codec, codec, zero, engine):
    """grad_dtype=fp8_e4m3 (+ the finite guards it requires) composes with
    every codec pair, both zero stages, and both AdamA fold engines — the
    fp8 decode happens on the in-kernel fp32 upcast, before any codec
    transform sees the gradient."""
    opt = OptimizerConfig(name="adama", accumulation=engine, arena=True,
                          use_pallas=True, state_codec=codec,
                          m_codec=m_codec, zero_stage=zero,
                          grad_dtype="fp8_e4m3", finite_guard=True)
    assert optimizer_capability(opt) is None


def test_fp8_wire_refusals_name_the_fix():
    # fp8 without the guards: e4m3's NaN-overflow encoding needs them
    reason = optimizer_capability(_mk(grad_dtype="fp8_e4m3", arena=True,
                                      use_pallas=True))
    assert "finite_guard=True" in reason
    # fp8 without the arena
    assert "arena=True" in optimizer_capability(_mk(grad_dtype="fp8_e4m3"))
    # fp8 on the ga engine: the accumulated-gradient path has no fold to
    # decode into
    reason = optimizer_capability(_mk(grad_dtype="fp8_e4m3",
                                      accumulation="ga", arena=True,
                                      use_pallas=True, finite_guard=True))
    assert "ga" in reason
    # the static loss-scale grammar accepts the fp8 wire
    opt = OptimizerConfig(name="adama", accumulation="adama", arena=True,
                          use_pallas=True, grad_dtype="fp8_e4m3",
                          finite_guard=True, loss_scale="256")
    assert optimizer_capability(opt) is None


def test_work_param_cache_requires_master():
    reason = optimizer_capability(_mk(work_param_cache=True))
    assert "master_params=True" in reason
    with pytest.raises(ValueError, match="master_params=True"):
        OptimizerConfig(work_param_cache=True, arena=True, use_pallas=True)
    opt = OptimizerConfig(name="adama", accumulation="adama", arena=True,
                          use_pallas=True, master_params=True,
                          work_param_cache=True)
    assert optimizer_capability(opt) is None


def test_arena_requires_pallas_with_guidance():
    reason = optimizer_capability(_mk(arena=True, use_pallas=False))
    assert "use_pallas=True" in reason
    with pytest.raises(ValueError, match="use_pallas=True"):
        OptimizerConfig(arena=True, use_pallas=False)


def test_codec_without_arena_names_the_fix():
    with pytest.raises(ValueError, match="arena=True"):
        OptimizerConfig(state_codec="int8")
    with pytest.raises(ValueError, match="state_store"):
        OptimizerConfig(state_codec="factored")


def test_arena_zero1_is_now_supported():
    """The PR-1 blanket ban is lifted: arena + zero_stage=1 row-shards."""
    opt = OptimizerConfig(name="adama", accumulation="adama", arena=True,
                          use_pallas=True, zero_stage=1)
    assert optimizer_capability(opt) is None


def test_unknown_values_rejected_with_alternatives():
    assert "expected one of" in optimizer_capability(_mk(state_codec="fp16"))
    assert "expected one of" in optimizer_capability(_mk(m_codec="fp16"))
    assert "expected one of" in optimizer_capability(_mk(accumulation="nope"))
    reason = optimizer_capability(_mk(zero_stage=3))
    assert "zero_stage=3" in reason
    with pytest.raises(ValueError, match="state_codec"):
        OptimizerConfig(state_codec="fp16", arena=True, use_pallas=True)
    with pytest.raises(ValueError, match="m_codec"):
        OptimizerConfig(m_codec="factored", arena=True, use_pallas=True)


def test_m_codec_without_arena_names_the_fix():
    with pytest.raises(ValueError, match="arena=True"):
        OptimizerConfig(m_codec="int8")


def test_arena_ga_engine_is_adam_only():
    reason = optimizer_capability(_mk(name="sm3", accumulation="ga",
                                      arena=True, use_pallas=True))
    assert "adam" in reason and "sm3" in reason
    # adam and adama themselves are fine
    for name in ("adam", "adama"):
        assert optimizer_capability(_mk(name=name, accumulation="ga",
                                        arena=True, use_pallas=True)) is None


def test_validate_raises_exactly_when_capability_says_so():
    good = _mk(name="adama", arena=True, use_pallas=True, state_codec="int8")
    validate_optimizer_config(good)        # no raise
    bad = _mk(state_codec="int8", arena=False)
    with pytest.raises(ValueError):
        validate_optimizer_config(bad)


# ---------------------------------------------------------------------------
# zero_async: the double-buffered bucket pipeline's capability row
# ---------------------------------------------------------------------------

def test_zero_async_requires_zero1():
    reason = optimizer_capability(_mk(zero_async=True, arena=True,
                                      use_pallas=True))
    assert "zero_stage=1" in reason


def test_zero_async_requires_arena():
    reason = optimizer_capability(_mk(zero_async=True, zero_stage=1))
    assert "arena=True" in reason


def test_zero_async_requires_a_bucketed_schedule():
    reason = optimizer_capability(_mk(name="adama", accumulation="adama",
                                      zero_async=True, zero_stage=1,
                                      arena=True, use_pallas=True,
                                      zero_bucketed=False))
    assert "bucketed" in reason
    # the layerwise stream IS a bucketed schedule (one bucket per layer):
    # zero_bucketed=False composes with it
    opt = _mk(name="adama", accumulation="adama_layerwise", zero_async=True,
              zero_stage=1, arena=True, use_pallas=True, zero_bucketed=False)
    assert optimizer_capability(opt) is None


@pytest.mark.parametrize("m_codec", M_CODECS)
@pytest.mark.parametrize("codec", STATE_CODECS)
@pytest.mark.parametrize("engine", ("adama", "adama_layerwise"))
@pytest.mark.parametrize("gdt", GRAD_DTYPES)
def test_full_matrix_zero_async(m_codec, codec, engine, gdt):
    """zero_async composes with every codec pair, both AdamA fold engines,
    and every gradient wire dtype over bucketed ZeRO-1 — the pipeline
    reorders WHEN each bucket's reduce-scatter is issued, never WHAT flows
    through it, so it is orthogonal to codecs and wire dtypes."""
    opt = OptimizerConfig(
        name="adama", accumulation=engine, arena=True, use_pallas=True,
        state_codec=codec, m_codec=m_codec, zero_stage=1, zero_async=True,
        grad_dtype=gdt,
        finite_guard=(gdt == "fp8_e4m3"))
    assert optimizer_capability(opt) is None


def test_matrix_exhaustive_with_zero_async_never_crashes():
    """The exhaustive totality sweep, zero_async dimension included."""
    for codec, zero, engine, arena, gdt, azync, bucketed in \
            itertools.product(STATE_CODECS, ZERO_STAGES, ACCUM_ENGINES,
                              (False, True), GRAD_DTYPES, (False, True),
                              (False, True)):
        reason = optimizer_capability(_mk(
            name="adama", accumulation=engine, state_codec=codec,
            zero_stage=zero, arena=arena, use_pallas=arena, grad_dtype=gdt,
            zero_async=azync, zero_bucketed=bucketed))
        assert reason is None or isinstance(reason, str)


# ---------------------------------------------------------------------------
# mesh_capability: the dp x tp mesh-composition matrix
# ---------------------------------------------------------------------------

def _good_opt(**kw):
    return _mk(name="adama", accumulation=kw.pop("accumulation", "adama"),
               arena=True, use_pallas=True, zero_stage=1, **kw)


def test_mesh_flat_dp_always_composes():
    assert mesh_capability(_good_opt(), (4,), ("data",),
                           tp_axis=None) is None


def test_mesh_multiaxis_manual_dp_product_composes():
    """A 2x2 'data' x 'model' mesh with BOTH axes manual dp is the pure-DP
    profile — supported everywhere, bitwise equal to flat 4dp."""
    assert mesh_capability(_good_opt(), (2, 2), ("data", "model"),
                           tp_axis=None) is None


def test_mesh_tp_size_one_degrades_to_pure_dp():
    assert mesh_capability(_good_opt(), (4, 1), ("data", "model"),
                           tp_axis="model") is None


def test_mesh_pjit_engine_accepts_any_tp():
    assert mesh_capability(_good_opt(), (2, 2), ("data", "model"),
                           tp_axis="model", engine="pjit") is None


def test_mesh_mixed_auto_tp_gated_on_jax_version():
    reason = mesh_capability(_good_opt(), (2, 2), ("data", "model"),
                             tp_axis="model", engine="shardmap")
    assert reason is None


def test_mesh_mixed_auto_tp_refuses_master_params_on_any_jax():
    reason = mesh_capability(_good_opt(master_params=True), (2, 2),
                             ("data", "model"), tp_axis="model")
    assert "master_params" in reason


def test_mesh_malformed_inputs_name_the_problem():
    assert "disagree in rank" in mesh_capability(
        _good_opt(), (2, 2), ("data",), tp_axis=None)
    assert "not a mesh axis" in mesh_capability(
        _good_opt(), (4,), ("data",), tp_axis="model")
    assert "unknown engine" in mesh_capability(
        _good_opt(), (4,), ("data",), tp_axis=None, engine="xmap")


def test_mesh_matrix_exhaustive_never_crashes():
    """mesh_capability is total over tp_axis x engine x codec x grad_dtype
    x master_params on both 1D and 2D meshes: None or str, never raises."""
    meshes = (((4,), ("data",)), ((2, 2), ("data", "model")),
              ((1, 4), ("data", "model")))
    for (shape, axes), tp, engine, codec, gdt, master in itertools.product(
            meshes, (None, "model", "data"), ("pjit", "shardmap"),
            STATE_CODECS, GRAD_DTYPES, (False, True)):
        reason = mesh_capability(
            _good_opt(state_codec=codec, grad_dtype=gdt,
                      master_params=master),
            shape, axes, tp_axis=tp, engine=engine)
        assert reason is None or isinstance(reason, str)


def test_compile_cache_keeps_env_dir_else_fixed_checkout_path(monkeypatch,
                                                              tmp_path):
    """launch/compile_cache.py: JAX_COMPILATION_CACHE_DIR wins and nothing
    else is set; without it the cache goes to <checkout>/.jax_cache, a path
    that is the same on every run."""
    from pathlib import Path

    import jax
    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = Path(__file__).resolve().parents[1]
    try:
        assert cc.use_compile_cache() == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            checkout / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()
