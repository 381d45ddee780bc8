"""Pallas kernels vs pure-jnp oracles: shape/dtype sweep + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


SHAPES = [(1,), (7,), (1024,), (300, 150), (2, 3, 257), (2048, 1024)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
def test_adama_accum_matches_ref(shape, gdtype):
    m = jax.random.normal(jax.random.key(1), shape, jnp.float32)
    v = jnp.abs(jax.random.normal(jax.random.key(2), shape, jnp.float32))
    g = jax.random.normal(jax.random.key(3), shape, gdtype)
    mo, vo = ops.adama_accumulate(m, v, g, beta1=0.9, beta2=0.99, scale=0.25)
    mr, vr = ref.adama_accum_ref(m, v, g, beta1=0.9, beta2=0.99, scale=0.25)
    np.testing.assert_allclose(mo, mr, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(vo, vr, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16])
def test_adam_apply_matches_ref(shape, pdtype):
    p = jax.random.normal(jax.random.key(4), shape, pdtype)
    m = jax.random.normal(jax.random.key(5), shape, jnp.float32)
    v = jnp.abs(jax.random.normal(jax.random.key(6), shape, jnp.float32))
    po = ops.adam_apply(p, m, v, lr=1e-3, bc1=0.5, bc2=0.3, weight_decay=0.01)
    pr = ref.adam_apply_ref(p, m, v, lr=1e-3, bc1=0.5, bc2=0.3,
                            weight_decay=0.01)
    tol = 2e-2 if pdtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(np.asarray(po, np.float32),
                               np.asarray(pr, np.float32), rtol=tol, atol=tol)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 5000), b1=st.floats(0.0, 0.999),
       b2=st.floats(0.9, 0.9999), scale=st.floats(0.01, 1.0))
def test_adama_accum_property(n, b1, b2, scale):
    m = jnp.linspace(-1, 1, n)
    v = jnp.linspace(0, 2, n)
    g = jnp.sin(jnp.arange(n, dtype=jnp.float32))
    mo, vo = ops.adama_accumulate(m, v, g, beta1=b1, beta2=b2, scale=scale)
    mr, vr = ref.adama_accum_ref(m, v, g, beta1=b1, beta2=b2, scale=scale)
    np.testing.assert_allclose(mo, mr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(vo, vr, rtol=1e-5, atol=1e-6)


# --- padding edge cases for the leaf -> (R, LANES) tiling -------------------
# sizes straddling every rounding rule: not LANES-divisible, exactly one
# block, and rows above BLOCK_ROWS that are NOT a block multiple (forces the
# round-up-to-block-multiple branch)
def _edge_shapes():
    from repro.kernels.adama_accum import BLOCK_ROWS, LANES
    return [(LANES - 1,), (LANES + 1,), (BLOCK_ROWS * LANES,),
            (BLOCK_ROWS * LANES + 13,), ((BLOCK_ROWS + 3) * LANES,)]


@pytest.mark.parametrize("shape", _edge_shapes())
def test_to_2d_roundtrip_and_padding(shape):
    from repro.kernels.adama_accum import BLOCK_ROWS, LANES
    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape) + 1.0
    arr, n = ops._to_2d(x)
    assert n == x.size and arr.shape[1] == LANES
    rows = arr.shape[0]
    assert rows * LANES >= n
    if rows > BLOCK_ROWS:
        assert rows % BLOCK_ROWS == 0, rows      # kernel grid divisibility
    flat = np.asarray(arr).reshape(-1)
    assert np.array_equal(flat[:n], np.asarray(x).reshape(-1))
    assert not flat[n:].any()                    # zero padding
    back = ops._from_2d(arr, n, x.shape, x.dtype)
    assert np.array_equal(np.asarray(back), np.asarray(x))


@pytest.mark.parametrize("shape", _edge_shapes())
@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
def test_accum_padding_edges_match_ref(shape, gdtype):
    m = jax.random.normal(jax.random.key(7), shape, jnp.float32)
    v = jnp.abs(jax.random.normal(jax.random.key(8), shape, jnp.float32))
    g = jax.random.normal(jax.random.key(9), shape, gdtype)
    mo, vo = ops.adama_accumulate(m, v, g, beta1=0.9, beta2=0.999, scale=0.5)
    mr, vr = ref.adama_accum_ref(m, v, g, beta1=0.9, beta2=0.999, scale=0.5)
    np.testing.assert_allclose(mo, mr, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(vo, vr, rtol=2e-6, atol=2e-6)


def test_kernels_jit_and_grad_free():
    """Kernels must be jit-compatible and not be traced through by autodiff
    (the optimizer path never differentiates them)."""
    m = jnp.zeros((128, 64))
    v = jnp.zeros((128, 64))
    g = jnp.ones((128, 64))
    mo, vo = jax.jit(lambda m, v, g: ops.adama_accumulate(
        m, v, g, beta1=0.9, beta2=0.999))(m, v, g)
    assert mo.shape == (128, 64) and bool(jnp.all(vo >= 0))


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    """Interpret on the CPU, compile on the TPU, and refuse any other backend
    rather than quietly interpreting the kernels there."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret
