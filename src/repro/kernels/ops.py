"""jit-ready wrappers: flatten pytree leaves to hardware-aligned 2D tiles and
dispatch the Pallas kernels (interpret=True on CPU, compiled on TPU; any
other backend raises, see `_interpret`)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.adam_apply import adam_apply_2d
from repro.kernels.adama_accum import LANES, adama_accum_2d


def _interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode: True on the `cpu`
    backend (tests, CPU runs), False on `tpu`, where they compile to
    Mosaic. Any other backend raises instead of quietly interpreting the
    kernels on a device they were not written for."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and interpret on CPU only; "
        f"the default backend is {backend!r}")


def _to_2d(x):
    """Flatten + zero-pad to (R, LANES) with R a multiple of the row block.
    Returns (arr2d, orig_size)."""
    from repro.kernels.adama_accum import BLOCK_ROWS
    n = x.size
    rows = max(1, -(-n // LANES))
    if rows > BLOCK_ROWS:                       # round up to block multiple
        rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    pad = rows * LANES - n
    flat = x.reshape(-1)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES), n


def _from_2d(arr, n, shape, dtype):
    return arr.reshape(-1)[:n].reshape(shape).astype(dtype)


def adama_accumulate(m, v, g, *, beta1, beta2, scale=1.0):
    """Single-leaf fused fold; shapes preserved."""
    m2, nm = _to_2d(m.astype(jnp.float32))
    v2, _ = _to_2d(v.astype(jnp.float32))
    g2, _ = _to_2d(g)
    # pad rows so the block divides evenly (kernel asserts divisibility)
    mo, vo = adama_accum_2d(m2, v2, g2, beta1=beta1, beta2=beta2, scale=scale,
                            interpret=_interpret())
    return (_from_2d(mo, nm, m.shape, jnp.float32),
            _from_2d(vo, nm, v.shape, jnp.float32))


def adama_accumulate_tree(m_tree, v_tree, g_tree, *, beta1, beta2, scale=1.0):
    flat_m, tdef = jax.tree.flatten(m_tree)
    flat_v = tdef.flatten_up_to(v_tree)
    flat_g = tdef.flatten_up_to(g_tree)
    out = [adama_accumulate(m, v, g, beta1=beta1, beta2=beta2, scale=scale)
           for m, v, g in zip(flat_m, flat_v, flat_g)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]))


def adam_apply(p, m, v, *, lr, bc1, bc2, eps=1e-8, weight_decay=0.0):
    p2, n = _to_2d(p)
    m2, _ = _to_2d(m.astype(jnp.float32))
    v2, _ = _to_2d(v.astype(jnp.float32))
    po = adam_apply_2d(p2, m2, v2, lr=lr, bc1=bc1, bc2=bc2, eps=eps,
                       weight_decay=weight_decay, interpret=_interpret())
    return _from_2d(po, n, p.shape, p.dtype)


def adam_apply_tree(params, m_tree, v_tree, *, lr, bc1, bc2, eps=1e-8,
                    weight_decay=0.0):
    return jax.tree.map(
        functools.partial(adam_apply, lr=lr, bc1=bc1, bc2=bc2, eps=eps,
                          weight_decay=weight_decay),
        params, m_tree, v_tree)
