"""Weights from the seed, made by the benchmark on the device.

The tree has the program's layout (the shapes `repro.models.model.
init_params` would give, read with `jax.eval_shape`), but its values are the
benchmark's own: each leaf is drawn from a key of its own, derived from the
seed and the leaf's index, and each layer of a stacked leaf from that key
folded with the layer. So the set-up makes all of them in one jitted call,
and the check and the reference can make any one leaf or layer again, bit
for bit, without keeping a copy.

Matrices are N(0, 0.02^2), output projections (`wo`, `w_down`) are scaled
down by sqrt(2 L) as GPT-2 does, norm scales are ones and norm biases zeros.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

STACK_KEYS = ("blocks", "dense_blocks", "enc_blocks")
OUT_PROJ = ("wo", "w_down")


class Leaf(NamedTuple):
    index: int
    name: str                 # "blocks/wq", "embed", ...
    shape: Tuple[int, ...]    # whole leaf, with the layer axis if stacked
    stacked: bool


def seed_key(seed: int):
    """A threefry key from all 64 bits of `seed` (jax.random.key keeps only
    32 of them without x64)."""
    seed = int(seed) % 2**64
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32),
        impl="threefry2x32")


def leaves_of(shapes) -> List[Leaf]:
    """Flatten a param tree (arrays or shapes) into named leaves, in
    `jax.tree.flatten` order."""
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    for i, (path, x) in enumerate(flat):
        keys = [p.key for p in path]
        out.append(Leaf(i, "/".join(keys), tuple(x.shape),
                        keys[0] in STACK_KEYS))
    return out


def _layer_values(key, name: str, shape, n_layers_total: int):
    base = name.rsplit("/", 1)[-1]
    if base.endswith("scale"):
        return jnp.ones(shape, jnp.float32)
    if base.endswith("bias"):
        return jnp.zeros(shape, jnp.float32)
    std = 0.02 / math.sqrt(2 * n_layers_total) if base in OUT_PROJ else 0.02
    return std * jax.random.normal(key, shape, jnp.float32)


def leaf_values(key, leaf: Leaf, n_layers_total: int, layers=None):
    """Values of one leaf, or of the given `layers` of a stacked leaf."""
    k = jax.random.fold_in(key, leaf.index)
    if not leaf.stacked:
        return _layer_values(k, leaf.name, leaf.shape, n_layers_total)
    idx = jnp.arange(leaf.shape[0]) if layers is None else jnp.asarray(layers)
    return jax.vmap(lambda j: _layer_values(
        jax.random.fold_in(k, j), leaf.name, leaf.shape[1:],
        n_layers_total))(idx)


def make_init(shapes, n_layers_total: int):
    """(init(seed_key) -> tree, leaves): one function for the whole tree,
    to be jitted by the caller with the output placement it needs."""
    leaves = leaves_of(shapes)
    treedef = jax.tree.structure(shapes)

    def init(key):
        return jax.tree.unflatten(treedef, [
            leaf_values(key, leaf, n_layers_total) for leaf in leaves])
    return init, leaves


def program_shapes(model) -> Dict[str, Any]:
    """The program's param tree as shapes (no allocation)."""
    from repro.models.model import init_params
    return jax.eval_shape(lambda: init_params(model, jax.random.key(0)))
