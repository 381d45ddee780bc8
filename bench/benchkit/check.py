"""The comparison that decides `correct`, on a training cell.

Three numbers are compared with the float32 reference, each against its
limit in bench/limits/<cell>.json:

  loss_gap    the largest, over the first three steps, of
              |loss - loss_ref| / loss_ref.
  grad_gap    the first step's gradient as the optimizer holds it, worked
              out from its first moment after one step (m / (1 - beta1)):
              the largest, over (leaf, layer), of
              | |g| - |g_ref| | / max(|g_ref|, median over leaves of |g_ref|).
  change_gap  the parameters' change over the first three steps, as step 4
              receives them: the same gap of norms per (leaf,
              layer), leaving out each (leaf, layer) whose reference
              gradient norm is under 1e-3 of the median one (those move
              under Adam by round-off alone).

Norms are taken per leaf and per layer of a stacked leaf ("blocks/wq[7]").
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchkit.weights import Leaf, leaf_values

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
EXCLUDE_BELOW = 1e-3           # of the median leaf's reference gradient


def _sumsq(x, stacked: bool):
    x = x.astype(jnp.float32)
    if stacked:
        return jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))
    return jnp.sum(jnp.square(x)).reshape(1)


@functools.partial(jax.jit, static_argnums=(1,))
def _tree_sumsq(flat, stacked):
    return [_sumsq(x, st) for x, st in zip(flat, stacked)]


def tree_sumsq(tree, leaves: Iterable[Leaf]) -> Dict[str, np.ndarray]:
    """{leaf name: sums of squares per layer} of a tree with the program's
    layout."""
    leaves = list(leaves)
    flat = jax.tree.leaves(tree)
    stacked = tuple(lf.stacked for lf in leaves)
    out = jax.device_get(_tree_sumsq(flat, stacked))
    return {lf.name: np.asarray(o, np.float64) for lf, o in zip(leaves, out)}


def arena_sumsq(row_sumsq: np.ndarray, layout, leaves: Iterable[Leaf]
                ) -> Dict[str, np.ndarray]:
    """{leaf name: sums of squares per layer} from the per-row sums of
    squares of a packed (rows, lanes) arena and its layout. Tail lanes of a
    leaf's last row are zero in the arena, so row sums add up exactly."""
    leaves = list(leaves)
    out = {}
    csum = np.concatenate([[0.0], np.cumsum(row_sumsq, dtype=np.float64)])

    def rng(a, n):
        return csum[a + n] - csum[a]

    for spec in layout.stacks:
        names = [lf.name for lf in leaves
                 if lf.name.split("/", 1)[0] == spec.name]
        assert len(names) == len(spec.leaves), (spec.name, names)
        for name, ls in zip(names, spec.leaves):
            out[name] = np.array([
                rng(spec.row + j * spec.layer_rows + ls.row, ls.rows)
                for j in range(spec.n_layers)])
    rest = [lf.name for lf in leaves if not lf.stacked]
    assert len(rest) == len(layout.rest.leaves), rest
    for name, ls in zip(rest, layout.rest.leaves):
        out[name] = np.array([rng(layout.rest.row + ls.row, ls.rows)])
    return out


def change_sumsq(params, key, leaves: Iterable[Leaf], n_layers_total: int
                 ) -> Dict[str, np.ndarray]:
    """{leaf name: per-layer sums of squares of params - p0}, where p0 is
    made again from the seed's key one leaf at a time (never held whole)."""
    out = {}
    for leaf, p in zip(leaves, jax.tree.leaves(params)):
        f = jax.jit(lambda x, k, lf=leaf: _sumsq(
            x.astype(jnp.float32) - leaf_values(k, lf, n_layers_total),
            lf.stacked))
        out[leaf.name] = np.asarray(jax.device_get(f(p, key)), np.float64)
    return out


def flat(d: Dict[str, np.ndarray]) -> Dict[str, float]:
    out = {}
    for name, arr in d.items():
        arr = np.asarray(arr, np.float64).reshape(-1)
        if arr.size == 1:
            out[name] = float(arr[0])
        else:
            for j, x in enumerate(arr):
                out[f"{name}[{j}]"] = float(x)
    return out


def norm_gap(prog_sq: Dict[str, np.ndarray], ref_sq: Dict[str, np.ndarray],
             *, scale: float = 1.0, keep=None) -> Tuple[float, str]:
    """(worst gap, its leaf) between per-(leaf, layer) norms. `scale`
    multiplies the program's norms; `keep` is the set of (leaf, layer)
    names counted (all when None)."""
    p, r = flat(prog_sq), flat(ref_sq)
    if set(p) != set(r):
        raise ValueError(f"leaf sets differ: {sorted(set(p) ^ set(r))[:5]}")
    names = sorted(r) if keep is None else sorted(keep)
    rn = {k: np.sqrt(r[k]) for k in r}
    med = float(np.median([rn[k] for k in names]))
    worst, which = 0.0, ""
    for k in names:
        gap = abs(scale * np.sqrt(p[k]) - rn[k]) / max(rn[k], med)
        if not gap <= worst:           # NaN counts as worst
            worst, which = float(gap), k
            if np.isnan(gap):
                break
    return worst, which


def moving(ref_grad_sq: Dict[str, np.ndarray]):
    """(leaf, layer) names whose reference gradient norm is at least
    EXCLUDE_BELOW of the median one."""
    g = {k: np.sqrt(v) for k, v in flat(ref_grad_sq).items()}
    med = float(np.median(list(g.values())))
    return {k for k, x in g.items() if x >= EXCLUDE_BELOW * med}


def loss_gap(losses, ref_losses) -> float:
    losses = np.asarray(losses, np.float64)
    ref = np.asarray(ref_losses, np.float64)
    if losses.shape != ref.shape:
        return float("inf")
    gap = np.abs(losses - ref) / np.abs(ref)
    return float(np.nan if np.isnan(gap).any() else gap.max())


def verdict(numbers: Dict[str, float], limits: Dict[str, dict]) -> bool:
    """True when every number is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]["limit"]
               for k in NUMBERS)
