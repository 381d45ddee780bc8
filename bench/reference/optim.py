"""Reference optimizer steps: AdamA (paper Algorithm 1) and Adam on the
gradient summed over micro-batches (gradient accumulation, "ga"), followed
through the first steps of a run from the same weights and batches as the
program, in float32.

AdamA, per step: m <- b1 m, v <- b2 v; for each of the n micro-batches
m += (1-b1) g_i/n and v += (1-b2) (g_i/n)^2; then t += 1 and
p -= lr (m / (1-b1^t)) / (sqrt(v / (1-b2^t)) + eps).
ga, per step: g = sum_i g_i/n; m <- b1 m + (1-b1) g; v <- b2 v + (1-b2) g^2;
then the same update.

Where the configuration keeps the moments in int8 (`m_codec`/`v_codec`
"int8"), each moment is rounded after every fold as the configuration
states: per row of 1024 consecutive elements of a leaf (of one layer, for
a stacked leaf; the last row zero-padded), scale = row max |x| / 127, m
rounded toward zero and v rounded up to a multiple of the scale. With a
bf16 gradient wire (`wire` "bf16") each micro-batch's gradient is rounded
to bf16 before it is folded.

On several chips (`spread`) only the placement changes: the weights, the
moments and each micro-batch's gradient are split over the chips leaf by
leaf (`Spread.leaf`), and each micro-batch along its batch axis; the
compiler partitions the same float32 arithmetic over them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reference.common import placed_layers

ENGINES = ("adama", "ga")
LANES = 1024
Q8 = 127.0


def _rows(x, stacked):
    lead = x.shape[:1] if stacked else ()
    flat = x.reshape(lead + (-1,))
    pad = (-flat.shape[-1]) % LANES
    flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, pad)])
    return flat.reshape(lead + (-1, LANES))


def _unrows(r, x, stacked):
    lead = x.shape[:1] if stacked else ()
    n = x.size // (x.shape[0] if stacked else 1)
    return r.reshape(lead + (-1,))[..., :n].reshape(x.shape)


class Spread:
    """Where the reference keeps its state on several chips. A leaf of at
    least MIN_BYTES is split along its first axis that the chips divide,
    after the layer axis of a stacked leaf (which the layer scan walks), so
    that each chip holds a run of consecutive elements of each layer and
    the int8 rows of 1024 of them stay on one chip; a smaller leaf, or one
    with no such axis, is held whole on every chip. A micro-batch is split
    along its batch axis."""

    MIN_BYTES = 1 << 20

    def __init__(self, devices):
        self.chips = len(devices)
        self.mesh = Mesh(np.array(devices), ("chips",))
        self.batch = NamedSharding(self.mesh, P("chips"))

    def leaf(self, shape, stacked: bool) -> NamedSharding:
        """Where a float32 leaf of `shape` lies."""
        spec = [None] * len(shape)
        if 4 * int(np.prod(shape)) >= self.MIN_BYTES:
            axes = [a for a in range(1 if stacked else 0, len(shape))
                    if shape[a] % self.chips == 0]
            if axes:
                spec[axes[0]] = "chips"
        return NamedSharding(self.mesh, P(*spec))

    def layer(self, x, weights, n_layers: int):
        """One layer's input and weights inside the layer scan: the input
        split along its batch axis, each weight where `leaf` puts that
        layer of the stacked leaf."""
        def pin(w):
            spec = self.leaf((n_layers,) + w.shape, True).spec[1:]
            return jax.lax.with_sharding_constraint(
                w, NamedSharding(self.mesh, P(*spec)))
        return (jax.lax.with_sharding_constraint(x, self.batch),
                jax.tree.map(pin, weights))


def int8_round(x, stacked, signed):
    """x as its per-row int8 code decodes: toward zero when `signed` (m),
    up when not (v)."""
    r = _rows(x, stacked)
    rowmax = jnp.max(jnp.abs(r), axis=-1, keepdims=True)
    s = rowmax / Q8
    s = jnp.where((s == 0.0) & (rowmax > 0.0), rowmax, s)
    safe = jnp.where(s > 0.0, s, 1.0)
    q = (jnp.clip(jnp.trunc(r / safe), -Q8, Q8) if signed
         else jnp.clip(jnp.ceil(r / safe), 0.0, Q8))
    return _unrows(q * s, x, stacked)


def run_steps(loss_fn, weights, step_batches, *, n_micro: int, engine: str,
              lr: float, beta1: float, beta2: float, eps: float,
              on_step=None, batch_filter=None, leaf_groups: int = 1,
              stacked=None, m_codec: str = "fp32", v_codec: str = "fp32",
              wire: str = "fp32", spread: Optional[Spread] = None):
    """Follow len(step_batches) optimizer steps from `weights`.

    `loss_fn(weights, micro_batch)` is the reference model's loss.
    `step_batches[s]` is step s's global batch (arrays with a leading batch
    axis), split into `n_micro` equal micro-batches in order, as the
    program splits it. `on_step(s, weights, m, v, loss)` is called after
    each step with the state it left. Returns the per-step losses: each the
    mean of the micro-batches' losses. `batch_filter(batch)` alters each
    step's batch before it is split (the control's faults use it).

    `leaf_groups > 1` takes each micro-batch's gradient in that many
    backward passes, each over a group of leaves, so that no more than one
    group's gradient is held at a time (AdamA only: ga holds the summed
    gradient whole anyway). The arithmetic is the same.

    `stacked[i]` says whether leaf i (in `jax.tree.flatten` order) is
    stacked over layers; the int8 moments need it for their rows.

    `spread` places the state over several chips (see `Spread`). Without
    it everything stays where the weights are.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}")
    if engine != "adama" and (leaf_groups > 1 or wire != "fp32"
                              or (m_codec, v_codec) != ("fp32", "fp32")):
        raise ValueError("leaf groups, int8 moments and a bf16 wire need "
                         "the adama engine")
    flat, treedef = jax.tree.flatten(weights)
    groups = _groups([x.size for x in flat], leaf_groups)
    stacked = tuple(stacked) if stacked is not None else (False,) * len(flat)
    for codec in (m_codec, v_codec):
        if codec not in ("fp32", "int8"):
            raise ValueError(f"unknown moment codec {codec!r}")

    if spread is None:
        def pin(xs, idx):
            return xs
    else:
        where = [spread.leaf(x.shape, st) for x, st in zip(flat, stacked)]

        def pin(xs, idx):
            return [jax.lax.with_sharding_constraint(x, where[i])
                    for x, i in zip(xs, idx)]
    every = tuple(range(len(flat)))

    def wire_round(g):
        return g.astype(jnp.bfloat16).astype(jnp.float32) \
            if wire == "bf16" else g

    def m_round(x, st):
        return int8_round(x, st, True) if m_codec == "int8" else x

    def v_round(x, st):
        return int8_round(x, st, False) if v_codec == "int8" else x

    def group_loss(sub, rest, mb, idx):
        full = list(rest)
        for i, x in zip(idx, sub):
            full[i] = x
        return loss_fn(jax.tree.unflatten(treedef, full), mb)

    @functools.partial(jax.jit, static_argnums=(3,))
    def grad(sub, rest, mb, idx):
        with placed_layers(None if spread is None else spread.layer):
            loss, g = jax.value_and_grad(group_loss)(sub, rest, mb, idx)
        return loss, pin(g, idx)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scale(t, c):
        return pin([c * x for x in t], every)

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       static_argnums=(3,))
    def fold(m, v, g, idx):
        g = [wire_round(x) / n_micro for x in g]
        st = [stacked[i] for i in idx]
        return (pin([m_round(a + (1 - beta1) * b, k)
                     for a, b, k in zip(m, g, st)], idx),
                pin([v_round(a + (1 - beta2) * jnp.square(b), k)
                     for a, b, k in zip(v, g, st)], idx))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, g):
        return pin([a + b / n_micro for a, b in zip(acc, g)], every)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def ga_moments(m, v, g):
        return (pin([beta1 * a + (1 - beta1) * b for a, b in zip(m, g)],
                    every),
                pin([beta2 * a + (1 - beta2) * b * b for a, b in zip(v, g)],
                    every))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def update(w, m, v, t):
        bc1 = 1 - beta1 ** t
        bc2 = 1 - beta2 ** t
        return pin([p - lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps)
                    for p, a, b in zip(w, m, v)], every)

    zeros = jax.jit(lambda t: pin([jnp.zeros_like(x) for x in t], every))
    w = flat if spread is None else jax.device_put(flat, where)
    m, v = zeros(w), zeros(w)
    losses = []
    for s, batch in enumerate(step_batches):
        if batch_filter is not None:
            batch = batch_filter(batch)
        rows = jax.tree.leaves(batch)[0].shape[0]
        per = rows // n_micro
        micro = [jax.tree.map(lambda x: x[i * per:(i + 1) * per], batch)
                 for i in range(n_micro)]
        if spread is not None:
            micro = [jax.device_put(mb, spread.batch) for mb in micro]
        lsum = 0.0
        if engine == "adama":
            m, v = scale(m, beta1), scale(v, beta2)
            for mb in micro:
                for k, idx in enumerate(groups):
                    l, g = grad([w[i] for i in idx], w, mb, idx)
                    mg, vg = fold([m[i] for i in idx], [v[i] for i in idx],
                                  g, idx)
                    for i, a, b in zip(idx, mg, vg):
                        m[i], v[i] = a, b
                    del g
                    if k == 0:
                        lsum += float(l)
        else:
            acc = zeros(w)
            idx = groups[0]
            for mb in micro:
                l, g = grad(w, w, mb, idx)
                acc = add(acc, g)
                lsum += float(l)
                del g
            m, v = ga_moments(m, v, acc)
            del acc
        w = update(w, m, v, jnp.float32(s + 1))
        losses.append(lsum / n_micro)
        if on_step is not None:
            on_step(s, jax.tree.unflatten(treedef, w),
                    jax.tree.unflatten(treedef, m),
                    jax.tree.unflatten(treedef, v), losses[-1])
    return losses


def _groups(sizes, n):
    """Split leaf indices, in order, into at most n groups of about equal
    total size."""
    if n <= 1:
        return [tuple(range(len(sizes)))]
    target = sum(sizes) / n
    out, cur, tot = [], [], 0
    for i, sz in enumerate(sizes):
        cur.append(i)
        tot += sz
        if tot >= target and len(out) < n - 1:
            out.append(tuple(cur))
            cur, tot = [], 0
    if cur:
        out.append(tuple(cur))
    return out
