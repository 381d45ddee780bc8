"""State-store layer over the flat optimizer arena: pluggable codecs for
BOTH Adam moments (the paper's Table-3 composition — AdamA for
activation/gradient memory x optimizer-state reduction for (m, v)).

The arena (core/arena.py) stores Adam's moments as flat (rows, LANES)
buffers. This module generalizes EACH moment into codec-encoded arena
columns; a training configuration picks an (m_codec, v_codec) pair and every
registered pair runs through the same three builder-generated kernels
(kernels/fused_step.py) at O(1) dispatches per micro-batch.

First-moment codecs (m is SIGNED and carries the update direction):

  fp32      (rows, LANES) fp32                   exact; default. 4 B/param.
  int8      (rows, LANES) int8 + (rows, 1) fp32  per-row symmetric quant
            scales                               over codes [-127, 127],
            rounding TOWARD ZERO so |m_hat| <= |m| — the update magnitude
            is only ever damped, never amplified (cf. MicroAdam, Modoranu
            et al. 2024). ~1 B/param; error one-sided toward zero,
            |m - m_hat| <= rowmax(|m|)/127 per element per fold.

Second-moment codecs (v >= 0, sits under the square root):

  fp32      (rows, LANES) fp32                   exact; default.
  int8      (rows, LANES) int8 + (rows, 1) fp32  CEIL quantization, codes
            [0, 127]: 0 <= v_hat - v <= rowmax/127 (never-amplify).
  factored  (rows, 1) fp32                       SM3-style per-row upper
            bound (lane-dim max); ~4/1024 B/param. v_hat >= v is the SM3
            cover-set guarantee, one cover per arena row.
  rowcol    (rows, 1) + (1, LANES) fp32          TRUE row x col rank-1
            factorization (Adafactor, Shazeer & Stern 2018): row sums
            (row-indexed) + column sums (a replicated accumulator), with
            v_hat = vr vc^T / sum(vc). ~2/1024 the memory of fp32 v at the
            full-matrix accuracy bound (exact when v is rank one; marginals
            always preserved exactly). The column sums are the ONE state
            column that is not row-indexed: under ZeRO-1 each row-range
            shard keeps a replica and contributes its partial column sums,
            combined by a single tiny (1, LANES) psum per mini-batch
            (core/dp_shardmap.py); its decay is applied OUTSIDE the kernel,
            once per micro-batch, so per-layer slice folds cannot decay the
            shared column twice.

All OTHER codec state is row-indexed, which is what makes ZeRO-1 row-range
sharding (core/zero.py::shard_rows) compose with every codec: a shard is
rows [k*R/M, (k+1)*R/M) of every row-indexed column, and the collectives are
a gradient reduce-scatter plus a param all-gather over the same ranges.

Each codec also DECLARES its conformance contract (`Conformance`): the
documented Adam-parity drift, whether updates can never be amplified, and
whether all its state is row-local. tests/test_codec_conformance.py is
parameterized over `registered_combinations()` and enforces exactly the
declared contract — adding a codec means adding a registry entry with
tolerances, not new tests.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FOLD_SCOPE
from repro.core import arena as arena_mod
from repro.core.arena import Arena, ArenaLayout
from repro.kernels.adama_accum import LANES


@jax.tree_util.register_pytree_node_class
class MomentState:
    """A codec-encoded Adam moment: a tuple of codec columns plus static
    (layout, codec name, moment) aux data. Mirrors Arena's pytree contract
    so it flows through jit / scan / donation / checkpointing — and because
    the aux data rides in the treedef, restoring a checkpoint onto a
    different codec (or onto the other moment) fails loudly."""

    def __init__(self, parts: Tuple[jnp.ndarray, ...], layout: ArenaLayout,
                 codec: str, moment: str = "v"):
        self.parts = tuple(parts)
        self.layout = layout
        self.codec = codec
        self.moment = moment

    def tree_flatten(self):
        return self.parts, (self.layout, self.codec, self.moment)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children), *aux)

    def with_parts(self, parts) -> "MomentState":
        return MomentState(tuple(parts), self.layout, self.codec, self.moment)

    def decode(self) -> jnp.ndarray:
        """Reconstruct the (rows, LANES) fp32 moment arena."""
        return get_codec(self.codec, self.moment).decode(self.parts)

    def to_tree(self, dtype=None):
        """Decode and unpack to the parameter-tree structure (parity/debug)."""
        return arena_mod.unpack(self.decode(), self.layout, dtype)

    def __repr__(self):
        return (f"MomentState({self.moment}_codec={self.codec!r}, "
                f"rows={self.layout.rows}, "
                f"parts={[tuple(p.shape) for p in self.parts]})")


@dataclass(frozen=True)
class Conformance:
    """The codec's DECLARED accuracy contract, enforced verbatim by
    tests/test_codec_conformance.py on every registered combination."""
    # elementwise |p - p_fp32| after one mini-batch, in units of lr;
    # None = no elementwise parity bound (lossy statistic codec — the
    # harness falls back to the structural contracts below)
    drift_lr: Optional[float]
    # elementwise |p(bf16 wire) - p(fp32 wire)| after one mini-batch, in
    # units of lr, same codec pair both sides: the drift the bf16 gradient
    # wire (OptimizerConfig.grad_dtype) may add. The wire perturbs g by at
    # most one bf16 ulp (~2^-8 relative) BEFORE the fp32 in-kernel upcast,
    # so for continuous codecs the drift is a small fraction of lr; quantized
    # codecs can flip a code boundary and inherit their own drift scale.
    bf16_wire_lr: float
    # |p_new - p_0| <= |p_new_fp32 - p_0| elementwise (updates only damped).
    # This is a PER-FOLD guarantee: a signed m shrunk toward zero on fold i
    # can overshoot the fp32 trajectory past zero when fold i+1's gradient
    # flips sign, so the harness checks it on single-fold mini-batches;
    # multi-fold drift is bounded by drift_lr instead.
    never_amplify: bool
    # every column row-indexed -> bitwise row-range shard parity
    row_local: bool
    # adama vs adama_layerwise engine parity on the same codec pair
    engine_tol: float
    # elementwise |p(fp8+EF wire) - p(fp32 wire)| after one mini-batch, in
    # units of lr, same codec pair both sides: the drift the fp8 (e4m3)
    # gradient wire WITH its error-feedback residual may add. e4m3's
    # mantissa step is 2^-4 of the row max (16x coarser than bf16), but the
    # residual state["ef"] re-injects each fold's quantization error into
    # the next micro-batch's pre-quantization gradient, so the declared
    # bound is well under the naive 16x-of-bf16 scaling. Defaulted (last
    # field) so pre-fp8 Conformance call sites stay source-compatible;
    # every registered codec declares it explicitly.
    fp8_wire_lr: float = 4.0


class MomentCodec:
    """Host-side half of a codec: storage init/wrap/decode and the
    codec-space decay. The kernel-side half (column list + fold/decode
    fragments) is `self.kernel`, consumed by the fused_step builders.
    `parts` is always a tuple of arrays so engines can carry it through
    lax.scan without knowing the codec."""

    name: str = "?"
    moment: str = "?"
    conformance: Conformance = None

    @property
    def kernel(self):
        from repro.kernels.fused_step import kernel_codec
        return kernel_codec(self.moment, self.name)

    def init(self, layout: ArenaLayout):
        raise NotImplementedError

    def parts_of(self, state) -> Tuple[jnp.ndarray, ...]:
        raise NotImplementedError

    def wrap(self, layout: ArenaLayout, parts):
        raise NotImplementedError

    def decode(self, parts) -> jnp.ndarray:
        """Full (rows, LANES) fp32 reconstruction (host/debug/parity)."""
        rows = parts[0].shape[0]
        return jnp.broadcast_to(self.kernel.decode(tuple(parts)),
                                (rows, LANES))

    def scale_state(self, state, c):
        """state <- c * state, in codec space (begin-minibatch decay)."""
        raise NotImplementedError

    def begin_micro(self, parts, decay):
        """Decay the REPLICATED (non-row-indexed) columns, once per
        micro-batch. Row-indexed columns decay inside the fold kernel (each
        row is folded exactly once per micro-batch); a shared column would
        be decayed once per slice fold, so it is decayed here instead.
        Identity for codecs whose state is fully row-indexed."""
        del decay
        return parts

    def psum_replicated(self, parts, axis_names):
        """Sum the replicated columns' per-shard partials across a device
        axis (ZeRO-1 row-range schedule). Identity for row-local codecs."""
        del axis_names
        return parts


class Fp32Codec(MomentCodec):
    """Identity codec: the moment is a full-precision Arena (PR-1 form)."""

    name = "fp32"
    conformance = Conformance(drift_lr=0.0, never_amplify=True,
                              row_local=True, engine_tol=5e-6,
                              bf16_wire_lr=0.25, fp8_wire_lr=2.0)

    def __init__(self, moment: str):
        self.moment = moment

    def init(self, layout):
        return Arena.zeros(layout)

    def parts_of(self, state):
        return (state.data,)

    def wrap(self, layout, parts):
        return Arena(parts[0], layout)

    def scale_state(self, state, c):
        return state.with_data(c * state.data)


class Int8Codec(MomentCodec):
    """(rows, LANES) int8 codes + (rows, 1) fp32 per-row scales. The m
    variant quantizes toward zero over [-127, 127]; the v variant CEILs
    over [0, 127] — both one-sided, both never-amplify."""

    name = "int8"
    conformance = Conformance(drift_lr=2.0, never_amplify=True,
                              row_local=True, engine_tol=2e-3,
                              bf16_wire_lr=2.0, fp8_wire_lr=4.0)

    def __init__(self, moment: str):
        self.moment = moment

    def init(self, layout):
        return MomentState((jnp.zeros((layout.rows, LANES), jnp.int8),
                            jnp.zeros((layout.rows, 1), jnp.float32)),
                           layout, self.name, self.moment)

    def parts_of(self, state):
        return state.parts

    def wrap(self, layout, parts):
        return MomentState(tuple(parts), layout, self.name, self.moment)

    def scale_state(self, state, c):
        # c * (q * s) == q * (c * s): decay touches only the scale column
        return state.with_parts((state.parts[0], c * state.parts[1]))


class FactoredCodec(MomentCodec):
    """v as a single (rows, 1) fp32 per-row statistic (SM3-style)."""

    name = "factored"
    conformance = Conformance(drift_lr=None, never_amplify=True,
                              row_local=True, engine_tol=5e-6,
                              bf16_wire_lr=1.0, fp8_wire_lr=2.0)

    moment = "v"

    def init(self, layout):
        return MomentState((jnp.zeros((layout.rows, 1), jnp.float32),),
                           layout, self.name, self.moment)

    def parts_of(self, state):
        return state.parts

    def wrap(self, layout, parts):
        return MomentState(tuple(parts), layout, self.name, self.moment)

    def scale_state(self, state, c):
        return state.with_parts((c * state.parts[0],))


class RowColCodec(MomentCodec):
    """v as its rank-1 marginals: (rows, 1) row sums + (1, LANES) column
    sums, v_hat = vr vc^T / sum(vc). The rank-1 reconstruction can sit
    UNDER the true v elementwise (exact only for rank-one v), so this codec
    does NOT declare never-amplify; its contracts are the Adafactor ones —
    exact marginals and exact reconstruction of rank-one moments (pinned by
    tests/test_codec_properties.py)."""

    name = "rowcol"
    conformance = Conformance(drift_lr=None, never_amplify=False,
                              row_local=False, engine_tol=2e-3,
                              bf16_wire_lr=1.0, fp8_wire_lr=2.0)

    moment = "v"

    def init(self, layout):
        return MomentState((jnp.zeros((layout.rows, 1), jnp.float32),
                            jnp.zeros((1, LANES), jnp.float32)),
                           layout, self.name, self.moment)

    def parts_of(self, state):
        return state.parts

    def wrap(self, layout, parts):
        return MomentState(tuple(parts), layout, self.name, self.moment)

    def scale_state(self, state, c):
        # both marginals are linear in v
        return state.with_parts((c * state.parts[0], c * state.parts[1]))

    def begin_micro(self, parts, decay):
        return (parts[0], decay * parts[1])

    def psum_replicated(self, parts, axis_names):
        return (parts[0], jax.lax.psum(parts[1], axis_names))


M_CODECS = {c.name: c for c in (Fp32Codec("m"), Int8Codec("m"))}
V_CODECS = {c.name: c for c in (Fp32Codec("v"), Int8Codec("v"),
                                FactoredCodec(), RowColCodec())}
_REGISTRIES = {"m": M_CODECS, "v": V_CODECS}


def get_codec(name: str, moment: str = "v") -> MomentCodec:
    if isinstance(name, MomentCodec):
        return name
    reg = _REGISTRIES[moment]
    try:
        return reg[name]
    except KeyError:
        raise KeyError(f"unknown {moment}-codec {name!r}; "
                       f"available: {sorted(reg)}") from None


def codec_of(state, moment: str = "v") -> MomentCodec:
    """The codec backing an arena-backed moment state object."""
    if isinstance(state, Arena):
        return _REGISTRIES[moment]["fp32"]
    if isinstance(state, MomentState):
        return _REGISTRIES[state.moment][state.codec]
    raise TypeError(f"not an arena-backed moment: {type(state)!r}")


def is_arena_backed(state) -> bool:
    return isinstance(state, (Arena, MomentState))


def registered_combinations() -> Tuple[Tuple[str, str], ...]:
    """Every (m_codec, v_codec) pair the store supports — the conformance
    suite, kernel_bench guards and capability matrix all iterate this."""
    return tuple((m, v) for m in sorted(M_CODECS) for v in sorted(V_CODECS))


# ---------------------------------------------------------------------------
# Pair-level fused ops: ONE kernel updates both moments
# ---------------------------------------------------------------------------


def _decay_pair(decay):
    return (1.0, 1.0) if decay is None else decay


def _resolve_guard(guard, g):
    """None -> unguarded. True -> self-check: finite flag over the packed
    slab, computed BEFORE anything (kernel write or replicated decay)
    commits. A traced array (the psum-agreed flag under shard_map) passes
    through verbatim."""
    if guard is None:
        return None
    if guard is True:
        return jnp.isfinite(g).all()
    return guard


def _guarded_begin_micro(codec, parts, decay, flag):
    """begin_micro with the replicated-column decay predicated on the
    finite flag: a skipped micro-batch must be a BITWISE no-op, and the
    rowcol column sums decay outside the kernel — so the decayed and
    original parts are `where`-selected instead of multiplying by a
    conditional 1.0 (x*1.0 is not a bitwise identity for all floats)."""
    parts = tuple(parts)
    decayed = codec.begin_micro(parts, decay)
    if flag is None or decayed is parts:
        return decayed
    return tuple(jnp.where(flag, d, o) for d, o in zip(decayed, parts))


def fold(m_codec, v_codec, m_parts, v_parts, g, *, beta1, beta2, scale=1.0,
         decay=None, replicated_decay=None, grad_dtype=None, grad_scale=None,
         guard=None):
    """Whole-arena fold of one micro-batch's gradient arena into both
    moments: one fused pallas_call. `decay=(dm, dv)` fuses the
    begin-minibatch decay (row-indexed columns decay in-kernel; replicated
    columns decay here, outside). `replicated_decay` overrides the decay of
    replicated columns only — the ZeRO-1 schedule passes dv/M so that the
    per-shard partial column sums psum to the exact global statistic.
    `g` may ride the bf16 wire (upcast in-kernel, fp32 accumulation);
    `grad_dtype` pins the caller's CONFIGURED wire against the slab it
    actually packed (a pack site that dropped the dtype fails loudly
    instead of silently widening the wire).

    An fp8 wire slab additionally carries its per-row `grad_scale` column
    (decode fused in-kernel; see kernels/fused_step).

    `guard` (True = self-check the slab, traced array = use verbatim)
    makes the whole fold — in-kernel writes AND the outside-the-kernel
    replicated decay — a bitwise no-op when the flag is false, and the
    return becomes (m_parts, v_parts, flag)."""
    mc, vc = get_codec(m_codec, "m"), get_codec(v_codec, "v")
    from repro.kernels import fused_step
    with jax.named_scope(FOLD_SCOPE):
        flag = _resolve_guard(guard, g)
        if decay is not None or replicated_decay is not None:
            rdm, rdv = _decay_pair(decay if replicated_decay is None
                                   else replicated_decay)
            m_parts = _guarded_begin_micro(mc, m_parts, rdm, flag)
            v_parts = _guarded_begin_micro(vc, v_parts, rdv, flag)
        return fused_step.arena_fold(tuple(m_parts), tuple(v_parts), g,
                                     beta1=beta1, beta2=beta2, scale=scale,
                                     decay=decay, m_codec=mc.kernel,
                                     v_codec=vc.kernel, grad_dtype=grad_dtype,
                                     grad_scale=grad_scale, guard=flag)


def fold_slice(m_codec, v_codec, m_parts, v_parts, g, row_offset, *,
               beta1, beta2, block, scale=1.0, decay=None, grad_dtype=None,
               grad_scale=None, guard=None):
    """Fold a gradient slab into rows [row_offset, row_offset+rows_g).
    Unlike `fold`, replicated columns are NOT decayed here — a micro-batch
    is many slice folds, so the engine decays them once per micro-batch via
    `codec.begin_micro` (see core/layerwise.py). `grad_dtype` as in
    `fold`: the declared wire is validated against the slab. `guard` as in
    `fold` (the return gains the flag); slice-fold callers predicate their
    own begin_micro decay with the same flag."""
    mc, vc = get_codec(m_codec, "m"), get_codec(v_codec, "v")
    from repro.kernels import fused_step
    with jax.named_scope(FOLD_SCOPE):
        return fused_step.arena_fold_slice(
            tuple(m_parts), tuple(v_parts), g, row_offset, beta1=beta1,
            beta2=beta2, block=block, scale=scale, decay=decay,
            m_codec=mc.kernel, v_codec=vc.kernel, grad_dtype=grad_dtype,
            grad_scale=grad_scale, guard=_resolve_guard(guard, g))


def apply(m_codec, v_codec, p, m_parts, v_parts, *, lr, bc1, bc2, eps=1e-8,
          weight_decay=0.0, work_dtype=None, guard=None):
    """Bias-corrected apply over the packed param arena, decoding both
    moments in-pass; p aliased in-place. With `work_dtype`, `p` is the fp32
    master region and the kernel also emits the `work_dtype` working params
    — returns (master_new, work) instead of the single updated arena.
    `guard` (traced bool): when false the params pass through bitwise
    (all-skipped mini-batch -> identity apply)."""
    mc, vc = get_codec(m_codec, "m"), get_codec(v_codec, "v")
    from repro.kernels import fused_step
    return fused_step.arena_apply(p, tuple(m_parts), tuple(v_parts), lr=lr,
                                  bc1=bc1, bc2=bc2, eps=eps,
                                  weight_decay=weight_decay,
                                  m_codec=mc.kernel, v_codec=vc.kernel,
                                  work_dtype=work_dtype, guard=guard)


# ---------------------------------------------------------------------------
# State-dict-level helpers (state = {"m": ..., "v": ..., "step": ...}, plus
# an optional "p" master-param Arena — extra keys always pass through)
# ---------------------------------------------------------------------------


def state_codecs(state) -> Tuple[MomentCodec, MomentCodec]:
    return codec_of(state["m"], "m"), codec_of(state["v"], "v")


def has_master(state) -> bool:
    """Whether the state dict carries the fp32 master-param region
    (OptimizerConfig.master_params; see apply_master_state)."""
    return "p" in state


def fold_state(state, g, *, beta1, beta2, scale=1.0, decay=None,
               replicated_decay=None, grad_dtype=None, grad_scale=None,
               guard=None):
    """One fused fold of a packed gradient arena into the state dict.
    With `guard` the return is (new_state, flag) — see `fold`."""
    mc, vc = state_codecs(state)
    layout = state["m"].layout
    out = fold(mc, vc, mc.parts_of(state["m"]),
               vc.parts_of(state["v"]), g, beta1=beta1,
               beta2=beta2, scale=scale, decay=decay,
               replicated_decay=replicated_decay,
               grad_dtype=grad_dtype, grad_scale=grad_scale, guard=guard)
    m_parts, v_parts = out[0], out[1]
    new = dict(state, m=mc.wrap(layout, m_parts),
               v=vc.wrap(layout, v_parts))
    return (new, out[2]) if len(out) == 3 else new


def begin_micro_state(state, decay, guard=None):
    """Apply this micro-batch's decay pair to the REPLICATED codec columns
    only (e.g. rowcol's column sums) — row-indexed columns decay inside the
    fold kernels. The bucketed ZeRO-1 schedule calls this once per
    micro-batch before its per-bucket slice folds, exactly as the layer-wise
    engine does before its backward scan; identity for row-local codecs.
    `guard` (traced bool, e.g. the psum-agreed finite flag) predicates the
    decay — a skipped micro-batch leaves the replicated columns bitwise."""
    if decay is None:
        return state
    mc, vc = state_codecs(state)
    layout = state["m"].layout
    return dict(state,
                m=mc.wrap(layout, _guarded_begin_micro(
                    mc, mc.parts_of(state["m"]), decay[0], guard)),
                v=vc.wrap(layout, _guarded_begin_micro(
                    vc, vc.parts_of(state["v"]), decay[1], guard)))


def fold_slice_state(state, g, row_offset, *, beta1, beta2, block, scale=1.0,
                     decay=None, grad_dtype=None, grad_scale=None,
                     guard=None):
    """One fused slice fold of a gradient slab into rows
    [row_offset, row_offset + g.shape[0]) of the state dict. Replicated
    codec columns are NOT decayed here (see fold_slice) — pair with
    begin_micro_state once per micro-batch. With `guard` the return is
    (new_state, flag)."""
    mc, vc = state_codecs(state)
    layout = state["m"].layout
    out = fold_slice(mc, vc, mc.parts_of(state["m"]),
                     vc.parts_of(state["v"]), g, row_offset,
                     beta1=beta1, beta2=beta2, block=block,
                     scale=scale, decay=decay, grad_dtype=grad_dtype,
                     grad_scale=grad_scale, guard=guard)
    m_parts, v_parts = out[0], out[1]
    new = dict(state, m=mc.wrap(layout, m_parts),
               v=vc.wrap(layout, v_parts))
    return (new, out[2]) if len(out) == 3 else new


def apply_state(p, state, *, lr, bc1, bc2, eps=1e-8, weight_decay=0.0,
                guard=None):
    """One fused bias-corrected apply of the state dict onto a param arena."""
    mc, vc = state_codecs(state)
    return apply(mc, vc, p, mc.parts_of(state["m"]), vc.parts_of(state["v"]),
                 lr=lr, bc1=bc1, bc2=bc2, eps=eps, weight_decay=weight_decay,
                 guard=guard)


def apply_master_state(state, *, lr, bc1, bc2, eps=1e-8, weight_decay=0.0,
                       work_dtype=jnp.bfloat16, guard=None):
    """Master-param apply: one fused kernel updates the fp32 master region
    (`state["p"]`, aliased in-place) AND emits the `work_dtype` working-
    param arena the next forward consumes. Returns (work_arena, new_state).
    The working params are a pure cast of the fp32 master every step — the
    master never round-trips through bf16, so the AMP round-trip is exact
    by construction (no precision leak across steps, no extra collective)."""
    mc, vc = state_codecs(state)
    p_master, p_work = apply(
        mc, vc, state["p"].data, mc.parts_of(state["m"]),
        vc.parts_of(state["v"]), lr=lr, bc1=bc1, bc2=bc2, eps=eps,
        weight_decay=weight_decay, work_dtype=work_dtype, guard=guard)
    return p_work, dict(state, p=state["p"].with_data(p_master))


def row_indexed_mask(state):
    """{"m": ..., "v": ...} mirroring the state's pytree structure with a
    bool per codec column: True where the column is ROW-INDEXED (shards and
    slices with the arena rows), False for replicated accumulators (e.g.
    rowcol's column sums). Derived from each codec's DECLARED kernel
    columns — the single source of truth the sharding sites (pjit
    constraints, shard_map specs, GSPMD pspecs) must agree with."""
    mc, vc = state_codecs(state)

    def mask(codec, s):
        flags = [c.row_indexed for c in codec.kernel.cols]
        return jax.tree.unflatten(jax.tree.structure(s), flags)

    return {"m": mask(mc, state["m"]), "v": mask(vc, state["v"])}


def psum_replicated_state(state, axis_names):
    """Combine per-shard partials of replicated codec columns (a no-op for
    fully row-local codec pairs) — the ZeRO-1 schedule calls this once per
    mini-batch, before the apply."""
    mc, vc = state_codecs(state)
    layout = state["m"].layout
    return dict(state,
                m=mc.wrap(layout, mc.psum_replicated(
                    mc.parts_of(state["m"]), axis_names)),
                v=vc.wrap(layout, vc.psum_replicated(
                    vc.parts_of(state["v"]), axis_names)))


def optimizer_state_bytes(state) -> int:
    """Measured bytes of an optimizer-state pytree (concrete arrays or
    ShapeDtypeStructs both work) — the number Table 3's capacity math needs."""
    import numpy as np
    total = 0
    for leaf in jax.tree.leaves(state):
        n = int(np.prod(leaf.shape, dtype=np.int64)) if leaf.shape else 1
        total += n * np.dtype(leaf.dtype).itemsize
    return total
