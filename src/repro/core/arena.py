"""Flat optimizer-state arena: all pytree leaves packed once into one
contiguous hardware-aligned (rows, LANES) fp32 buffer with a STATIC layout
table, so the whole AdamA fold/apply pipeline dispatches O(1) Pallas kernels
per micro-batch instead of O(leaves).

Layout (built once from the param tree, hashable, rides through jit as
pytree aux data):

  [ stack "blocks":   layer 0 | layer 1 | ... | layer L-1 ]
  [ stack "dense_blocks": ... ]  [ stack "enc_blocks": ... ]
  [ rest: embed | lm_head | final_norm_* | ... ]
  [ tail padding to a BLOCK_ROWS multiple ]

Stacked trees (leaves with a shared leading layer dim) are packed
LAYER-MAJOR: every layer occupies an identical, ROW_ALIGN-aligned row range
(`layer_rows`), so layer j of stack s lives at rows
`s.row + j * s.layer_rows` — a statically-strided slice the layer-wise
engine (Algorithm 2) folds into with one offset-indexed kernel per layer.
Within a region each leaf starts on a fresh row; tail lanes of its last row
are zero padding that no kernel result ever depends on (fold keeps 0 at 0,
unpack never reads it).

Everything is packed as fp32 by default (m, v are fp32 anyway; params/grads
are cast on pack and cast back to their recorded dtype on unpack — bitwise
identical to the per-leaf kernels' in-kernel casts). Mixed-dtype trees
therefore share a single arena and a single dispatch.

Every pack helper additionally takes a `dtype` — the GRADIENT WIRE dtype of
the mixed-precision AdamA path (`OptimizerConfig.grad_dtype`): packing a
gradient tree with dtype=bfloat16 halves the slab and every collective that
moves it, and the fold kernels (kernels/fused_step.py) upcast to fp32
in-pass so the moments still accumulate exactly.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.adama_accum import BLOCK_ROWS, LANES

# top-level keys holding per-layer stacked subtrees (leading dim = layer);
# must match the stages core/layerwise.py walks.
STACK_KEYS = ("blocks", "dense_blocks", "enc_blocks")

ROW_ALIGN = 8        # fp32 sublane multiple: every region is 8-row aligned

# Documented minimum row-block for offset-indexed slice-fold kernels.
# slice_block() is a gcd over region stride / offset — with regions only
# ROW_ALIGN-aligned it can legally collapse to 8 rows (32 KB blocks), a
# ~10x launch-overhead hit on the per-layer fold path. build_layout pads
# every region stride to a MIN_SLICE_BLOCK multiple so the gcd never drops
# below it; slice_block warns if handed a layout that was not.
MIN_SLICE_BLOCK = 64


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _align(n: int, mult: int) -> int:
    return _cdiv(n, mult) * mult


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def region_grain(n_shards: int = 1) -> int:
    """Row granularity of every region boundary/stride in a layout built
    with `build_layout(tree, n_shards=...)`: the lcm of the slice-fold
    block minimum and the ZeRO-1 scatter unit (each bucket of
    core/buckets.py must split into `n_shards` equal ROW_ALIGN-aligned
    slices, so per-layer strides must be n_shards*ROW_ALIGN-divisible)."""
    return _lcm(MIN_SLICE_BLOCK, ROW_ALIGN * max(1, n_shards))


@dataclass(frozen=True)
class LeafSpec:
    """One leaf's slot inside its region (per-layer shape for stacked leaves)."""
    shape: Tuple[int, ...]
    dtype: Any                   # np.dtype — restored on unpack
    row: int                     # row offset inside the region
    rows: int                    # whole rows occupied (= ceil(size/LANES))

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1


@dataclass(frozen=True)
class StackSpec:
    name: str
    treedef: Any                 # treedef of the stacked subtree
    n_layers: int
    leaves: Tuple[LeafSpec, ...]
    layer_rows: int              # ROW_ALIGN-aligned rows per layer
    row: int                     # arena row of layer 0

    @property
    def rows(self) -> int:
        return self.n_layers * self.layer_rows


@dataclass(frozen=True)
class RestSpec:
    treedef: Any                 # treedef of the non-stacked remainder
    leaves: Tuple[LeafSpec, ...]
    row: int
    rows: int                    # ROW_ALIGN-aligned


@dataclass(frozen=True)
class ArenaLayout:
    stacks: Tuple[StackSpec, ...]
    rest: RestSpec
    rows: int                    # total, padded so block_rows() divides it

    def stack(self, name: str) -> StackSpec:
        for s in self.stacks:
            if s.name == name:
                return s
        raise KeyError(name)

    def block_rows(self) -> int:
        """Row-block for whole-arena kernels (divides self.rows exactly)."""
        return min(BLOCK_ROWS, self.rows)

    def slice_block(self, spec) -> int:
        """Row-block for offset-indexed slice kernels over `spec` (a
        StackSpec or RestSpec): must divide both the region stride and every
        possible row offset. Layouts from build_layout pad every region to a
        MIN_SLICE_BLOCK multiple, so this is >= MIN_SLICE_BLOCK there; a
        hand-built layout with an odd stride can still gcd below it, which
        is correct but destroys slice-fold throughput — warn instead of
        silently dispatching tiny blocks."""
        if isinstance(spec, StackSpec):
            stride = spec.layer_rows
        else:
            stride = spec.rows
        blk = math.gcd(math.gcd(stride, spec.row), BLOCK_ROWS)
        if blk < MIN_SLICE_BLOCK:
            warnings.warn(
                f"slice_block={blk} < MIN_SLICE_BLOCK={MIN_SLICE_BLOCK} for "
                f"region at row {spec.row} (stride {stride}): tiny row "
                f"blocks destroy slice-fold kernel throughput. Layouts from "
                f"build_layout are padded to avoid this — rebuild the "
                f"layout instead of constructing specs by hand.",
                stacklevel=2)
        return blk


# ---------------------------------------------------------------------------
# Layout construction
# ---------------------------------------------------------------------------


def _leaf_specs(leaves) -> Tuple[Tuple[LeafSpec, ...], int]:
    specs = []
    row = 0
    for x in leaves:
        shape = tuple(x.shape)
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        rows = max(1, _cdiv(size, LANES))
        specs.append(LeafSpec(shape, np.dtype(x.dtype), row, rows))
        row += rows
    return tuple(specs), row


def split_tree(tree):
    """(stack_items, rest_tree): pull the STACK_KEYS subtrees off a dict
    tree; any other tree is entirely `rest`."""
    if isinstance(tree, dict):
        stack_items = [(k, tree[k]) for k in STACK_KEYS if k in tree]
        rest = {k: v for k, v in tree.items() if k not in STACK_KEYS}
        return stack_items, rest
    return [], tree


def build_layout(tree, n_shards: int = 1, tp_shards: int = 1) -> ArenaLayout:
    """Build the packed layout. `n_shards > 1` additionally pads the total
    row count so the arena splits into `n_shards` equal, kernel-block-aligned
    row ranges (core/zero.py::shard_rows) — ZeRO-1 over the arena is a
    row-range shard of every state column, so each shard must itself satisfy
    the fold/apply kernels' block-divisibility contract.

    Every region stride/boundary is padded to `region_grain(n_shards)` rows
    (lcm of MIN_SLICE_BLOCK and n_shards*ROW_ALIGN): the slice-fold block
    never gcds below MIN_SLICE_BLOCK, and each per-layer row range splits
    into n_shards equal aligned slices — the unit the bucketed ZeRO-1
    schedule (core/buckets.py) reduce-scatters.

    `tp_shards` makes the layout mesh-aware for a 2D dp×tp mesh: every
    dp slice must further split into `tp_shards` equal aligned sub-slices
    (stacked regions split along the tp axis). The layout depends only on
    the PRODUCT n_shards*tp_shards — build_layout(t, d, tp) ==
    build_layout(t, d*tp) — which is the canonical-order property the
    dp×tp composition relies on: a (2dp×2tp) plan addresses the same arena
    rows as a flat 4dp plan, so manual×manual mesh folding is bitwise and
    elastic resharding (train/checkpoint.py) round-trips through arena
    order regardless of the mesh shape it was saved under."""
    assert n_shards >= 1, n_shards
    assert tp_shards >= 1, tp_shards
    n_shards = n_shards * tp_shards
    grain = region_grain(n_shards)
    stack_items, rest_tree = split_tree(tree)
    row = 0
    stacks = []
    for name, sub in stack_items:
        leaves, tdef = jax.tree.flatten(sub)
        n_layers = int(leaves[0].shape[0])
        for x in leaves:
            assert x.shape[0] == n_layers, \
                f"stacked leaf in {name!r} has leading dim {x.shape[0]} != {n_layers}"
        specs, used = _leaf_specs([jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
                                   for x in leaves])
        layer_rows = max(grain, _align(used, grain))
        stacks.append(StackSpec(name, tdef, n_layers, specs, layer_rows, row))
        row += n_layers * layer_rows
    rleaves, rdef = jax.tree.flatten(rest_tree)
    rspecs, rused = _leaf_specs(rleaves)
    rest_rows = _align(max(rused, 0), grain)
    rest = RestSpec(rdef, rspecs, row, rest_rows)
    row += rest_rows
    total = _align(row, BLOCK_ROWS) if row > BLOCK_ROWS else max(row, ROW_ALIGN)
    if n_shards > 1:
        # equal shards, each a ROW_ALIGN multiple; whenever the padded total
        # exceeds BLOCK_ROWS, each shard must itself be a BLOCK_ROWS multiple
        # so both the whole-arena AND the per-shard fold/apply keep their
        # block-divisibility contract
        per = _align(_cdiv(total, n_shards), ROW_ALIGN)
        if per * n_shards > BLOCK_ROWS:
            per = _align(per, BLOCK_ROWS)
        total = per * n_shards
    return ArenaLayout(tuple(stacks), rest, total)


# ---------------------------------------------------------------------------
# Pack / unpack
# ---------------------------------------------------------------------------


def _check_pack_dtype(dtype):
    """The pack helpers CAST; fp8 needs a scaled encode. A raw cast to
    e4m3 (dynamic range ±448, 3 mantissa bits) silently flushes most of a
    gradient to zero/saturation, so packing straight to fp8 is always a
    bug — the fp8 wire packs fp32 (or bf16) first and then encodes with
    kernels.adama_accum.fp8_encode_rows (codes + per-row scale column)."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.float8_e4m3fn):
        raise TypeError(
            "cannot pack a tree directly to float8_e4m3fn: an unscaled "
            "cast destroys the gradient. Pack fp32 and encode with "
            "kernels.adama_accum.fp8_encode_rows (codes + per-row scale "
            "column) instead")


def _pack_region(leaves, specs, region_rows, lead: Tuple[int, ...] = (),
                 dtype=jnp.float32, front: int = 0):
    """Concatenate leaves (each reshaped (*lead, -1), zero-padded to whole
    rows) into a (*lead, region_rows, LANES) `dtype` block, after `front`
    zero rows."""
    _check_pack_dtype(dtype)
    mats = [jnp.zeros(lead + (front, LANES), dtype)] if front else []
    for x, spec in zip(leaves, specs):
        flat = x.reshape(lead + (-1,)).astype(dtype)
        pad = spec.rows * LANES - spec.size
        if pad:
            flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, pad)])
        mats.append(flat.reshape(lead + (spec.rows, LANES)))
    used = front + sum(s.rows for s in specs)
    if region_rows > used:
        mats.append(jnp.zeros(lead + (region_rows - used, LANES), dtype))
    return jnp.concatenate(mats, axis=len(lead)) if len(mats) > 1 else mats[0]


def pack_layer(layer_tree, spec: StackSpec, dtype=jnp.float32) -> jnp.ndarray:
    """One layer's (un-stacked) subtree -> (layer_rows, LANES) `dtype` slab."""
    leaves = spec.treedef.flatten_up_to(layer_tree)
    return _pack_region(leaves, spec.leaves, spec.layer_rows, dtype=dtype)


def pack_rest(rest_tree, layout: ArenaLayout, dtype=jnp.float32) -> jnp.ndarray:
    """The non-stacked remainder -> (rest.rows, LANES) `dtype` slab."""
    leaves = layout.rest.treedef.flatten_up_to(rest_tree)
    return _pack_region(leaves, layout.rest.leaves, layout.rest.rows,
                        dtype=dtype)


def pack_stack_layers(stack_tree, spec: StackSpec, j0: int, j1: int,
                      dtype=jnp.float32) -> jnp.ndarray:
    """Layers [j0, j1) of a stacked subtree -> ((j1-j0)*layer_rows, LANES)
    `dtype` slab — rows [spec.row + j0*layer_rows, spec.row + j1*layer_rows)
    of the full pack, bitwise, without materializing the other layers."""
    assert 0 <= j0 < j1 <= spec.n_layers, (j0, j1, spec.n_layers)
    leaves = [x[j0:j1] for x in spec.treedef.flatten_up_to(stack_tree)]
    block = _pack_region(leaves, spec.leaves, spec.layer_rows,
                         lead=(j1 - j0,), dtype=dtype)
    return block.reshape(-1, LANES)


def pack_rest_rows(rest_tree, layout: ArenaLayout, row_lo: int, row_hi: int,
                   dtype=jnp.float32) -> jnp.ndarray:
    """Arena rows [row_lo, row_hi) of the rest region's pack — bitwise equal
    to pack_rest(...)[row_lo-rest.row : row_hi-rest.row] but touching only
    the leaves that intersect the range (the bucketed ZeRO-1 schedule packs
    the rest region one size-capped bucket at a time). The range may cut
    through a leaf mid-row-run; cuts are static, so the slices are too."""
    _check_pack_dtype(dtype)
    rest = layout.rest
    lo, hi = row_lo - rest.row, row_hi - rest.row
    assert 0 <= lo < hi <= rest.rows, (row_lo, row_hi, rest.row, rest.rows)
    leaves = rest.treedef.flatten_up_to(rest_tree)
    mats = []
    cursor = lo
    for x, spec in zip(leaves, rest.leaves):
        a = max(spec.row, lo)
        b = min(spec.row + spec.rows, hi)
        if a >= b:
            continue
        flat = x.reshape(-1).astype(dtype)
        e0 = (a - spec.row) * LANES
        e1 = min(spec.size, (b - spec.row) * LANES)
        seg = flat[e0:max(e0, e1)]
        pad = (b - a) * LANES - seg.shape[0]
        if pad:
            seg = jnp.pad(seg, (0, pad))
        mats.append(seg.reshape(b - a, LANES))
        cursor = b
    if cursor < hi:                      # region alignment rows past leaves
        mats.append(jnp.zeros((hi - cursor, LANES), dtype))
    return jnp.concatenate(mats, axis=0) if len(mats) > 1 else mats[0]


def pack(tree, layout: ArenaLayout, dtype=jnp.float32) -> jnp.ndarray:
    """Whole tree -> (layout.rows, LANES) `dtype` arena (layer-major stacks)."""
    stack_items, rest_tree = split_tree(tree)
    parts = []
    for (name, sub), spec in zip(stack_items, layout.stacks):
        assert name == spec.name
        leaves = spec.treedef.flatten_up_to(sub)
        block = _pack_region(leaves, spec.leaves, spec.layer_rows,
                             lead=(spec.n_layers,), dtype=dtype)
        parts.append(block.reshape(-1, LANES))
    if layout.rest.rows:
        parts.append(pack_rest(rest_tree, layout, dtype=dtype))
    used = sum(p.shape[0] for p in parts)
    if layout.rows > used:
        parts.append(jnp.zeros((layout.rows - used, LANES), dtype))
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


def _unpack_region(block, specs, dtype, lead: Tuple[int, ...] = ()):
    leaves = []
    for spec in specs:
        seg = block[..., spec.row:spec.row + spec.rows, :]
        seg = seg.reshape(lead + (-1,))[..., :spec.size]
        leaves.append(seg.reshape(lead + spec.shape)
                      .astype(dtype if dtype is not None else spec.dtype))
    return leaves


def unpack(arena: jnp.ndarray, layout: ArenaLayout, dtype=None):
    """Arena -> tree. Leaves cast back to their recorded dtypes (or a forced
    `dtype`, e.g. fp32 for optimizer moments)."""
    out: Dict[str, Any] = {}
    for spec in layout.stacks:
        block = arena[spec.row:spec.row + spec.rows]
        block = block.reshape(spec.n_layers, spec.layer_rows, LANES)
        leaves = _unpack_region(block, spec.leaves, dtype,
                                lead=(spec.n_layers,))
        out[spec.name] = spec.treedef.unflatten(leaves)
    rblock = arena[layout.rest.row:layout.rest.row + layout.rest.rows]
    rleaves = _unpack_region(rblock, layout.rest.leaves, dtype)
    rest_tree = layout.rest.treedef.unflatten(rleaves)
    if not layout.stacks:
        return rest_tree
    out.update(rest_tree)
    return out


# ---------------------------------------------------------------------------
# Gradient sources: the whole-arena fold reads the gradient tree in place
# ---------------------------------------------------------------------------
#
# Instead of packing a micro-batch's gradient into a (rows, LANES) slab, the
# fold kernel (kernels/fused_step.py::arena_fold) can gather each row block
# of gradient straight from a handful of SOURCE arrays, one DMA per source
# run the block touches. A source is either
#
#   direct  one leaf whose (rows, LANES) view is free: its last dim is
#           LANES and its rows come in whole (sublane-tile, LANES) tiles,
#           so the reshape is a bitcast and the kernel reads the leaf as the
#           backward left it (cast to the wire in-kernel), or
#   copy    one leaf that needs a relayout (last dim != LANES, a tail lane
#           pad, another dtype): one XLA copy into a (rows, LANES) buffer
#           of the wire dtype; or a run of consecutive sub-tile leaves
#           (norm scales and biases) packed together, placed so its rows
#           sit at the same offset within a tile as their arena rows.
#
# Stacked sources keep the layer as their leading dim, so every layer of a
# stack uses the same source at the same inner offset. A DMA moves whole
# sublane tiles, so the kernel lands each source's tiles in a VMEM staging
# buffer keyed by (dtype, residue): the residue is the source's arena-row
# offset within a tile, which the kernel undoes with one static sublane
# shift. Sources whose tiles can meet in one staging tile take different
# staging buffers ("lanes" of the same residue).


def sublane_tile(dtype) -> int:
    """Rows of one HBM tile of `dtype` on the TPU (8 for 32-bit, 16 for
    16-bit): a DMA's row offset and row count are multiples of it."""
    return 32 // jnp.dtype(dtype).itemsize


def _is_direct(spec: LeafSpec) -> bool:
    shape, dtype = spec.shape, jnp.dtype(spec.dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    t = sublane_tile(dtype)
    return (len(shape) >= 2 and shape[-1] == LANES and shape[-2] % t == 0
            and spec.rows % t == 0)


@dataclass(frozen=True)
class GradSource:
    """One source array of the gathered fold and where its rows go."""
    stack: int                   # index into layout.stacks; -1: rest region
    leaves: Tuple[int, ...]      # leaf indices (region flatten order)
    row: int                     # first arena row, inside the layer/region
    rows: int                    # arena rows it covers
    phys_row: int                # its first row inside the source array
    phys_rows: int               # rows of the source array (per layer)
    dtype: Any                   # np.dtype of the source array
    direct: bool                 # the leaf itself, reshaped (no copy)
    buf: int                     # the kernel's staging buffer


@dataclass(frozen=True)
class GradRun:
    """One layer's instance of a source: arena rows [start, start+rows)."""
    start: int
    rows: int
    src: int
    layer: int


@dataclass(frozen=True)
class GradPlan:
    """Static plan of the gathered fold: sources, staging buffers (dtype,
    residue within a tile) and, per layer instance, the run of arena rows
    each source fills. Rows no run covers fold a zero gradient, as the
    slab's padding does."""
    layout: ArenaLayout
    wire: Any                    # np.dtype of the gradient wire
    sources: Tuple[GradSource, ...]
    bufs: Tuple[Tuple[Any, int], ...]
    runs: Tuple[GradRun, ...]    # sorted by start, disjoint


def _region(layout: ArenaLayout, stack: int):
    """(specs, base row, layer stride, layers) of region `stack`."""
    if stack < 0:
        r = layout.rest
        return r.leaves, r.row, 0, 1
    s = layout.stacks[stack]
    return s.leaves, s.row, s.layer_rows, s.n_layers


def _region_sources(specs, stack: int, wire) -> list:
    """Split one region's leaves into sources (buf left unassigned)."""
    tw = sublane_tile(wire)
    out, group = [], []

    def copy(idx):
        # one leaf's copy is its relayout alone (no front pad, which XLA
        # would write as a second pass); a group of sub-tile leaves is
        # placed at its arena offset within a tile
        row = specs[idx[0]].row
        rows = sum(specs[k].rows for k in idx)
        front = row % tw if len(idx) > 1 else 0
        out.append(GradSource(stack, tuple(idx), row, rows, front,
                              _align(front + rows, tw), np.dtype(wire),
                              False, -1))

    for k, spec in enumerate(specs):
        if spec.rows < tw and not _is_direct(spec):
            group.append(k)                  # sub-tile leaves share a copy
            continue
        if group:
            copy(group)
            group = []
        if _is_direct(spec):
            out.append(GradSource(stack, (k,), spec.row, spec.rows, 0,
                                  spec.rows, np.dtype(spec.dtype), True, -1))
        else:
            copy([k])
    if group:
        copy(group)
    return out


def _tiles(start: int, rows: int, src: GradSource):
    """[first, last] tile of a run in its staging grid."""
    t = sublane_tile(src.dtype)
    shift = start - src.phys_row          # arena row of source row 0
    lo = src.phys_row // t
    hi = (src.phys_row + rows - 1) // t
    return lo + (shift - shift % t) // t, hi + (shift - shift % t) // t


@functools.lru_cache(maxsize=None)
def grad_plan(layout: ArenaLayout, wire=jnp.float32) -> GradPlan:
    """The gathered fold's static plan for `layout` with the gradient
    arriving on the `wire` dtype (fp32 or bf16)."""
    wire = np.dtype(jnp.dtype(wire))
    assert wire in (np.dtype(np.float32), np.dtype(jnp.bfloat16)), wire
    sources = []
    for k in range(-1, len(layout.stacks)):
        specs = _region(layout, k)[0]
        sources += _region_sources(specs, k, wire) if specs else []
    runs = []
    for sid, src in enumerate(sources):
        _, base, stride, layers = _region(layout, src.stack)
        runs += [GradRun(base + j * stride + src.row, src.rows, sid, j)
                 for j in range(layers)]
    runs.sort(key=lambda r: r.start)

    def key(src):
        t = sublane_tile(src.dtype)
        return src.dtype, (src.row - src.phys_row) % t

    # sources of one key whose runs meet in a staging tile need separate
    # buffers: colour the conflict graph greedily
    clash = {sid: set() for sid in range(len(sources))}
    by_key: Dict[Any, list] = {}
    for r in runs:
        by_key.setdefault(key(sources[r.src]), []).append(r)
    for rs in by_key.values():
        tiles = [_tiles(r.start, r.rows, sources[r.src]) for r in rs]
        for a in range(len(rs)):
            for b in range(a + 1, len(rs)):
                if tiles[b][0] > tiles[a][1]:
                    break
                assert rs[a].src != rs[b].src, "a source meets itself"
                clash[rs[a].src].add(rs[b].src)
                clash[rs[b].src].add(rs[a].src)
    bufs, lanes = [], {}
    for sid, src in enumerate(sources):
        taken = {lanes[o] for o in clash[sid] if o in lanes}
        lane = next(n for n in range(len(sources) + 1)
                    if (key(src), n) not in taken)
        lanes[sid] = (key(src), lane)
        if lanes[sid] not in bufs:
            bufs.append(lanes[sid])
        sources[sid] = dataclasses.replace(src, buf=bufs.index(lanes[sid]))
    return GradPlan(layout, wire, tuple(sources),
                    tuple(k for k, _ in bufs), tuple(runs))


class GradLeaves:
    """A micro-batch's gradient as the gathered fold reads it: the source
    arrays of `plan`, built from the gradient tree by `gather_sources`."""

    def __init__(self, arrays: Tuple[jnp.ndarray, ...], plan: GradPlan):
        self.arrays = tuple(arrays)
        self.plan = plan


def gather_sources(grads, layout: ArenaLayout, wire=jnp.float32) -> GradLeaves:
    """Gradient tree -> the gathered fold's sources: direct leaves are
    reshaped to (layers, rows, LANES) views (a bitcast on the chip), the
    rest copied once into wire-dtype buffers. No (rows, LANES) slab."""
    plan = grad_plan(layout, wire)
    stack_items, rest_tree = split_tree(grads)
    leaves = {-1: layout.rest.treedef.flatten_up_to(rest_tree)}
    for k, ((name, sub), spec) in enumerate(zip(stack_items, layout.stacks)):
        assert name == spec.name
        leaves[k] = spec.treedef.flatten_up_to(sub)
    arrays = []
    for src in plan.sources:
        specs = _region(layout, src.stack)[0]
        xs = [leaves[src.stack][k] for k in src.leaves]
        if src.stack < 0:
            xs = [x[None] for x in xs]
        if src.direct:
            arrays.append(xs[0].reshape(xs[0].shape[0], src.rows, LANES))
        else:
            arrays.append(_pack_region(xs, [specs[k] for k in src.leaves],
                                       src.phys_rows, lead=xs[0].shape[:1],
                                       dtype=plan.wire, front=src.phys_row))
    return GradLeaves(arrays, plan)


# ---------------------------------------------------------------------------
# Arena: the (buffer, static layout) pair as a first-class pytree
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class Arena:
    """A (rows, LANES) fp32 buffer + its static layout. Registered as a
    pytree (layout = aux data), so arena-backed optimizer state flows through
    jit / scan / psum / donation exactly like the per-leaf tree state."""

    def __init__(self, data: jnp.ndarray, layout: ArenaLayout):
        self.data = data
        self.layout = layout

    def tree_flatten(self):
        return (self.data,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], layout)

    @classmethod
    def zeros(cls, layout: ArenaLayout) -> "Arena":
        return cls(jnp.zeros((layout.rows, LANES), jnp.float32), layout)

    @classmethod
    def from_tree(cls, tree, layout: Optional[ArenaLayout] = None) -> "Arena":
        layout = layout if layout is not None else build_layout(tree)
        return cls(pack(tree, layout), layout)

    def to_tree(self, dtype=None):
        return unpack(self.data, self.layout, dtype)

    def with_data(self, data: jnp.ndarray) -> "Arena":
        return Arena(data, self.layout)

    def __repr__(self):
        return (f"Arena(rows={self.layout.rows}, lanes={LANES}, "
                f"stacks={[s.name for s in self.layout.stacks]})")
