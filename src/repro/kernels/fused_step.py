"""Arena-wide fused AdamA kernels, generated by ONE parameterized builder
per entry point: the whole optimizer state is packed arena columns, and each
micro-batch fold / mini-batch apply is ONE `pallas_call`, independent of the
number of parameter leaves AND of the (m, v) codec pair.

A codec is DATA here (`KernelCodec`): it contributes only

  cols    the list of arena columns its moment rides in — per column a
          (width, dtype, row_indexed) triple. Row-indexed columns are sliced
          with the arena rows (and therefore row-range shardable, see
          core/zero.py); a non-row-indexed column is a single (1, width)
          block every grid step sees (a replicated accumulator, e.g. the
          rowcol codec's column-sum statistic).
  update  the kernel-body fold fragment: given the column refs and this
          block's contribution (g for m, g^2 for v), write the new columns.
  decode  the reconstruction fragment: column values -> an fp32 value
          broadcastable to (block, LANES). Pure jnp, so the SAME callable
          runs inside the apply kernel and on the host (state_store decode
          paths, tests).

The three builders below generate the (fold, fold_slice, apply) entry points
from those fragments — BlockSpecs, grids, SMEM scalars, out_shapes and
input->output aliasing included — so a new codec is a registry entry, not
new pallas_call scaffolding:

  arena_fold        m/v state update over the full arena. The decay pair
                    (dm, dv) is an SMEM scalar input: passing
                    (beta1, M*beta2) on the FIRST fold of a mini-batch fuses
                    `begin_minibatch` into it, eliminating an entire arena
                    read+write pass. The gradient is a packed slab, or the
                    gradient tree's sources (core/arena.py::GradLeaves):
                    then each row block's tiles are DMA'd from the leaves
                    into VMEM staging, one block ahead, and no slab is
                    ever written to HBM.
  arena_fold_slice  Same fold restricted to rows [offset, offset+rows_g).
                    `offset` is a TRACED scalar-prefetch argument feeding
                    the BlockSpec index maps, so the layer-wise engine
                    (Algorithm 2) folds layer j into its arena slice from
                    inside a lax.scan with a single kernel. Rows outside
                    the slice keep their values (all row-indexed columns
                    are aliased input->output).
  arena_apply       The bias-corrected parameter update over the packed
                    param arena (reads p and the state columns once, writes
                    p once, aliased), decoding both moments in-pass.

Codec fragments (all fused in-pass, never a separate kernel):
  int8 (v)   ceil-quantized codes + (rows, 1) fp32 per-row scale; dequant ->
             decay+accumulate -> per-row requant (the row is one block, so
             the row-max for the new scale is kernel-local).
  int8 (m)   the SIGNED variant: codes in [-127, 127], rounding TOWARD
             ZERO, so |m_hat| <= |m| (never-amplify from the numerator
             side; see adama_accum.q8s_encode_rows).
  factored   v as a (rows, 1) per-row SM3-style lane-max upper bound.
  rowcol     v as its rank-1 marginals: (rows, 1) row sums (row-indexed)
             + (1, LANES) column sums (replicated accumulator). The column
             sums accumulate ACROSS grid steps into the same output block
             (`pl.when(i == 0)` seeds it); their decay is applied OUTSIDE
             the kernel (core/state_store.py), because a slice fold only
             sees part of the rows and must not decay the shared column
             twice.

All fp32 operands are packed with a cast; betas/scale are static,
step-dependent scalars ride in SMEM so one compiled kernel serves every step.

`interpret=None` (every entry point's default) asks kernels/ops.py::
_interpret: the kernels compile on TPU, run in Pallas interpret mode on the
CPU backend, and raise on any other backend.

Mixed-precision wire (grad_dtype): the gradient operand of fold/fold_slice
may arrive as bf16 — the kernels upcast it to fp32 IN-PASS (one `.astype`
on the VMEM block before the scale multiply), so the moments always
accumulate in fp32 and no fp32 copy of the gradient ever materializes in
HBM. The builders take an explicit `grad_dtype` so a caller's declared wire
dtype is validated against the slab it actually packed.

fp8 wire (grad_dtype=float8_e4m3fn): the slab arrives as e4m3 CODES plus a
(rows, 1) fp32 per-row scale column (`grad_scale`, produced by
adama_accum.fp8_encode_rows — possibly psum-summed codes under a shared,
pmax-agreed scale). The decode is one extra multiply fused into the same
in-pass upcast: g_fp32 = codes.astype(f32) * fold_scale * grad_scale. The
scale column is a (block, 1) input block riding the gradient's row index
map, appended AFTER the slab so the state-column input->output aliases are
untouched. _check_grad_dtype enforces the pairing both ways: fp8 codes
without their scale column — or a scale column with a full-precision slab —
is always a plumbing bug.

Master params (arena_apply(work_dtype=...)): the param operand becomes the
fp32 MASTER region (aliased in-place) and the kernel emits a second,
`work_dtype` (bf16) output column set — the working params the next
forward/backward consumes. Still one pallas_call; the bf16 params are a
pure cast of the fp32 master every step, so the AMP round-trip is exact by
construction (the master never passes through bf16).
"""
from __future__ import annotations

import collections
import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.adama_accum import (BLOCK_ROWS, LANES, fac_row_stat,
                                       q8_decode_rows, q8_encode_rows,
                                       q8s_encode_rows, rowcol_decode)
from repro.kernels.ops import _interpret


# ---------------------------------------------------------------------------
# Codec descriptors: a codec is a column list + two kernel-body fragments
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Col:
    """One arena column of a codec's state. `row_indexed` columns have
    shape (rows, width) and slice/shard with the arena; a non-row-indexed
    column is a single (1, width) replicated accumulator."""
    width: int
    dtype: Any
    row_indexed: bool = True


@dataclass(frozen=True)
class KernelCodec:
    """What a codec contributes to the fused kernels (data, not code)."""
    name: str
    cols: Tuple[Col, ...]
    # decode(vals) -> fp32, broadcastable to (block, LANES); pure jnp
    decode: Callable = field(repr=False)
    # update(i, in_refs, out_refs, contrib, decay, coeff): write out_refs
    update: Callable = field(repr=False)


def _fp32_decode(vals):
    return vals[0]


def _fp32_update(i, ins, outs, contrib, decay, coeff):
    del i
    outs[0][...] = decay * ins[0][...] + coeff * contrib


def _q8u_decode(vals):
    return q8_decode_rows(vals[0], vals[1])


def _q8u_update(i, ins, outs, contrib, decay, coeff):
    del i
    v = decay * q8_decode_rows(ins[0][...], ins[1][...]) + coeff * contrib
    q, s = q8_encode_rows(v)
    outs[0][...] = q
    outs[1][...] = s


def _q8s_update(i, ins, outs, contrib, decay, coeff):
    del i
    m = decay * q8_decode_rows(ins[0][...], ins[1][...]) + coeff * contrib
    q, s = q8s_encode_rows(m)
    outs[0][...] = q
    outs[1][...] = s


def _fac_decode(vals):
    return vals[0]                               # (R, 1) broadcasts


def _fac_update(i, ins, outs, contrib, decay, coeff):
    del i
    outs[0][...] = decay * ins[0][...] + coeff * fac_row_stat(contrib)


def _rowcol_decode(vals):
    return rowcol_decode(vals[0], vals[1])


def _rowcol_update(i, ins, outs, contrib, decay, coeff):
    # row sums: ordinary row-local decay + accumulate
    outs[0][...] = decay * ins[0][...] \
        + coeff * jnp.sum(contrib, axis=-1, keepdims=True)
    # column sums: one (1, LANES) block revisited by EVERY grid step — seed
    # from the input on step 0, then accumulate this block's column sums.
    # The decay is applied outside the kernel (state_store), once per
    # micro-batch, because a slice fold sees only part of the rows.
    @pl.when(i == 0)
    def _():
        outs[1][...] = ins[1][...]
    outs[1][...] += coeff * jnp.sum(contrib, axis=0, keepdims=True)


_KERNEL_CODECS = {
    "m": {
        "fp32": KernelCodec("fp32", (Col(LANES, jnp.float32),),
                            _fp32_decode, _fp32_update),
        "int8": KernelCodec("int8", (Col(LANES, jnp.int8),
                                     Col(1, jnp.float32)),
                            _q8u_decode, _q8s_update),
    },
    "v": {
        "fp32": KernelCodec("fp32", (Col(LANES, jnp.float32),),
                            _fp32_decode, _fp32_update),
        "int8": KernelCodec("int8", (Col(LANES, jnp.int8),
                                     Col(1, jnp.float32)),
                            _q8u_decode, _q8u_update),
        "factored": KernelCodec("factored", (Col(1, jnp.float32),),
                                _fac_decode, _fac_update),
        "rowcol": KernelCodec("rowcol",
                              (Col(1, jnp.float32),
                               Col(LANES, jnp.float32, row_indexed=False)),
                              _rowcol_decode, _rowcol_update),
    },
}


def kernel_codec(moment: str, name) -> KernelCodec:
    """Resolve a kernel codec by (moment, name); KernelCodec passes through."""
    if isinstance(name, KernelCodec):
        return name
    try:
        return _KERNEL_CODECS[moment][name]
    except KeyError:
        raise KeyError(f"unknown {moment}-codec kernel {name!r}; available: "
                       f"{sorted(_KERNEL_CODECS[moment])}") from None


# ---------------------------------------------------------------------------
# Builder plumbing shared by the three entry points
# ---------------------------------------------------------------------------


# dtypes a gradient slab may arrive in (the "wire"): fp32, bf16 with the
# upcast fused into the fold kernels, or fp8 (e4m3) codes whose per-row
# scale column rides in as the `grad_scale` operand (decode fused in-pass)
GRAD_WIRE_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn)


def _check_grad_dtype(g, grad_dtype, grad_scale=None):
    """Validate the fold's gradient wire dtype. `grad_dtype=None` infers it
    from the slab; an explicit dtype pins the caller's declared wire against
    what was actually packed. An fp8 slab and a `grad_scale` column come in
    a pair — codes without their scale (or a scale with a full-precision
    slab) is always a plumbing bug, so both directions raise."""
    if grad_dtype is not None and g.dtype != jnp.dtype(grad_dtype):
        raise TypeError(f"gradient slab dtype {g.dtype} != declared "
                        f"grad_dtype {jnp.dtype(grad_dtype)}")
    if g.dtype not in [jnp.dtype(d) for d in GRAD_WIRE_DTYPES]:
        raise TypeError(f"unsupported gradient wire dtype {g.dtype}; "
                        f"expected one of {GRAD_WIRE_DTYPES}")
    is_fp8 = g.dtype == jnp.dtype(jnp.float8_e4m3fn)
    if is_fp8 and grad_scale is None:
        raise TypeError("fp8 gradient slab requires its per-row grad_scale "
                        "column (see kernels/adama_accum.fp8_encode_rows); "
                        "codes without the scale would fold garbage")
    if grad_scale is not None:
        if not is_fp8:
            raise TypeError(f"grad_scale column passed with a {g.dtype} "
                            "slab; the scale column pairs only with an "
                            "fp8 (float8_e4m3fn) wire")
        if tuple(grad_scale.shape) != (g.shape[0], 1) \
                or grad_scale.dtype != jnp.float32:
            raise TypeError(f"grad_scale must be ({g.shape[0]}, 1) fp32, "
                            f"got {grad_scale.shape} {grad_scale.dtype}")


def _decay_scalars(decay):
    dm, dv = (1.0, 1.0) if decay is None else decay
    return jnp.stack([jnp.asarray(dm, jnp.float32),
                      jnp.asarray(dv, jnp.float32)])


def _guard_scalars(decay, ok, scale):
    """Extended SMEM scalar vector for guarded / traced-scale folds:
    [dm, dv, ok, scale]. `ok` (bool, 1.0/0.0 in SMEM) predicates the state
    writes; `scale` replaces the static baked-in scale so one compiled
    kernel serves every loss-scale value."""
    dm, dv = (1.0, 1.0) if decay is None else decay
    return jnp.stack([jnp.asarray(dm, jnp.float32),
                      jnp.asarray(dv, jnp.float32),
                      jnp.asarray(ok, jnp.float32),
                      jnp.asarray(scale, jnp.float32)])


class _GuardedRef:
    """Predicated output ref: pairs an output ref with its ALIASED input
    ref and a scalar predicate. Reads delegate to the output ref; every
    write commits `where(ok, value, input)` — so an ok=False fold is a
    bitwise no-op on the state while still writing each output block
    (Pallas writes out windows back as the grid moves; leaving a block
    unwritten would commit garbage, not the old value). NaN/Inf in the
    discarded branch is safe: `where` selects elementwise and does not
    propagate the unselected operand. Codec update fragments run unchanged
    against these proxies — predication wraps the refs, not the codecs."""

    __slots__ = ("_out", "_in", "_ok")

    def __init__(self, out_ref, in_ref, ok):
        self._out, self._in, self._ok = out_ref, in_ref, ok

    def __getitem__(self, idx):
        return self._out[idx]

    def __setitem__(self, idx, val):
        self._out[idx] = jnp.where(self._ok, val, self._in[idx])


def _as_parts(x):
    """Normalize a moment state to a column tuple. Bare arrays (the fp32
    codec's natural form) are accepted and returned bare."""
    if isinstance(x, (tuple, list)):
        return tuple(x), False
    return (x,), True


def _check_parts(parts, kc: KernelCodec, rows: int):
    assert len(parts) == len(kc.cols), \
        f"{kc.name}: {len(parts)} columns, expected {len(kc.cols)}"
    for p, c in zip(parts, kc.cols):
        want = (rows, c.width) if c.row_indexed else (1, c.width)
        assert tuple(p.shape) == want, (kc.name, p.shape, want)


def _col_specs(cols, block, *, sliced: bool):
    """BlockSpecs for a codec's columns. Sliced kernels take the scalar-
    prefetch args (row offset in block units, decay pair) in the index map."""
    specs = []
    for c in cols:
        if c.row_indexed:
            if sliced:
                specs.append(pl.BlockSpec((block, c.width),
                                          lambda i, off, sc: (off[0] + i, 0)))
            else:
                specs.append(pl.BlockSpec((block, c.width),
                                          lambda i, *_: (i, 0)))
        else:
            # a single replicated block, revisited by every grid step
            if sliced:
                specs.append(pl.BlockSpec((1, c.width),
                                          lambda i, off, sc: (0, 0)))
            else:
                specs.append(pl.BlockSpec((1, c.width),
                                          lambda i, *_: (0, 0)))
    return specs


def _out_shapes(parts):
    return [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in parts]


def _split(out, nm):
    out = out if isinstance(out, (tuple, list)) else [out]
    return tuple(out[:nm]), tuple(out[nm:])


def _on_mesh(call, args, row_in, row_out, acc=()):
    """Run `call(*args)` — one pallas_call over arena rows — under the pjit
    engine's mesh (sharding/ctx.py::kernel_mesh). XLA cannot partition a
    Mosaic kernel, so the call goes into a shard_map manual over every
    mesh axis, and each device runs the kernel on its own rows: operands
    flagged in `row_in` enter as their row block over the dp axes, the
    rest whole; `row_out` flags the results likewise. `acc` pairs each
    replicated accumulator result with its aliased operand (rowcol's column
    sums): every device folds its rows into zeros and the partials are
    psum'd onto the operand. Rows that do not split into block-aligned dp
    shards — and `row_in=()` — run the whole kernel on every device.
    Without such a mesh `call` runs as is."""
    from repro.core.arena import ROW_ALIGN
    from repro.sharding.ctx import kernel_mesh
    km = kernel_mesh()
    if km is None:
        return call(*args)
    mesh, dp = km
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    rows = next((x.shape[0] for x, r in zip(args, row_in) if r), 0)
    per = rows // n if dp and rows % n == 0 else 0
    split = per > 0 and per % ROW_ALIGN == 0 and (
        per <= BLOCK_ROWS or per % BLOCK_ROWS == 0)
    row = P(dp, None) if split else P()

    def body(*xs):
        local = list(xs)
        if split:
            for _, i in acc:
                local[i] = jnp.zeros_like(xs[i])
        out = call(*local)
        if not isinstance(out, (tuple, list)):
            return out
        out = list(out)
        if split:
            for o, i in acc:
                out[o] = xs[i] + jax.lax.psum(out[o], dp)
        return tuple(out)

    in_specs = tuple(row if r else P() for r in row_in) \
        + (P(),) * (len(args) - len(row_in))
    if isinstance(row_out, (tuple, list)):
        out_specs = tuple(row if r else P() for r in row_out)
    else:
        out_specs = row if row_out else P()
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# The three builder-generated entry points
# ---------------------------------------------------------------------------


def _fold_body(sc_ref, *refs, mk, vk, beta1, beta2, scale, nm, nv,
               guarded=False, has_gs=False):
    m_in, v_in = refs[:nm], refs[nm:nm + nv]
    # in-kernel upcast: a bf16 wire slab becomes fp32 HERE, on the VMEM
    # block — the moments always accumulate in fp32 and no fp32 gradient
    # buffer ever exists in HBM. scale=None reads the traced per-call
    # scale (1/n divided by the live loss scale) from SMEM instead of a
    # static bake, so one compiled kernel serves every scale value.
    s = sc_ref[3] if scale is None else scale
    g = refs[nm + nv][...].astype(jnp.float32) * s
    k = nm + nv + 1
    if has_gs:
        # fp8 wire decode, fused: codes x their (block, 1) per-row scale
        # column — the decoded fp32 gradient exists only on this VMEM block
        g = g * refs[k][...]
        k += 1
    _fold_update(pl.program_id(0), sc_ref, g, m_in, v_in, refs[k:k + nm],
                 refs[k + nm:], mk=mk, vk=vk, beta1=beta1, beta2=beta2,
                 guarded=guarded)


def _fold_update(i, sc_ref, g, m_in, v_in, m_out, v_out, *, mk, vk, beta1,
                 beta2, guarded):
    if guarded:
        ok = sc_ref[2] != 0.0
        m_out = tuple(_GuardedRef(o, p, ok) for o, p in zip(m_out, m_in))
        v_out = tuple(_GuardedRef(o, p, ok) for o, p in zip(v_out, v_in))
    mk.update(i, m_in, m_out, g, sc_ref[0], 1.0 - beta1)
    vk.update(i, v_in, v_out, g * g, sc_ref[1], 1.0 - beta2)


def _fold_guard_setup(g, scale, guard):
    """Shared fold/fold_slice guard plumbing. Returns (flag, body_scale,
    scalars_fn, guarded): `flag` is the finite flag actually used (None
    when unguarded), `body_scale` the static scale to bake (None = read
    from SMEM), `scalars_fn(decay)` builds the SMEM scalar vector.

    guard=None with a static python scale is the LEGACY path — (2,)
    scalars, scale baked, bitwise identical to the unguarded builders.
    guard=True self-checks: flag = isfinite over the packed slab, computed
    BEFORE the pallas_call commits any state (one pass over a slab XLA
    fuses; an in-kernel two-pass check would double state traffic).
    A traced-array guard is used verbatim — the shard_map engines pass the
    post-reduce-scatter, psum-AGREED flag so all shards skip or none do."""
    static_scale = isinstance(scale, (int, float))
    if guard is None and static_scale:
        return None, float(scale), _decay_scalars, False
    if guard is True:
        flag = jnp.isfinite(g).all()
    elif guard is None:
        flag = None
    else:
        flag = guard
    ok = jnp.float32(1.0) if flag is None else flag
    return flag, None, lambda decay: _guard_scalars(decay, ok, scale), \
        flag is not None


def arena_fold(m, v, g, *, beta1: float, beta2: float, scale=1.0,
               decay=None, m_codec="fp32", v_codec="fp32", grad_dtype=None,
               grad_scale=None, guard=None, interpret=None):
    """Whole-arena fold for any (m_codec, v_codec) pair; one pallas_call.
    `m`/`v` are the codecs' column tuples (bare arrays for fp32); all state
    columns are aliased in-place. decay=(dm, dv) (traced ok) fuses the
    begin-minibatch decay pass for the ROW-INDEXED columns; replicated
    columns are decayed by the caller (see state_store.fold). `g` may be a
    bf16 wire slab (`grad_dtype` validates the declared wire); the upcast
    to fp32 happens inside the kernel.

    `guard` adds the fused finite guard: True computes the finite flag over
    the packed slab before the state commits; a traced bool array (the
    psum-agreed flag under shard_map) is used verbatim. Either way every
    m/v write is predicated on the flag, so a non-finite micro-batch is a
    BITWISE no-op fold, and the return becomes (m, v, flag). A traced
    (non-static) `scale` rides in SMEM alongside the flag.

    `g` may instead be a micro-batch's gradient sources
    (core/arena.py::gather_sources): the kernel then gathers each row
    block from them (`_fold_leaves`), with no guard, fp8 wire or kernel
    mesh, and m and v come out bitwise as from the packed slab."""
    mk, vk = kernel_codec("m", m_codec), kernel_codec("v", v_codec)
    m_parts, m_bare = _as_parts(m)
    v_parts, v_bare = _as_parts(v)
    from repro.core.arena import GradLeaves
    if isinstance(g, GradLeaves):
        FOLD_SOURCES["leaves"] += 1
        out = _fold_leaves(m_parts, v_parts, g, mk, vk, beta1=beta1,
                           beta2=beta2, scale=scale, decay=decay,
                           grad_dtype=grad_dtype, grad_scale=grad_scale,
                           guard=guard, interpret=interpret)
        m_new, v_new = _split(out, len(m_parts))
        return (m_new[0] if m_bare else m_new,
                v_new[0] if v_bare else v_new)
    FOLD_SOURCES["slab"] += 1
    rows = g.shape[0]
    assert g.shape == (rows, LANES), g.shape
    _check_grad_dtype(g, grad_dtype, grad_scale)
    _check_parts(m_parts, mk, rows)
    _check_parts(v_parts, vk, rows)
    block = min(BLOCK_ROWS, rows)
    assert rows % block == 0, (rows, block)
    nm, nv = len(m_parts), len(v_parts)
    flag, body_scale, scalars_fn, guarded = _fold_guard_setup(g, scale, guard)
    n_sc = 2 if body_scale is not None else 4
    has_gs = grad_scale is not None
    gs_in = [grad_scale] if has_gs else []

    def call(sc, *xs):              # xs: the (per-device) state + slab rows
        block = min(BLOCK_ROWS, xs[nm + nv].shape[0])
        gspec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
        gs_specs = [pl.BlockSpec((block, 1), lambda i: (i, 0))] * has_gs
        return pl.pallas_call(
            functools.partial(_fold_body, mk=mk, vk=vk, beta1=beta1,
                              beta2=beta2, scale=body_scale, nm=nm, nv=nv,
                              guarded=guarded, has_gs=has_gs),
            grid=(xs[nm + nv].shape[0] // block,),
            in_specs=[pl.BlockSpec((n_sc,), lambda i: (0,))]
            + _col_specs(mk.cols, block, sliced=False)
            + _col_specs(vk.cols, block, sliced=False) + [gspec] + gs_specs,
            out_specs=_col_specs(mk.cols, block, sliced=False)
            + _col_specs(vk.cols, block, sliced=False),
            out_shape=_out_shapes(xs[:nm + nv]),
            input_output_aliases={1 + k: k for k in range(nm + nv)},
            interpret=_interpret() if interpret is None else interpret,
            name="arena_fold",
        )(sc, *xs)

    row_cols = [c.row_indexed for c in mk.cols + vk.cols]
    out = _on_mesh(call, (scalars_fn(decay), *m_parts, *v_parts, g, *gs_in),
                   row_in=(False, *row_cols, True, *[True] * has_gs),
                   row_out=row_cols,
                   acc=[(k, 1 + k) for k, r in enumerate(row_cols) if not r])
    m_new, v_new = _split(out, nm)
    m_new = m_new[0] if m_bare else m_new
    v_new = v_new[0] if v_bare else v_new
    return (m_new, v_new, flag) if flag is not None else (m_new, v_new)


# Folds traced so far, by gradient source: "leaves" (arena_fold gathering
# from the gradient tree) or "slab" (a packed slab: arena_fold and
# arena_fold_slice); train/loop.py::train logs the step's counts.
FOLD_SOURCES: collections.Counter = collections.Counter()


def _leaf_tables(plan, block: int):
    """The gathered fold's scalar-prefetch table (int32, SMEM): per row
    block the first run it meets and how many, then per run its source,
    layer, first arena row and row count. Returns (table, offsets of the
    six columns)."""
    runs = plan.runs
    start = np.array([r.start for r in runs], np.int64)
    rows = np.array([r.rows for r in runs], np.int64)
    b0 = np.arange(plan.layout.rows // block, dtype=np.int64) * block
    first = np.searchsorted(start + rows, b0, side="right")
    count = np.searchsorted(start, b0 + block, side="left") - first
    # a block no run meets still reads its "first" run's fields: keep the
    # index inside the table
    first = np.minimum(first, len(runs) - 1)
    cols = [first, count, [r.src for r in runs], [r.layer for r in runs],
            start, rows]
    offs = np.cumsum([0] + [len(c) for c in cols[:-1]]).tolist()
    table = np.concatenate([np.asarray(c, np.int64) for c in cols])
    assert table.max() < 2 ** 31, "arena too large for int32 tables"
    return table.astype(np.int32), offs


def _leaf_fold_body(tbl, sc_ref, *refs, mk, vk, beta1, beta2, scale, nm,
                    nv, plan, block, offs):
    """The fold over row block i with the gradient gathered from the
    plan's sources: while block i folds, block i+1's gradient tiles are in
    flight into the other staging slot (two slots per staging buffer)."""
    from repro.core.arena import sublane_tile
    srcs = plan.sources
    ns, nbuf = len(srcs), len(plan.bufs)
    m_in, v_in = refs[:nm], refs[nm:nm + nv]
    arrays = refs[nm + nv:nm + nv + ns]
    k = nm + nv + ns
    m_out, v_out = refs[k:k + nm], refs[k + nm:k + nm + nv]
    k += nm + nv
    stag, g_asm, sem = refs[k:k + nbuf], refs[k + nbuf], refs[k + nbuf + 1]
    c_first, c_count, c_src, c_layer, c_start, c_rows = offs
    i = pl.program_id(0)

    def each_run(b, fn):
        """fn(source, layer, first arena row, block) for each run that
        meets block b: static code per source, picked by the table."""
        first = tbl[c_first + b]

        def run(r, carry):
            sid = tbl[c_src + r]
            for s in range(ns):
                pl.when(sid == s)(lambda s=s: fn(
                    s, tbl[c_layer + r], tbl[c_start + r], b))
            return carry
        jax.lax.fori_loop(first, first + tbl[c_count + b], run, 0)

    def span(s, start, b):
        """Arena rows [lo, hi) of block b that source s's run fills."""
        b0 = b * block
        return (jnp.maximum(b0, start),
                jnp.minimum(b0 + block, start + srcs[s].rows))

    def geometry(s):
        """(tile rows, log2 of it, residue in the tile) of source s."""
        src = srcs[s]
        t = sublane_tile(src.dtype)
        return t, t.bit_length() - 1, (src.row - src.phys_row) % t

    def copies(wait):
        def fn(s, layer, start, b):
            # whole source tiles [t0, t0+nt) land in the staging grid at
            # tile d0; nt is split into power-of-two DMAs so every copy
            # has a static shape and tile-aligned offsets
            t, sh, r = geometry(s)
            lo, hi = span(s, start, b)
            delta = start - srcs[s].phys_row    # arena row of source row 0
            t0 = (lo - delta) >> sh
            nt = ((hi - delta + t - 1) >> sh) - t0
            d0 = t0 + ((delta - r) >> sh) + (1 if r else 0) \
                - b * (block // t)
            slot = b % 2
            done = jnp.int32(0)
            most = min(block // t + 1, srcs[s].phys_rows // t)
            for bit in reversed(range(most.bit_length())):
                take = (nt >> bit) & 1

                @pl.when(take == 1)
                def _(done=done, n=1 << bit):
                    cp = pltpu.make_async_copy(
                        arrays[s].at[layer, pl.ds(t * (t0 + done), t * n)],
                        stag[srcs[s].buf].at[slot,
                                             pl.ds(t * (d0 + done), t * n)],
                        sem.at[slot])
                    cp.wait() if wait else cp.start()
                done = done + take * (1 << bit)
        return fn

    def prefetch(b, carry):
        each_run(b, copies(wait=False))
        return carry
    # step 0 also starts its own block; the last step has no next block
    jax.lax.fori_loop(jnp.where(i == 0, 0, i + 1),
                      jnp.minimum(i + 2, pl.num_programs(0)), prefetch, 0)
    each_run(i, copies(wait=True))

    def view(b):
        """Block i's rows of staging buffer b, rounded to the wire."""
        dtype, r = plan.bufs[b]
        t = sublane_tile(dtype)
        x = stag[b][i % 2, pl.ds((t - r) % t, block), :]
        return x.astype(plan.wire).astype(jnp.float32)

    s = sc_ref[3] if scale is None else scale

    def update(g):
        _fold_update(i, sc_ref, g * s, m_in, v_in, m_out, v_out, mk=mk,
                     vk=vk, beta1=beta1, beta2=beta2, guarded=False)

    b0 = i * block
    first = tbl[c_first + i]
    sid0, start0 = tbl[c_src + first], tbl[c_start + first]
    whole = (tbl[c_count + i] == 1) & (start0 <= b0) \
        & (start0 + tbl[c_rows + first] >= b0 + block)
    # the common block lies inside one run: fold straight from its
    # staging buffer
    buf0 = jnp.int32(0)
    for k, src in enumerate(srcs):
        buf0 = jnp.where(sid0 == k, src.buf, buf0)
    for b in range(nbuf):
        pl.when(whole & (buf0 == b))(lambda b=b: update(view(b)))

    @pl.when(jnp.logical_not(whole))
    def _():
        # a block that meets several runs or padding: rows no run fills
        # fold a zero gradient, as the slab's padding rows do
        g_asm[...] = jnp.zeros((block, LANES), jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 0) + b0

        def fill(k, layer, start, b):
            lo, hi = span(k, start, b)
            g_asm[...] = jnp.where((row >= lo) & (row < hi),
                                   view(srcs[k].buf), g_asm[...])
        each_run(i, fill)
        update(g_asm[...])


def _fold_leaves(m_parts, v_parts, g, mk, vk, *, beta1, beta2, scale,
                 decay, grad_dtype, grad_scale, guard, interpret):
    """arena_fold with its gradient gathered from a micro-batch's gradient
    sources (core/arena.py::GradLeaves): the (rows, LANES) slab is never
    written. One pallas_call over the arena's row blocks, m and v aliased
    in place, the same codec update as the slab fold: m and v come out
    bitwise equal to packing the tree and folding the slab."""
    from repro.core.arena import sublane_tile
    from repro.sharding.ctx import kernel_mesh
    plan = g.plan
    if guard is not None or grad_scale is not None:
        raise ValueError("the gathered fold has no finite guard or fp8 "
                         "wire: fold a packed slab for those")
    if kernel_mesh() is not None:
        raise ValueError("the gathered fold does not split rows over a "
                         "kernel mesh: fold a packed slab there")
    if grad_dtype is not None and jnp.dtype(grad_dtype) != plan.wire:
        raise TypeError(f"gradient sources on a {plan.wire} wire != "
                        f"declared grad_dtype {jnp.dtype(grad_dtype)}")
    rows = plan.layout.rows
    _check_parts(m_parts, mk, rows)
    _check_parts(v_parts, vk, rows)
    block = min(BLOCK_ROWS, rows)
    assert rows % block == 0, (rows, block)
    assert all(block % sublane_tile(d) == 0 for d, _ in plan.bufs), block
    nm, nv = len(m_parts), len(v_parts)
    _, body_scale, scalars_fn, _ = _fold_guard_setup(None, scale, None)
    table, offs = _leaf_tables(plan, block)
    state = _col_specs(mk.cols, block, sliced=False) \
        + _col_specs(vk.cols, block, sliced=False)
    scratch = [pltpu.VMEM((2, block + sublane_tile(d), LANES), d)
               for d, _ in plan.bufs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # (run table, scalar vec)
        grid=(rows // block,),
        in_specs=state + [pl.BlockSpec(memory_space=pl.ANY)] * len(g.arrays),
        out_specs=state,
        scratch_shapes=scratch + [pltpu.VMEM((block, LANES), jnp.float32),
                                  pltpu.SemaphoreType.DMA((2,))])
    call = pl.pallas_call(
        functools.partial(_leaf_fold_body, mk=mk, vk=vk, beta1=beta1,
                          beta2=beta2, scale=body_scale, nm=nm, nv=nv,
                          plan=plan, block=block, offs=offs),
        grid_spec=grid_spec,
        out_shape=_out_shapes(m_parts + v_parts),
        input_output_aliases={2 + k: k for k in range(nm + nv)},
        # blocks run in order: block i prefetches block i+1's gradient
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=_interpret() if interpret is None else interpret,
        name="arena_fold",
    )
    return call(jnp.asarray(table), scalars_fn(decay), *m_parts, *v_parts,
                *g.arrays)


def _slice_fold_body(off_ref, sc_ref, *refs, mk, vk, beta1, beta2, scale,
                     nm, nv, guarded=False, has_gs=False):
    del off_ref                      # consumed by the index maps
    _fold_body(sc_ref, *refs, mk=mk, vk=vk, beta1=beta1, beta2=beta2,
               scale=scale, nm=nm, nv=nv, guarded=guarded, has_gs=has_gs)


def arena_fold_slice(m, v, g, row_offset, *, beta1: float, beta2: float,
                     block: int, scale=1.0, decay=None,
                     m_codec="fp32", v_codec="fp32", grad_dtype=None,
                     grad_scale=None, guard=None, interpret=None):
    """Fold a (rows_g, LANES) gradient slab into arena rows
    [row_offset, row_offset+rows_g) for any codec pair. `row_offset` may be
    traced but must be a multiple of `block` (layout.slice_block guarantees
    it). Row-indexed columns outside the slice pass through untouched via
    input->output aliasing; replicated columns (rowcol's column sums) are
    updated by every slice — their decay is the caller's job, once per
    micro-batch (state_store begin_micro). Like arena_fold, `g` may be a
    bf16 wire slab — upcast in-kernel.

    `guard`/traced `scale` behave exactly as in arena_fold: guarded state
    writes are predicated (bitwise no-op slice fold when the flag is
    false), the return gains the flag. Under the bucketed ZeRO-1 schedule
    the caller passes the post-reduce-scatter, psum-agreed flag so all
    shards of the bucket skip or none do."""
    FOLD_SOURCES["slab"] += 1
    mk, vk = kernel_codec("m", m_codec), kernel_codec("v", v_codec)
    m_parts, m_bare = _as_parts(m)
    v_parts, v_bare = _as_parts(v)
    rows = next(p.shape[0] for p, c in zip(m_parts, mk.cols) if c.row_indexed)
    rows_g = g.shape[0]
    assert g.shape[1] == LANES and rows_g % block == 0, (g.shape, block)
    _check_grad_dtype(g, grad_dtype, grad_scale)
    _check_parts(m_parts, mk, rows)
    _check_parts(v_parts, vk, rows)
    nm, nv = len(m_parts), len(v_parts)
    flag, body_scale, scalars_fn, guarded = _fold_guard_setup(g, scale, guard)
    gspec = pl.BlockSpec((block, LANES), lambda i, off, sc: (i, 0))
    has_gs = grad_scale is not None
    gs_in = [grad_scale] if has_gs else []
    gs_specs = [pl.BlockSpec((block, 1), lambda i, off, sc: (i, 0))] \
        if has_gs else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # (row offset, scalar vec)
        grid=(rows_g // block,),
        in_specs=_col_specs(mk.cols, block, sliced=True)
        + _col_specs(vk.cols, block, sliced=True) + [gspec] + gs_specs,
        out_specs=_col_specs(mk.cols, block, sliced=True)
        + _col_specs(vk.cols, block, sliced=True),
    )
    off = jnp.asarray(row_offset, jnp.int32).reshape(1) // block
    call = pl.pallas_call(
        functools.partial(_slice_fold_body, mk=mk, vk=vk, beta1=beta1,
                          beta2=beta2, scale=body_scale, nm=nm, nv=nv,
                          guarded=guarded, has_gs=has_gs),
        grid_spec=grid_spec,
        out_shape=_out_shapes(m_parts + v_parts),
        input_output_aliases={2 + k: k for k in range(nm + nv)},
        interpret=_interpret() if interpret is None else interpret,
        name="arena_fold_slice",
    )
    # the offset addresses global arena rows, so under a pjit mesh every
    # device folds the whole slice into the whole arena (the shard_map
    # ZeRO-1 engine is this path's row-sharded form)
    out = _on_mesh(call, (off, scalars_fn(decay), *m_parts, *v_parts, g,
                          *gs_in), row_in=(), row_out=[False] * (nm + nv))
    m_new, v_new = _split(out, nm)
    m_new = m_new[0] if m_bare else m_new
    v_new = v_new[0] if v_bare else v_new
    return (m_new, v_new, flag) if flag is not None else (m_new, v_new)


def _apply_body(sc_ref, *refs, mk, vk, eps, weight_decay, nm, nv, emit_work,
                guarded=False):
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]
    p_ref = refs[0]
    m_vals = tuple(r[...] for r in refs[1:1 + nm])
    v_vals = tuple(r[...] for r in refs[1 + nm:1 + nm + nv])
    po_ref = refs[1 + nm + nv]
    p = p_ref[...].astype(jnp.float32)
    mh = mk.decode(m_vals) / bc1
    vh = vk.decode(v_vals) / bc2                 # broadcasts over lanes
    u = mh / (jnp.sqrt(vh) + eps)
    if weight_decay:
        u = u + weight_decay * p
    p_new = p - lr * u
    if guarded:
        # all-micro-batches-skipped mini-batch: the apply is an identity
        # (bitwise — `where` discards p_new, including the NaNs a bc1=0
        # bias correction produces when the step counter never advanced)
        p_new = jnp.where(sc_ref[3] != 0.0, p_new, p)
    po_ref[...] = p_new.astype(po_ref.dtype)
    if emit_work:
        # working params: the SAME fp32 update, cast once to the work
        # dtype — the master (po_ref) never round-trips through it
        wo_ref = refs[1 + nm + nv + 1]
        wo_ref[...] = p_new.astype(wo_ref.dtype)


def arena_apply(p, m, v, *, lr, bc1, bc2, eps: float = 1e-8,
                weight_decay: float = 0.0, m_codec="fp32", v_codec="fp32",
                work_dtype=None, guard=None, interpret=None):
    """Bias-corrected apply over the packed param arena for any codec pair,
    decoding both moments in-pass; p aliased in-place.

    `work_dtype` turns on the master-param form: `p` is the fp32 MASTER
    region and the same single kernel additionally emits a `work_dtype`
    (bf16) working-param arena — the return value becomes the
    (master_new, work) pair. One extra output column set, still one
    pallas_call.

    `guard` (traced bool, e.g. `good > 0` after a guarded fold scan)
    predicates the whole update: when false the params pass through
    BITWISE (an all-skipped mini-batch applies nothing — in particular the
    bc1=0 NaNs of a never-advanced step counter are discarded, not
    applied). The flag rides in the SMEM scalar vector like the fold's."""
    mk, vk = kernel_codec("m", m_codec), kernel_codec("v", v_codec)
    m_parts, _ = _as_parts(m)
    v_parts, _ = _as_parts(v)
    rows = p.shape[0]
    assert p.shape[1] == LANES, p.shape
    _check_parts(m_parts, mk, rows)
    _check_parts(v_parts, vk, rows)
    block = min(BLOCK_ROWS, rows)
    assert rows % block == 0, (rows, block)
    nm, nv = len(m_parts), len(v_parts)
    guarded = guard is not None
    sc = [jnp.asarray(lr, jnp.float32),
          jnp.asarray(bc1, jnp.float32),
          jnp.asarray(bc2, jnp.float32)]
    if guarded:
        sc.append(jnp.asarray(guard, jnp.float32))
    scalars = jnp.stack(sc)
    emit_work = work_dtype is not None
    if emit_work:
        assert p.dtype == jnp.float32, \
            f"master-param apply requires an fp32 master region, got {p.dtype}"

    def call(sc, p, *mv):           # p, mv: the (per-device) arena rows
        block = min(BLOCK_ROWS, p.shape[0])
        pspec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct(p.shape, p.dtype)
        if emit_work:
            out_shape = [out_shape,
                         jax.ShapeDtypeStruct(p.shape, jnp.dtype(work_dtype))]
        return pl.pallas_call(
            functools.partial(_apply_body, mk=mk, vk=vk, eps=eps,
                              weight_decay=weight_decay, nm=nm, nv=nv,
                              emit_work=emit_work, guarded=guarded),
            grid=(p.shape[0] // block,),
            in_specs=[pl.BlockSpec((4 if guarded else 3,), lambda i: (0,)),
                      pspec]
            + _col_specs(mk.cols, block, sliced=False)
            + _col_specs(vk.cols, block, sliced=False),
            out_specs=[pspec, pspec] if emit_work else pspec,
            out_shape=out_shape,
            input_output_aliases={1: 0},
            interpret=_interpret() if interpret is None else interpret,
            name="arena_apply",
        )(sc, p, *mv)

    out = _on_mesh(call, (scalars, p, *m_parts, *v_parts),
                   row_in=(False, True,
                           *[c.row_indexed for c in mk.cols + vk.cols]),
                   row_out=[True, True] if emit_work else True)
    return tuple(out) if emit_work else out
