"""Activation-sharding context: model code stays mesh-agnostic; the launcher
installs a mesh + dp axes here and `maybe_shard` becomes a no-op otherwise."""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_MESH = contextvars.ContextVar("repro_mesh", default=None)
_DP = contextvars.ContextVar("repro_dp_axes", default=())
_MANUAL = contextvars.ContextVar("repro_manual_axes", default=())
_TP = contextvars.ContextVar("repro_tp_axis", default=None)


@contextlib.contextmanager
def use_mesh(mesh, dp_axes: Tuple[str, ...],
             manual_axes: Tuple[str, ...] = (),
             tp_axis: Optional[str] = None):
    """Install mesh + dp axes for `maybe_shard`. `manual_axes`: axes a
    surrounding shard_map holds MANUAL — with_sharding_constraint inside
    the manual region may not reference them (jax raises "Axis ... is also
    found in manual_axes"), so maybe_shard silently drops them from every
    constraint it emits. Under the pure-DP shard_map profile every mesh
    axis is manual and the constraints degrade to no-ops, which is correct:
    the values they would pin are already device-local.

    `tp_axis` composes the logical axes onto a 2D dp×tp mesh: the "tp"
    sentinel in maybe_shard specs resolves to it. In the MIXED manual-dp ×
    auto-tp regime (shard_map manual over dp_axes only), the manual filter
    above drops exactly the dp axes from each constraint and KEEPS the tp
    entries — the surviving constraint is what GSPMD needs to keep the
    auto-TP param sharding pinned inside the manual region. With no tp_axis
    installed the "tp" sentinel degrades to None (replicated), keeping
    model code mesh-agnostic."""
    t1 = _MESH.set(mesh)
    t2 = _DP.set(tuple(dp_axes))
    t3 = _MANUAL.set(tuple(manual_axes))
    t4 = _TP.set(tp_axis)
    try:
        yield
    finally:
        _MESH.reset(t1)
        _DP.reset(t2)
        _MANUAL.reset(t3)
        _TP.reset(t4)


def dp_axes() -> Tuple[str, ...]:
    return _DP.get()


def kernel_mesh():
    """(mesh, dp axes) when a multi-device mesh is installed and no
    surrounding shard_map holds any of its axes manual — the pjit engine's
    regime, in which XLA would have to partition a Mosaic kernel and cannot
    (kernels/fused_step.py shard_maps its kernels over this mesh) — else
    None."""
    mesh = _MESH.get()
    if mesh is None or mesh.size == 1 or _MANUAL.get():
        return None
    return mesh, _DP.get()


def tp_axis() -> Optional[str]:
    return _TP.get()


def _dp_if_divides(n: int):
    """The installed dp axes when a batch dim of size `n` splits evenly
    over them, else None (replicated)."""
    mesh, dp = _MESH.get(), _DP.get()
    if mesh is None or not dp:
        return None
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    return dp if n % size == 0 else None


def shard_micro_batches(x):
    """Pin (N, B/N, ...) micro-batches: each micro-batch's rows over dp
    (when they divide), the micro-batch axis unsharded, so every step of
    the scan over micro-batches finds its rows already split."""
    if _MESH.get() is None:
        return x
    return maybe_shard(x, None, _dp_if_divides(x.shape[1]))


def shard_attention_operand(x):
    """Pin (B, H, S, d) attention operands: batch over dp, heads over
    "model" when divisible, everything else replicated. Without this GSPMD
    sometimes shards the kv-block (contraction) dim in the backward
    recompute, all-reducing the (B,H,Sq,hv) accumulator once per kv block
    (observed 1.5 TiB/step on hymba-1.5b)."""
    mesh = _MESH.get()
    if mesh is None or x.ndim != 4:
        return x
    tp = mesh.shape.get("model", 1)
    dp = _DP.get()
    b_ax = _dp_if_divides(x.shape[0])
    h_ax = "model" if (tp > 1 and x.shape[1] % tp == 0 and
                       "model" not in (dp or ())) else None
    return maybe_shard(x, b_ax, h_ax, None, None)


def maybe_shard(x, *spec_entries):
    """Constrain `x` to P(*spec_entries) if a mesh is installed. Entries may
    include the sentinels "dp" (expands to the installed dp axes) and "tp"
    (expands to the installed tp axis, or None when the mesh has no tensor
    axis — logical-axis specs compose onto any mesh shape)."""
    mesh = _MESH.get()
    if mesh is None:
        return x
    entries = tuple(_DP.get() if e == "dp" else
                    _TP.get() if e == "tp" else e for e in spec_entries)
    entries = tuple(None if e == () else e for e in entries)
    # an axis may appear only once in a PartitionSpec: when the dp group
    # already covers "model" (pure-DP profile) drop later duplicates
    used = set()
    dedup = []
    for e in entries:
        axes = e if isinstance(e, tuple) else (e,) if e else ()
        if any(a in used for a in axes):
            dedup.append(None)
            continue
        used.update(axes)
        dedup.append(e)
    manual = set(_MANUAL.get())
    if manual:
        # a constraint may not name an axis a surrounding shard_map holds
        # manual — drop those axes; skip the call entirely if nothing is
        # left to constrain
        filt = []
        for e in dedup:
            axes = e if isinstance(e, tuple) else (e,) if e else ()
            keep = tuple(a for a in axes if a not in manual)
            filt.append(keep if len(keep) > 1
                        else (keep[0] if keep else None))
        dedup = filt
        if all(e is None for e in dedup):
            return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*dedup)))
