"""Production meshes.

Target: TPU v5e, 256 chips per pod. Single-pod mesh is (data=16, model=16);
multi-pod is (pod=2, data=16, model=16) = 512 chips, with the "pod" axis an
outer data-parallel axis (AdamA's optimizer-state all-reduce crosses it once
per mini-batch, which is what makes the schedule multi-pod-friendly: 2 x P
bytes over DCI per mini-batch regardless of micro-batch count).

Defined as functions so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis Auto (GSPMD propagates shardings; the
    shard_map engines mark their own axes manual)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            "=512 BEFORE importing jax (launch/dryrun.py does this).")
    return make_mesh(shape, axes, devices=devices)


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = 1
    for s in shape:
        n *= s
    return make_mesh(shape, axes, devices=jax.devices()[:n])
