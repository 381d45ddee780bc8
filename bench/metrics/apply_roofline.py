"""apply_roofline (%): the same as fold_roofline for the `arena_apply`
kernel: params, m and v read, params written (layer: kernels,
kernels/fused_step.py)."""
from benchkit import kernels


def read(ctx):
    return kernels.roofline(ctx, "apply")
