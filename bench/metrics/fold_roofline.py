"""fold_roofline (%): the HBM bytes the `arena_fold` kernel calls of the
window must move (bench/benchkit/flops.py::fold_bytes, from the arena's
rows per chip, the m/v codecs and the gradient wire), over 819 GB/s, over
the kernel's summed device time; mean over chips (layer: kernels,
kernels/fused_step.py)."""
from benchkit import kernels


def read(ctx):
    return kernels.roofline(ctx, "fold")
