"""backward_ms (ms): device self time per step of the window of the
operations under `transpose(jvp(model))`, remat recompute included, or
under `model.recompute` (the layer-wise engine's recompute): the
backward pass; mean over chips (layer: train step, models/;
bench/benchkit/scopes.py)."""
from benchkit import scopes


def read(ctx):
    return scopes.device_ms(ctx).get("backward")
