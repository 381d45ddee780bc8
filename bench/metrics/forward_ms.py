"""forward_ms (ms): device self time per step of the window of the
operations under the program's `model` scope and not under a
`transpose(` or a remat recompute: the forward pass; mean over chips
(layer: train step, models/; bench/benchkit/scopes.py)."""
from benchkit import scopes


def read(ctx):
    return scopes.device_ms(ctx).get("forward")
