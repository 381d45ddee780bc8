"""grad_pack_ms (ms): device self time per step of the window of the
operations under `optimizer.grad_pack`: each micro-batch's gradient tree
packed into an arena slab (and, on an fp8 wire, encoded); mean over chips
(layer: arena, core/arena.py via core/adama.py;
bench/benchkit/scopes.py)."""
from benchkit import scopes


def read(ctx):
    return scopes.device_ms(ctx).get("grad_pack")
