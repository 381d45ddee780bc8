"""optimizer_ms (ms): device self time per step of the window of the
operations under `optimizer.fold`, `optimizer.accumulate` and
`optimizer.apply`: the folds into (m, v), ga's gradient accumulate, and
the apply with its parameter pack and unpack; mean over chips (layer:
optimizer, core/accumulation.py and kernels/fused_step.py;
bench/benchkit/scopes.py)."""
from benchkit import scopes


def read(ctx):
    return scopes.device_ms(ctx).get("optimizer")
