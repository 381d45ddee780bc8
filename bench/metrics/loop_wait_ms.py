"""loop_wait_ms (ms): device idle time per step of the window that
overlaps the loop's other per-step spans (`train.dispatch`, `train.sync`,
`train.log`, `train.checkpoint`), interval by interval, with each
chip's clock first aligned to the host's; mean over chips
(layer: host loop, train/loop.py; bench/benchkit/scopes.py)."""
from benchkit import scopes


def read(ctx):
    return scopes.wait_ms(ctx, scopes.LOOP_SPANS)
