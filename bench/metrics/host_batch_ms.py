"""host_batch_ms (ms): host time per step inside the benchmark's token
stream (`batch()`, the `bench.batch` span the harness wraps it in), mean
over the batches built in the traced window (layer: host loop,
train/loop.py)."""


def read(ctx):
    if ctx.trace is None:
        return None
    spans = ctx.trace.host_spans("bench.batch")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
