"""collective_exposed_ms (ms): per step, the device time of reduce-scatter,
all-gather, all-reduce and other collectives during which no other
operation ran on that chip, on the worst chip (layer: sharding, ZeRO-1 in
core/accumulation.py). Only cells on several chips have collectives."""


def read(ctx):
    if ctx.trace is None or ctx.chips < 2 or ctx.info.steps == 0:
        return None
    worst = max(ctx.trace.exposed_collective_s(c) for c in ctx.trace.ops)
    return 1e3 * worst / ctx.info.steps
