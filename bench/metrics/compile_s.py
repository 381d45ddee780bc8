"""compile_s (s): `train()`'s own `compile_s`, the seconds it took to lower
and compile the step (read from the persistent cache after a cell's first
run) (layer: entry, launch/)."""


def read(ctx):
    return ctx.info.compile_s
