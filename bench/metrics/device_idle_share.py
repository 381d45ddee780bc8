"""device_idle_share (%): 1 - the union of device-operation intervals over
the traced window, averaged over the cell's chips (layer: device)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()
