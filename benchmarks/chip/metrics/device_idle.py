"""device_idle: 1 - (union of the op intervals on a device) / the traced
window, averaged over chips, in percent."""


def read(ctx):
    if not ctx.reduced.devices or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.reduced.busy_s() / ctx.reduced.window_s())
