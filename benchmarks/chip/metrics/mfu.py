"""mfu: dense model FLOPs per image (``chipbench.flops``) times the traced
window's images per second, over chips times the chip's peak (``peaks.json``,
the key the config names), in percent."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.reduced.devices:
        return None
    rate = ctx.flops_per_image * ctx.images / ctx.window_s
    return 100.0 * rate / (ctx.chips * ctx.peak_flops)
