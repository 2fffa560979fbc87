"""layout_ms: device ms per step under ``repro:layout:*`` scopes that no
other reader of the step's time takes: the conv engine's data-side
re-layout in FP, BP and WG (activation and gradient im2col, dy dilation,
weight flips and transposes, the σ′ multiplier, ``pm.T``, group and output
reshapes).  A part of ``unscoped_ms``."""
from chipbench import attribution


def read(ctx):
    return attribution.per_step_ms(ctx, "layout", attribution.unscoped)
