"""scatter_ms: device ms per step of the non-Pallas ops under
``repro:scatter:*`` scopes inside a GEMM dispatch: the compact queue's
scatter of its tiles (and emitted bitmaps) back to the dense layout, and
the unpad of the result.  A part of ``dispatch_ms``."""
from chipbench import attribution


def read(ctx):
    return attribution.per_step_ms(ctx, "scatter", attribution.dispatched)
