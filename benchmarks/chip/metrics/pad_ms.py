"""pad_ms: device ms per step of the non-Pallas ops under ``repro:pad:*``
scopes inside a GEMM dispatch: the padding of operands, masks and the
epilogue multiplier to whole tiles.  A part of ``dispatch_ms``."""
from chipbench import attribution


def read(ctx):
    return attribution.per_step_ms(ctx, "pad", attribution.dispatched)
