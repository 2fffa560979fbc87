"""norm_ms: device ms per step under ``repro:norm:*`` scopes that no other
reader of the step's time takes: BatchNorm, forward and backward.  A part
of ``unscoped_ms``; only cells whose model has BatchNorm list it."""
from chipbench import attribution


def read(ctx):
    return attribution.per_step_ms(ctx, "norm", attribution.unscoped)
