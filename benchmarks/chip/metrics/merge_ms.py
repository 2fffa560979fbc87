"""merge_ms: device ms per step under ``repro:merge:*`` scopes that no
other reader of the step's time takes: a branch's residual add or concat,
the ReLU on entry to a branch, and their backward passes.  A part of
``unscoped_ms``; only cells whose model has branches list it."""
from chipbench import attribution


def read(ctx):
    return attribution.per_step_ms(ctx, "merge", attribution.unscoped)
