"""derive_ms: device ms per step under ``repro:derive:*`` scopes (im2col of
activations and of bitmaps, gradient patches, bitmap coarsening, the
weight-gradient operand masks), averaged over chips."""
from chipbench.tracing import in_scope


def read(ctx):
    return ctx.per_step_ms(lambda op: in_scope(op, "derive"))
