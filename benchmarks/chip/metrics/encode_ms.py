"""encode_ms: device ms per step of the fused ReLU + bitmap encode kernel:
the ``tpu_custom_call`` ops under ``repro:encode:*`` scopes."""
from chipbench.tracing import in_scope


def read(ctx):
    return ctx.per_step_ms(lambda op: op.pallas and in_scope(op, "encode"))
