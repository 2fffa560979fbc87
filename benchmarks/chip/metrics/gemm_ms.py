"""gemm_ms: device ms per step of the Pallas grouped-GEMM kernels: the
``tpu_custom_call`` ops under ``repro:gemm:*`` scopes."""
from chipbench.tracing import in_scope


def read(ctx):
    return ctx.per_step_ms(lambda op: op.pallas and in_scope(op, "gemm"))
