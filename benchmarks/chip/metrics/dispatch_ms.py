"""dispatch_ms: device ms per step under ``repro:gemm:*`` and
``repro:queue:*`` scopes that are not the Pallas GEMM kernels themselves:
operand padding, tile-queue construction, the compact schedule's scatter
back to the dense output."""
from chipbench.tracing import in_scope


def read(ctx):
    return ctx.per_step_ms(lambda op: in_scope(op, "gemm", "queue")
                           and not op.pallas)
