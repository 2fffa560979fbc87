"""unscoped_ms: device ms per step of the operations that no other
reader of the step's time takes: those outside the ``repro:derive:*``,
``repro:gemm:*`` and ``repro:queue:*`` scopes and other than the Pallas
encode kernels.  On a v5e these are the copies, reshapes and transposes
under a ``layer:*`` scope, and the unscoped head, loss and SGD update;
with ``derive_ms``, ``dispatch_ms``, ``gemm_ms`` and ``encode_ms`` it
accounts for the whole traced step."""
from chipbench.tracing import in_scope


def _read_elsewhere(op) -> bool:
    return (in_scope(op, "derive")
            or (op.pallas and in_scope(op, "gemm", "encode"))
            or (not op.pallas and in_scope(op, "gemm", "queue")))


def read(ctx):
    return ctx.per_step_ms(lambda op: not _read_elsewhere(op))
