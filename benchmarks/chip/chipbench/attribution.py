"""The program's attribution scopes, as the per-layer readers take them.

Besides its bitmap-lifecycle scopes the program names where the rest of a
step's device time goes with ``repro:<kind>:`` scopes of six more kinds
(``kernels/stats.py``): ``layout``, ``pool``, ``norm`` and ``merge`` split
what ``unscoped_ms`` reads; ``pad`` and ``scatter``, nested inside a
``repro:gemm:`` scope, split what ``dispatch_ms`` reads.  An op belongs to
the innermost attribution scope of its ``op_name``, so no op is read by
two of these readers.
"""
from __future__ import annotations

import re
from typing import Callable, Optional

from chipbench.tracing import Context, Op, in_scope

KINDS = ("layout", "pool", "norm", "merge", "pad", "scatter")
_TAG = re.compile(r"repro:(%s):" % "|".join(KINDS))


def kind(op: Op) -> Optional[str]:
    """The innermost attribution kind of the op's scope, if any."""
    tags = _TAG.findall(op.scope)
    return tags[-1] if tags else None


def unscoped(op: Op) -> bool:
    """The op is one that ``unscoped_ms`` reads: no other reader of the
    step's device time takes it."""
    return not (in_scope(op, "derive")
                or (op.pallas and in_scope(op, "gemm", "encode"))
                or (not op.pallas and in_scope(op, "gemm", "queue")))


def dispatched(op: Op) -> bool:
    """The op is one that ``dispatch_ms`` reads."""
    return not op.pallas and in_scope(op, "gemm", "queue")


def per_step_ms(ctx: Context, of: str,
                within: Callable[[Op], bool]) -> Optional[float]:
    """Device ms per step of the ops of attribution kind ``of`` that
    ``within`` keeps.  None where the trace holds none of them; 0 where
    the trace's program names no attribution scope at all (a program
    older than these scopes: none of its time is attributed here)."""
    got = ctx.per_step_ms(lambda op: kind(op) == of and within(op))
    if got is None and ctx.reduced.devices and not any(
            kind(o) for d in ctx.reduced.devices for o in ctx.reduced.ops[d]):
        return 0.0
    return got
