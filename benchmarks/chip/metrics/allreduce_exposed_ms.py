"""allreduce_exposed_ms: device ms per step, averaged over chips, during
which a collective operation runs on a chip and no other operation does.

A collective operation is one under a ``repro:collective:*`` scope, or an
``all-reduce``, ``reduce-scatter``, ``all-gather`` or
``collective-permute`` HLO instruction, by its name: XLA's all-reduce
combiner builds the combined instruction without the scopes of the ones it
merged.  An asynchronous pair counts from the start of its ``-start`` op
to the end of its ``-done`` op (paired in order, kind by kind), whatever
runs between them; the time in which another operation runs on that chip
is hidden behind it and left out.  None where the trace holds no
collective operation.
"""
import re

from chipbench.tracing import _union, in_scope

_KIND = re.compile(r"^(all-reduce|reduce-scatter|all-gather|"
                   r"collective-permute)(-start|-done)?(\.\d+)?$")


def _collective(op):
    """(kind, "-start" | "-done" | "") of a collective op, else None."""
    m = _KIND.match(op.name)
    if m:
        return m.group(1), m.group(2) or ""
    if in_scope(op, "collective"):
        return "scoped", ""
    return None


def _intervals(ops):
    """(collective intervals, other ops' intervals) of one chip."""
    coll, other, open_ = [], [], {}
    for op in sorted(ops, key=lambda o: o.start):
        c = _collective(op)
        if c is None:
            other.append((op.start, op.end))
        elif c[1] == "-start":
            open_.setdefault(c[0], []).append(op)
        elif c[1] == "-done" and open_.get(c[0]):
            coll.append((open_[c[0]].pop(0).start, op.end))
        else:
            coll.append((op.start, op.end))
    coll += [(o.start, o.end) for starts in open_.values() for o in starts]
    return coll, other


def exposed_ns(coll, other) -> float:
    """Length of the union of ``coll`` that the union of ``other`` leaves
    uncovered."""
    busy = _union(other)
    total, j = 0.0, 0
    for s, e in _union(coll):
        total += e - s
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < e:
            total -= min(e, busy[k][1]) - max(s, busy[k][0])
            k += 1
    return total


def read(ctx):
    r = ctx.reduced
    found, total = False, 0.0
    for d in r.devices:
        coll, other = _intervals(r.ops[d])
        found = found or bool(coll)
        total += exposed_ns(coll, other)
    if not found:
        return None
    return total / len(r.devices) / ctx.steps / 1e6
