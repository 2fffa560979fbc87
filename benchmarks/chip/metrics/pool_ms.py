"""pool_ms: device ms per step under ``repro:pool:*`` scopes that no other
reader of the step's time takes: max and average pools, the global
average pool, the ReLU just before each, and their backward passes.  A
part of ``unscoped_ms``."""
from chipbench import attribution


def read(ctx):
    return attribution.per_step_ms(ctx, "pool", attribution.unscoped)
