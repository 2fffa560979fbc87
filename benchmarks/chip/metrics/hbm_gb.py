"""hbm_gb: the step executable's compiled footprint per chip, argument +
output + temporary - aliased bytes from ``memory_analysis()``, in GB
(1e9 bytes).  The compiler's own count, not the runtime's peak."""


def read(ctx):
    return ctx.hbm_bytes / 1e9 if ctx.hbm_bytes else None
