"""Device programs launched per serve cycle (all modules in the traced
window over the cycles completed in it)."""
import trace_reduce


def read(ctx):
    if ctx.units <= 0:
        return None
    n = trace_reduce.module_count(ctx.view)
    return n / ctx.units if n > 0 else None
