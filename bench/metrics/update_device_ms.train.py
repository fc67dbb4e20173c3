"""Device milliseconds per update of the policy-update program
(``core/policy.py`` ``_update_step``)."""
import trace_reduce

#: the update step is jitted from a ``functools.partial``, which carries no
#: name, so XLA calls its module ``jit__unknown``
MODULE = "jit__unknown"


def read(ctx):
    if ctx.units <= 0:
        return None
    s = trace_reduce.module_seconds(ctx.view, MODULE)
    return 1e3 * s / ctx.units if s > 0 else None
