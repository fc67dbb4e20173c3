"""Device milliseconds per update of the fused episode program
(``device_loop._episode_fn`` -> ``fleet_jax.build_step_window``)."""
import trace_reduce

MODULE = "jit_program"


def read(ctx):
    if ctx.units <= 0:
        return None
    s = trace_reduce.module_seconds(ctx.view, MODULE)
    return 1e3 * s / ctx.units if s > 0 else None
