"""Milliseconds per serve cycle in the shadow pass (one tuning update on
the shadow fleet, ``ServeController.run_cycle``).
Read from the program's ``serve.shadow`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "serve.shadow")
