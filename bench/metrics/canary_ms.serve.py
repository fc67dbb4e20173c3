"""Milliseconds per serve cycle in the paired canary evaluation
(``ServeController._canary_eval``).
Read from the program's ``serve.canary`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "serve.canary")
