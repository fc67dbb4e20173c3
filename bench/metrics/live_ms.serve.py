"""Milliseconds per serve cycle in the live window
(``ServeController._live_window``).
Read from the program's ``serve.live`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "serve.live")
