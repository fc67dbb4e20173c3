"""Milliseconds per serve cycle in the interpreter's garbage collections.
Read from the program's ``python.gc`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "python.gc")
