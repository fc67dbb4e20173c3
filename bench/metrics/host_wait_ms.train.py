"""Host milliseconds per update blocked on the device: the episode's
outputs in ``finalize`` and the update's statistics.
Read from the program's ``tune.wait`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "tune.wait")
