"""Host milliseconds per update replaying the chosen bins into the
adaptive discretiser (§2.4.1, ``DynamicBins.record_many``).
Read from the program's ``tune.replay`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "tune.replay")
