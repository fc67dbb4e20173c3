"""Host milliseconds per update building the ``StepRecord``s of the
episode batch (``DeviceEpisodeRunner._materialise``).
Read from the program's ``tune.records`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "tune.records")
