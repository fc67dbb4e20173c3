"""Host milliseconds per update pulling the episode's outputs and carry to
the host (``DeviceEpisodeRunner.finalize`` and ``_materialise``).
Read from the program's ``tune.pull`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "tune.pull")
