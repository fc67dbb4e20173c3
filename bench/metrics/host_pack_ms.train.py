"""Host milliseconds per update packing the episode program's inputs
(``DeviceEpisodeRunner._fresh_inputs``: table repack, config index, puts).
Read from the program's ``tune.pack`` spans in the traced window."""
import span_reduce


def read(ctx):
    return span_reduce.span_ms_per_unit(ctx, "tune.pack")
