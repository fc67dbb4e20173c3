"""Share of the traced window in which no operation ran on the device,
averaged over the chips: the host between programs (``Configurator.run_update``
-> ``DeviceEpisodeRunner.finalize`` / ``_materialise`` / ``_fresh_inputs``)."""
import trace_reduce


def read(ctx):
    return trace_reduce.idle_pct(ctx.view)
