"""Share of the traced window in which no operation ran on the device: the
serve control plane's host work (canary, live, gate, checkpoint, history)."""
import trace_reduce


def read(ctx):
    return trace_reduce.idle_pct(ctx.view)
