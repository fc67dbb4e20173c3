"""Share of the traced window in which a collective (the per-step running-
range ``pmin``/``pmax`` of ``distribution/sharding.py``) runs on a chip with
no other operation beside it, averaged over the chips."""
import trace_reduce


def read(ctx):
    return trace_reduce.collective_exposed_pct(ctx.view)
