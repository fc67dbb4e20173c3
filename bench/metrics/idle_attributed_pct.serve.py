"""Share of the device's idle time in the traced window that the program's
own host spans put down to a named phase of the serve control plane (the
unit spans ``tune.update`` and ``serve.cycle`` and the benchmark's own
spans left out)."""
import span_reduce


def read(ctx):
    return span_reduce.idle_attributed_pct(ctx)
