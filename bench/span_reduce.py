"""The program's own host spans in a profiler trace, for the per-layer
metrics that read them.

The program names its host phases with ``repro.monitoring.spans``: spans
``tune.*`` (the tuning loop), ``serve.*`` (the serve control plane) and
``python.gc`` (the interpreter's collections), each a TraceMe on the
profiler's clock. A trace of a program that has no such spans (an older
commit) gives ``None`` for every reading here.
"""
from __future__ import annotations

import trace_reduce as tr

#: the outer span of one unit of work: it covers the whole unit, so it
#: names no phase
UNIT_SPANS = frozenset({"tune.update", "serve.cycle"})


def base(name: str) -> str:
    """An event's name without TraceMe metadata (``name#k=v#``)."""
    return name.split("#", 1)[0]


def is_program_span(name: str) -> bool:
    n = base(name)
    return n.startswith(("tune.", "serve.")) or n == "python.gc"


def _program_events(view):
    return [e for e in view.host if is_program_span(e.name)]


def span_ms_per_unit(ctx, name: str) -> float | None:
    """Milliseconds per unit (update or cycle) of the union of the span's
    intervals within the traced window; 0 where the program has spans but
    this one did not occur, ``None`` where the window or the program's
    spans are missing."""
    win = ctx.view.window()
    evs = _program_events(ctx.view)
    if win is None or not evs or ctx.units <= 0:
        return None
    mine = [e for e in evs if base(e.name) == name]
    return tr.total(tr.union(tr.clip(mine, *win))) / 1e6 / ctx.units


def idle_attributed_pct(ctx) -> float | None:
    """Share of the first device's idle time in the window that lies inside
    some program span other than the unit spans."""
    view = ctx.view
    win = view.window()
    devs = sorted(set(view.ops) | set(view.modules))
    evs = [e for e in _program_events(view) if base(e.name) not in UNIT_SPANS]
    if win is None or not devs or not evs:
        return None
    lo, hi = win
    idle = tr.subtract([(lo, hi)], tr.busy(view, devs[0], lo, hi))
    idle_ns = tr.total(idle)
    if idle_ns <= 0:
        return None
    covered = tr.union(tr.clip(evs, lo, hi))
    return 100.0 * (1.0 - tr.total(tr.subtract(idle, covered)) / idle_ns)
