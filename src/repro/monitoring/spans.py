"""Named host spans and counters, on the profiler's clock.

``span(name, **meta)`` marks one phase of host work. It is two things at
once:

* a ``jax.profiler.TraceAnnotation`` — a host TraceMe on the clock the
  device planes of a profile use, so a trace can put every idle gap of the
  device down to the phase the host was in (``bench/trace_reduce.py``);
* an in-memory total per name (count, seconds, longest), taken with
  ``time.perf_counter``, which ``prometheus_text`` renders into the
  launchers' ``metrics.prom`` — per-phase seconds without a profiler.

``step(name, number)`` is the same for the outer span of one unit of work (a
tuning update, a serve cycle), as a ``StepTraceAnnotation`` that carries
the unit's number. ``count(name, n)`` adds to a named counter.

Spans are always on: with no profiler running a span costs one to two
microseconds. A ``gc.callbacks`` hook (installed once per process, on
import) wraps every collection of the interpreter in a ``python.gc`` span
and counts collections per generation (``python.gc.gen<g>``).
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.monitoring.metrics import _prometheus_text


@dataclass
class SpanTotal:
    count: int = 0
    seconds: float = 0.0
    max_s: float = 0.0


class SpanTotals:
    """Per-name span totals and counters of one process."""

    def __init__(self):
        self.spans: dict[str, SpanTotal] = {}
        self.counters: dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        t = self.spans.get(name)
        if t is None:
            t = self.spans[name] = SpanTotal()
        t.count += 1
        t.seconds += seconds
        if seconds > t.max_s:
            t.max_s = seconds

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def reset(self) -> None:
        self.spans.clear()
        self.counters.clear()

    def prometheus_text(self, prefix: str = "repro") -> str:
        """Each span as ``<prefix>_span_<name>_seconds_total`` and
        ``..._count_total`` counters plus a ``..._max_seconds`` gauge, each
        counter as ``<prefix>_count_<name>_total`` (dots become ``_``)."""
        vals: dict = {}
        for name, t in sorted(self.spans.items()):
            key = name.replace(".", "_")
            vals[f"{key}_seconds"] = t.seconds
            vals[f"{key}_count"] = t.count
            vals[f"{key}_max_seconds"] = t.max_s
        monotone = {k for k in vals if not k.endswith("_max_seconds")}
        text = _prometheus_text(f"{prefix}_span", vals, monotone)
        counts = {name.replace(".", "_"): n
                  for name, n in sorted(self.counters.items())}
        return text + _prometheus_text(f"{prefix}_count", counts, set(counts))


#: the process's totals (like ``device_loop.TRACE_COUNTS``, one per process:
#: the profiler it mirrors is process-wide too)
TOTALS = SpanTotals()


class span:
    """Context manager: one named host span (see the module docstring).
    ``set_metadata(**kw)`` attaches values known only inside the span."""

    __slots__ = ("name", "_ann", "_t0")
    _kind = TraceAnnotation

    def __init__(self, name: str, **meta):
        self.name = name
        self._ann = self._kind(name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        TOTALS.add(self.name, time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)

    def set_metadata(self, **meta) -> None:
        self._ann.set_metadata(**meta)


class step(span):
    """The outer span of one unit of work, carrying its number."""

    __slots__ = ()
    _kind = StepTraceAnnotation

    def __init__(self, name: str, number: int, **meta):
        super().__init__(name, step_num=int(number), **meta)


def count(name: str, n: int = 1) -> None:
    TOTALS.count(name, n)


def prometheus_text(prefix: str = "repro") -> str:
    return TOTALS.prometheus_text(prefix)


_gc_open: list = []


def _gc_callback(phase: str, info: dict) -> None:
    if phase == "start":
        g = int(info.get("generation", -1))
        s = span("python.gc", generation=g)
        s.__enter__()
        _gc_open.append(s)
        TOTALS.count(f"python.gc.gen{g}")
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


if _gc_callback not in gc.callbacks:
    gc.callbacks.append(_gc_callback)
