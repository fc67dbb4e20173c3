"""The per-layer metrics that read the program's own host spans, on a
synthetic trace (nanoseconds, built by hand like ``test_bench_trace.py``)."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Event, TraceView  # noqa: E402

MS = 1e6   # ns


def _ev(name, a, b):
    return Event(name, a * MS, (b - a) * MS)


def train_view() -> TraceView:
    """Window 0-100 ms, two updates (0-50, 50-100). Device 0 busy 10-20
    and 60-62 ms, so idle 88 ms. Program spans cover the idle time except
    45-50 (inside ``tune.update`` only), 85-90 and 95-98 ms; a replay span
    runs past the window's end and a GC lies in a gap between phases."""
    v = TraceView()
    v.ops[0] = [_ev("fusion.1", 10, 20), _ev("fusion.2", 60, 62)]
    v.host = [_ev(tr.WINDOW_SPAN, 0, 100),
              _ev("run_update", 0, 50), _ev("run_update", 50, 100),
              _ev("_materialise", 20, 45),
              _ev("tune.update", 0, 50), _ev("tune.update#step_num=8#", 50, 100),
              _ev("tune.pack", 0, 8), _ev("tune.pack", 50, 58),
              _ev("tune.dispatch.episode", 8, 10),
              _ev("tune.dispatch.episode#traced=0#", 58, 60),
              _ev("tune.wait", 10, 20), _ev("tune.wait", 60, 62),
              _ev("tune.pull#bytes=800#", 20, 22), _ev("tune.pull", 62, 63),
              _ev("tune.records", 22, 40), _ev("tune.records", 63, 80),
              _ev("python.gc#generation=0#", 30, 35),
              _ev("python.gc", 90, 95),
              _ev("tune.replay", 40, 45), _ev("tune.replay", 80, 85),
              _ev("tune.replay", 98, 104)]
    return v


def ctx_of(view, units=2):
    return SimpleNamespace(view=view, units=units, traffic={},
                           device_kind="TPU v5 lite", peaks={}, bench=BENCH)


def read(name, ctx):
    return run.metric_reader(BENCH, name)(ctx)


@pytest.mark.parametrize("name, value", [
    ("host_pack_ms.train", (8 + 8) / 2),
    ("host_pull_ms.train", (2 + 1) / 2),
    ("host_records_ms.train", (18 + 17) / 2),
    ("host_replay_ms.train", (5 + 5 + 2) / 2),     # clipped at the window
    ("host_wait_ms.train", (10 + 2) / 2),
    ("gc_ms.train", (5 + 5) / 2),
    # idle 88 ms; uncovered 45-50, 85-90 and 95-98: the unit span and the
    # benchmark's spans over them do not count
    ("idle_attributed_pct.train", 100 * (1 - 13 / 88)),
])
def test_train_span_readers(name, value):
    assert read(name, ctx_of(train_view())) == pytest.approx(value)


def test_overlapping_spans_of_one_name_count_once():
    v = train_view()
    v.host.append(_ev("tune.records", 30, 45))    # overlaps 22-40
    assert read("host_records_ms.train", ctx_of(v)) == pytest.approx(
        (23 + 17) / 2)


def serve_view() -> TraceView:
    """Window 0-100 ms, two cycles. Device busy 5-15 and 55-65 ms. A
    ``tune.update`` nests in each shadow pass; the canary runs only in the
    first cycle and nothing is collected."""
    v = TraceView()
    v.ops[0] = [_ev("fusion.1", 5, 15), _ev("fusion.1", 55, 65)]
    v.host = [_ev(tr.WINDOW_SPAN, 0, 100),
              _ev("run_cycle", 0, 50), _ev("run_cycle", 50, 100),
              _ev("serve.cycle", 0, 50), _ev("serve.cycle", 50, 100),
              _ev("serve.shadow", 0, 30), _ev("serve.shadow", 50, 80),
              _ev("tune.update", 1, 29), _ev("tune.update", 51, 79),
              _ev("serve.canary", 30, 40),
              _ev("serve.gate", 40, 41),
              _ev("serve.live", 42, 48), _ev("serve.live", 80, 96)]
    return v


@pytest.mark.parametrize("name, value", [
    ("shadow_ms.serve", (30 + 30) / 2),
    ("canary_ms.serve", 10 / 2),
    ("live_ms.serve", (6 + 16) / 2),
    ("gc_ms.serve", 0.0),         # the program has spans, none of them GC
    # idle 80 ms; uncovered 41-42, 48-50, 96-100
    ("idle_attributed_pct.serve", 100 * (1 - 7 / 80)),
])
def test_serve_span_readers(name, value):
    assert read(name, ctx_of(serve_view())) == pytest.approx(value)


NEW = ["host_pack_ms.train", "host_pull_ms.train", "host_records_ms.train",
       "host_replay_ms.train", "host_wait_ms.train", "gc_ms.train",
       "idle_attributed_pct.train", "shadow_ms.serve", "canary_ms.serve",
       "live_ms.serve", "gc_ms.serve", "idle_attributed_pct.serve"]


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_without_program_spans(name):
    """A program with no spans of its own (an older commit), a trace with
    no window, and an untraced run give no reading."""
    v = train_view()
    v.host = [e for e in v.host if not e.name.startswith(
        ("tune.", "serve.", "python."))]
    assert read(name, ctx_of(v)) is None
    v = train_view()
    v.host = [e for e in v.host if e.name != tr.WINDOW_SPAN]
    assert read(name, ctx_of(v)) is None
    assert read(name, ctx_of(TraceView(), units=0)) is None


def test_idle_attribution_needs_a_device_line():
    v = train_view()
    v.ops.clear()
    assert read("idle_attributed_pct.train", ctx_of(v)) is None
