"""The trace reduction behind the per-layer metrics, on a synthetic trace.

Describes no TPU topology: a ``TraceView`` is built by hand, in nanoseconds.
"""
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


def view() -> TraceView:
    """Window 0-100 ms. Device 0: episode module 10-40 ms (ops 10-25 and
    20-40, overlapping), update module 60-70 ms (op 60-70), a collective
    75-80 ms with an op beside it 78-79 ms. Device 1: one op 0-50 ms."""
    v = TraceView()
    v.modules[0] = [Event("jit_program(12)", 10 * MS, 30 * MS),
                    Event("jit__unknown(3)", 60 * MS, 10 * MS),
                    Event("jit_program(12)", 120 * MS, 5 * MS)]   # outside
    v.ops[0] = [Event("fusion.1", 10 * MS, 15 * MS),
                Event("%fleet_tick_window.3 = (f32[2,8]) custom-call(f32[2,8] %a)",
                      20 * MS, 20 * MS),
                Event("%fusion.2 = f32[8] fusion(f32[8] %p)", 60 * MS,
                      10 * MS),
                Event("all-reduce.7", 75 * MS, 5 * MS),
                Event("fusion.3", 78 * MS, 1 * MS)]
    v.modules[1] = [Event("jit_program(12)", 0, 50 * MS)]
    v.ops[1] = [Event("fusion.1", 0, 50 * MS)]
    v.host = [Event(tr.WINDOW_SPAN, 0, 100 * MS),
              Event("run_update", 40 * MS, 20 * MS),
              Event("_materialise", 42 * MS, 15 * MS),
              Event("callback", 85 * MS, 10 * MS)]
    return v


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.total([(0, 3), (5, 8)]) == 6


def test_busy_idle_and_window():
    v = view()
    # device 0 busy: 10-40, 60-70, 75-80 = 45 ms; device 1: 50 ms
    busy_s, window_s = tr.busy_seconds(v)
    assert window_s == pytest.approx(0.1)
    assert busy_s == pytest.approx((0.045 + 0.050) / 2)
    assert tr.idle_pct(v) == pytest.approx(100 * (1 - 0.0475 / 0.1))


def test_module_and_kernel_time():
    v = view()
    # the episode module: 30 ms on device 0 (the one outside the window
    # is left out), 50 ms on device 1 -> averaged
    assert tr.module_seconds(v, "jit_program") == pytest.approx(0.040)
    assert tr.module_seconds(v, "jit__unknown") == pytest.approx(0.005)
    assert tr.module_count(v) == 2


def test_collective_exposure():
    v = view()
    # the all-reduce runs 75-80 ms, an op beside it 78-79: 4 ms exposed on
    # device 0, none on device 1 (no collective) -> mean 2 % of 100 ms
    assert tr.collective_exposed_pct(v) == pytest.approx(2.0)
    v.ops[0] = [e for e in v.ops[0] if "all-reduce" not in e.name]
    assert tr.collective_exposed_pct(v) is None


def test_gap_attribution_and_top_ops():
    v = view()
    gaps = tr.idle_gaps(v)
    # device 0's gaps: 0-10, 40-60, 70-75, 80-100 ms; the longest two are
    # named after the innermost host event at their midpoints
    assert gaps[0] == ["_materialise", pytest.approx(0.020)]
    assert gaps[1] == ["callback", pytest.approx(0.020)]
    assert [g[0] for g in gaps[2:]] == [tr.WINDOW_SPAN, tr.WINDOW_SPAN]
    v.ops[0].append(Event("%while.3 = (s32[]) while(s32[] %x)", 10 * MS,
                          30 * MS))
    top = tr.top_ops(v)
    # the while loop holds other ops and is left out; names are shortened
    # to the HLO instruction name
    assert top[0] == ["%fleet_tick_window.3", pytest.approx(0.020)]
    assert ["%fusion.2", pytest.approx(0.010)] in top
    assert len(top) == 5


def test_metric_readers_on_the_synthetic_trace():
    import json

    peaks = json.loads((BENCH / "peaks.json").read_text())
    traffic = {"fleet": 1024,
               "window_kernel": {"name": "fleet_tick", "T": 192, "S": 8,
                                 "K": 24}}
    ctx = SimpleNamespace(view=view(), units=2, traffic=traffic,
                          device_kind="TPU v5 lite", peaks=peaks, bench=BENCH)
    read = lambda name: run.metric_reader(BENCH, name)(ctx)
    assert read("device_idle_pct.train") == pytest.approx(52.5)
    assert read("episode_device_ms.train") == pytest.approx(20.0)
    assert read("update_device_ms.train") == pytest.approx(2.5)
    assert read("dispatches_per_cycle.serve") == pytest.approx(1.0)
    assert read("collective_exposed_pct.train") == pytest.approx(2.0)
    roof = read("fleet_tick_roofline_pct")
    assert 0 < roof < 100
    # no kernel in the trace: the reader finds nothing and says so
    ctx.view.ops[0] = [e for e in ctx.view.ops[0]
                       if "fleet_tick" not in e.name]
    assert read("fleet_tick_roofline_pct") is None
