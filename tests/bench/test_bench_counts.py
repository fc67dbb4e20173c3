"""The fused window's byte count against the kernel's real operand shapes,
and the peaks table's refusal of an unknown device."""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from trace_reduce import Event, TraceView, WINDOW_SPAN  # noqa: E402


def counts():
    spec = importlib.util.spec_from_file_location(
        "counts_fleet_tick", BENCH / "counts" / "fleet_tick.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_cells():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    out = []
    for w in spec["workloads"]:
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        if t.get("window_kernel"):
            k = t["window_kernel"]
            out.append((w["name"], k["T"], k["S"], int(t["fleet"]), k["K"]))
    return out


@pytest.mark.parametrize("cell,T,S,N,K", kernel_cells())
def test_window_bytes_match_the_kernel_operands(cell, T, S, N, K):
    """Sum of the kernel's operand and result bytes, from the shapes
    ``fleet_tick_window`` really takes and returns, equals the count."""
    import jax
    import jax.numpy as jnp

    from repro.engine.fleet_jax import lane_budget
    from repro.kernels.fleet_tick import (CONSTS_ROWS, fleet_tick_window,
                                          head_budget)

    assert S == lane_budget(T)
    p99_k = min(T * S, int(np.ceil(0.01 * (T * S - 1))) + 2)
    assert K == head_budget(S, p99_k)
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    args = [f(2, N), f(CONSTS_ROWS, N)] + [f(T, N)] * 7 \
        + [f(T, S, N), f(T, S, N), f(T, N), f(T, N)]
    outs = jax.eval_shape(
        lambda *a: fleet_tick_window(
            *a[:9], a[9], a[10], a[11], a[12], noise=0.04, retention_s=300.0,
            straggler_prob=0.05, slo=1.5, shi=3.0, p99_k=p99_k, mode="xla"),
        *args)
    nbytes = lambda xs: sum(4 * int(np.prod(x.shape)) for x in xs)
    got = nbytes(args) + nbytes(jax.tree_util.tree_leaves(outs))
    assert counts().window_bytes(T, S, N, K) == got


def test_ops_and_bound_at_the_pallas_cell():
    c = counts()
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    secs, bound = c.roofline_seconds(192, 8, 1024, 24, peaks)
    assert bound == "bytes"
    assert secs == pytest.approx(c.window_bytes(192, 8, 1024, 24) / 819e9)
    assert c.window_ops(192, 8, 1024, 24) > 0


def test_peaks_refuse_an_unknown_device_kind():
    traffic = {"fleet": 1024, "window_kernel": {"T": 192, "S": 8, "K": 24}}
    v = TraceView(ops={0: [Event("%fleet_tick_window.7 = f32[2] custom-call()",
                                  0, 1e6),
                            Event("%fusion.1 = f32[2] fusion(f32[2] "
                                  "%jit_fleet_tick_window_.5)", 0, 1e6)]},
                  host=[Event(WINDOW_SPAN, 0, 1e7)])
    peaks = json.loads((BENCH / "peaks.json").read_text())
    read = run.metric_reader(BENCH, "fleet_tick_roofline_pct")
    ok = SimpleNamespace(view=v, units=1, traffic=traffic, peaks=peaks,
                         device_kind="TPU v5 lite", bench=BENCH)
    assert read(ok) > 0
    with pytest.raises(KeyError):
        read(SimpleNamespace(**dict(vars(ok), device_kind="TPU v9")))
