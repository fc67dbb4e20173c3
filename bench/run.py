#!/usr/bin/env python3
"""On-chip benchmark of the RL stream-processing tuner, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is one process and holds the chip for its whole life:

1. looks up the cell in ``BENCHMARK.json`` and its files by name
   (``configs/<config>.json``, ``traffic/<traffic>.json``,
   ``limits/<cell>.json``, ``metrics/<metric>.py``, and where the cell's
   workload law, reference or traffic kind is not built in,
   ``laws/<law>.py``, ``references/<config>.py``, ``drivers/<kind>.py``:
   ``lookup.py``);
2. exits non-zero, printing no result, unless JAX sees a TPU with as many
   chips as the cell asks for;
3. builds the system from the seed, drives its first updates (or serve
   cycles) through the window's own call for the correctness check, and
   warms up the rest of the cell's shapes — all of it counted as set-up;
4. runs the closed loop for ``--seconds`` (``--trace 1`` profiles the
   first ``trace_seconds`` of it and reports the per-layer metrics);
5. compares the checked updates with the plain reference (``compare.py``)
   and prints each number beside its limit on standard error, then one JSON
   line on standard output: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` when traced), ``checks`` last.

JAX's persistent compilation cache lives at ``.jax_cache/`` in the checkout
and keeps every program, so only a cell's first run in a checkout compiles.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
sys.path.insert(0, str(BENCH))

import lookup  # noqa: E402


# --------------------------------------------------------------------------
# the cell, by name
# --------------------------------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """Resolve a cell of ``BENCHMARK.json`` to its files and metrics."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no cell {name!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    deploy = json.loads((root / cfg["file"]).read_text())
    bench = root / spec["paths"][0]
    traffic = json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((bench / "limits" / f"{name}.json").read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return SimpleNamespace(name=name, cell=cell, deploy=deploy,
                           traffic=traffic, limits=limits["limits"],
                           not_compared=limits.get("not_compared", {}),
                           e2e=e2e, per_layer=per_layer, bench=bench)


def metric_reader(bench: Path, name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    return lookup.load(bench, "metrics", name).read


# --------------------------------------------------------------------------
# device and compile bookkeeping
# --------------------------------------------------------------------------

def device_check(chips: int):
    """The devices the cell runs on; exits unless JAX sees a TPU with at
    least ``chips`` of them (never falls back to the CPU)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit records its retrieval time instead)."""

    def __init__(self):
        import jax

        self.secs, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.secs, self.programs, self.hits


def percentile(xs, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(xs, float), q))


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def annotate(targets) -> None:
    """Wrap each (object, method) in a profiler span of the given name, on
    that object only (the benchmark's spans around its calls)."""
    import functools

    import jax

    for obj, name, label in targets:
        fn = getattr(obj, name)

        @functools.wraps(fn)
        def wrapped(*a, _fn=fn, _label=label, **kw):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **kw)

        setattr(obj, name, wrapped)


def end_to_end(cell, driver, t0: float, marks: list) -> dict:
    """The cell's host-clock metrics over the whole window."""
    span = marks[-1] - t0
    gaps = [b - a for a, b in zip([t0] + marks[:-1], marks)]
    vals = {
        "train_windows_per_s": len(marks) * driver.windows_per_unit / span,
        "train_update_p95_ms": 1e3 * percentile(gaps, 95.0),
        "serve_cycles_per_s": len(marks) / span,
        "serve_cycle_p95_ms": 1e3 * percentile(gaps, 95.0),
    }
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell.e2e if m["name"] in vals}


def run(args) -> dict:
    """One benchmark run on the TPU; returns the result line as a dict."""
    t_start = time.perf_counter()
    cell = load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    devs = device_check(int(cell.cell["chips"]))
    enable_cache()
    return measure(cell, args, devs, t_start)


def measure(cell, args, devs, t_start: float) -> dict:
    """Build the cell's driver from ``cell.traffic``, set it up, run the
    window on ``devs`` and judge the checked updates; set-up counts from
    ``t_start``."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    clock = CompileClock()
    import loops
    import trace_reduce

    traffic = cell.traffic
    with tempfile.TemporaryDirectory(prefix="bench-") as work:
        driver = loops.driver_for(traffic["kind"], cell.bench)(
            cell.deploy, traffic, int(args.seed), Path(work), cell.bench)
        driver.setup()
        c0 = clock.snapshot()
        setup_s = time.perf_counter() - t_start

        marks: list = []
        trace_dir = Path(work) / "trace"
        trace_s = float(traffic.get("trace_seconds", args.seconds))
        traced = {"units": 0, "on": False, "ann": None}

        def stop_trace():
            if traced["on"]:
                traced["ann"].__exit__(None, None, None)
                jax.profiler.stop_trace()
                traced["on"] = False

        def on_unit():
            now = time.perf_counter()
            marks.append(now)
            if traced["on"]:
                traced["units"] += 1
                if now - t0 >= trace_s:
                    stop_trace()
            if now - t0 >= args.seconds:
                raise loops.WindowClosed

        if args.trace:
            annotate(driver.span_targets())
            # the Python tracer stays off: it slows the host several-fold
            # and would inflate the idle share it is meant to explain
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            traced["ann"] = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            traced["ann"].__enter__()
            traced["on"] = True
        t0 = time.perf_counter()
        driver.run(on_unit)
        stop_trace()
        c1 = clock.snapshot()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)

        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}
        out = {"correct": False, "attempted": len(marks),
               "failed": int(driver.failed), "metrics": {}, "device": device}
        if c1[1] > c0[1]:
            print(f"bench: {c1[1] - c0[1]} programs compiled inside the "
                  f"window ({c1[0] - c0[0]:.3f} s)", file=sys.stderr)
        if args.trace:
            out["metrics"], bd, busy = per_layer(cell, driver, trace_dir,
                                                 traced["units"],
                                                 devs[0].device_kind)
            if busy is not None:
                device["busy_s"], device["window_s"] = busy
            out["breakdown"] = bd
        else:
            out["metrics"] = end_to_end(cell, driver, t0, marks)
            out["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
        print(f"bench: set-up {setup_s:.3f} s, compile {c0[0]:.3f} s over "
              f"{c0[1]} programs, {c0[2]} persistent-cache hits; window "
              f"{len(marks)} {driver.unit}s", file=sys.stderr)

        import compare

        numbers = driver.numbers()
        ok, checks = compare.judge(numbers, cell.limits, cell.not_compared)
        for k in cell.not_compared:
            print(f"not compared {k}: {numbers.get(k)!r}", file=sys.stderr)
        if hasattr(driver, "promotions"):
            print(f"bench: {driver.promotions} promotions checked",
                  file=sys.stderr)
        out["correct"] = ok and driver.failed == 0
        driver.close()
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    out["checks"] = {k: {"value": _finite(v["value"]),
                         "limit": _finite(v["limit"])}
                     for k, v in checks.items()}
    return out


def per_layer(cell, driver, trace_dir: Path, units: int, kind: str):
    """(per-layer metrics, breakdown, (busy_s, window_s)) from the trace."""
    import trace_reduce as trace

    path = trace.find_xplane(trace_dir)
    if path is None:
        return {}, {"device_ops": [], "idle_gaps": []}, None
    view = trace.load_xplane(path)
    peaks = json.loads((cell.bench / "peaks.json").read_text())
    ctx = SimpleNamespace(view=view, units=units, traffic=cell.traffic,
                          device_kind=kind, peaks=peaks, bench=cell.bench)
    metrics = {}
    for m in cell.per_layer:
        v = metric_reader(cell.bench, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    bd = {"device_ops": trace.top_ops(view), "idle_gaps": trace.idle_gaps(view)}
    return metrics, bd, trace.busy_seconds(view)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    out = run(args)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
