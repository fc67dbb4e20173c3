#!/usr/bin/env python3
"""Smoke run of the tuner's main path on a TPU, in one process.

    python chip_smoke.py             # one chip: phases 1-5
    python chip_smoke.py --chips 4   # the §11 cluster-sharded loop only

The chip belongs to the process that first touches JAX, so every phase is
imported and called here; nothing is launched as a child process.

1. device check — exits non-zero, naming the platform found, unless JAX
   sees a TPU (the run never carries on on the CPU);
2. tune launcher — ``repro.launch.tune.main`` at N=1024 on the paper's §4.4
   switching workload (λ1 = 10k ev/s at 0.5 MB ↔ λ2 = 100k ev/s at 5 MB),
   jax backend, fused loop on, SLO reward; then one K=4 epoch mega-scan
   (§15, donated carries) on the configurator it returns;
3. pallas backend on Mosaic — two fused updates at N=1024 and one observe
   at the top of the window-length ladder (T=1024), with the interpret tier
   forbidden (``REPRO_REQUIRE_COMPILED``);
4. numpy oracle — window mean/p99 of the jax and pallas backends against
   the numpy ``FleetEnv`` at N=256 on matched seeds, at the tolerances of
   ``tests/chaos_harness.py``;
5. serve plane — ``repro.launch.serve.main`` for 3 cycles, then
   ``--resume`` for 5 more; the cycle counter must carry on.

``--chips 4`` runs only the fused loop at N=8192 on an explicit 4-device
``fleet_mesh`` and the same fleet on one device, and checks mesh size,
carry placement and statistical agreement.

Lines before the last are smoke readings (wall and compile seconds,
dispatch counts, p99s), not benchmark metrics. The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
WINDOW_S = 240.0
#: the serve plane's preset metric/lever selection, plus the tick-length
#: lever (it puts the fused loop on its TICK_BUDGET window shape)
SMOKE_METRICS = ["latency_p99_ms", "latency_mean_ms", "queue_depth",
                 "device_util", "sched_queue_depth"]
SMOKE_LEVERS = ["max_batch_events", "prefetch_depth", "driver_memory_gb",
                "sink_partitions", "backup_tasks", "batch_interval_s"]


class SmokeFailure(AssertionError):
    pass


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def finite(xs) -> bool:
    return all(math.isfinite(float(x)) for x in xs)


def device_check(chips: int):
    """Phase 1: a TPU with enough devices, before any other work."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if chips == 1 and len(devs) != 1:
        sys.exit(f"chip_smoke: the default phases use one device, but JAX "
                 f"sees {len(devs)}; run --chips 4 on a four-chip host")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
                 f"sees {len(devs)}")
    print(f"[smoke] device: {devs[0].platform} {devs[0].device_kind}, "
          f"{len(devs)} visible")
    return devs


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


@contextlib.contextmanager
def captured():
    """Tee stdout into a buffer, for checks on what a launcher printed."""
    buf = io.StringIO()
    real = sys.stdout

    class Tee(io.TextIOBase):
        def write(self, s):
            real.write(s)
            buf.write(s)
            return len(s)

        def flush(self):
            real.flush()

    with contextlib.redirect_stdout(Tee()):
        yield buf


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_tune(fleet=1024, collect=2048, updates=3, epoch_k=4):
    """Phase 2: the tune launcher in process, then one epoch mega-scan."""
    from repro.launch import tune

    out = OUT / "tune"
    argv = ["--env", "sim", "--fleet", str(fleet), "--workload", "switching",
            "--backend", "jax", "--device-loop", "on", "--reward", "slo",
            "--collect", str(collect), "--updates", str(updates),
            "--window", str(WINDOW_S), "--out", str(out)]
    with captured() as buf:
        cfgr = tune.main(argv)
    text = buf.getvalue()
    require("fused device loop (§10): ACTIVE" in text,
            "tune: the fused loop did not report ACTIVE")
    returns = [float(x) for x in re.findall(r"return (\S+)$", text, re.M)]
    require(len(returns) == updates and finite(returns),
            f"tune: update returns {returns}")
    json.loads((out / "analysis.json").read_text())
    hist = json.loads((out / "history.json").read_text())
    p99 = [r["p99_ms"] for r in hist["history"]]
    require(len(p99) > 0 and finite(p99 + [hist["default_p99_ms"],
                                          hist["best_p99_ms"]]),
            "tune: non-finite p99 in history.json")
    runner = cfgr._device_runner()
    require(len(runner._table.specs) == 109,
            f"tune: lever table holds {len(runner._table.specs)} levers")
    per_update = len(p99) // updates
    print(f"[smoke] tune p99: default {hist['default_p99_ms']!r} ms, last "
          f"update mean {sum(p99[-per_update:]) / per_update!r} ms, best "
          f"{hist['best_p99_ms']!r} ms")

    n0 = len(cfgr.history)
    stats = cfgr.run_epoch(epoch_k)
    recs = cfgr.history[n0:]
    require(len(stats) == epoch_k
            and finite([s["mean_return"] for s in stats]),
            f"megascan: stats {stats}")
    require(len(recs) == epoch_k * per_update
            and finite([r.p99_ms for r in recs] + [r.reward for r in recs]),
            "megascan: missing or non-finite records")
    print(f"[smoke] megascan K={epoch_k}: returns "
          f"{[s['mean_return'] for s in stats]!r}, last update mean p99 "
          f"{sum(r.p99_ms for r in recs[-per_update:]) / per_update!r} ms")


def _switching_fleet(n):
    """The paper's §4.4 λ1 ↔ λ2 switching workload, one per cluster."""
    from repro.data.workloads import SwitchingWorkload

    return [SwitchingWorkload() for _ in range(n)]


def phase_pallas(fleet=1024, updates=2, top_T=1024):
    """Phase 3: the fused loop and the observe path on the Mosaic tier."""
    from repro.core.configurator import Configurator
    from repro.engine import FleetEnv
    from repro.kernels.fleet_tick import DISPATCH_COUNTS, pallas_mode

    for var in ("REPRO_PALLAS_INTERPRET", "REPRO_FLEET_IMPL"):
        require(var not in os.environ, f"pallas: {var} is set")
    require(pallas_mode() == "mosaic", f"pallas: tier is {pallas_mode()}")
    os.environ["REPRO_REQUIRE_COMPILED"] = "1"   # interpret traces raise
    try:
        env = FleetEnv(_switching_fleet(fleet), seed=1, backend="pallas")
        cfgr = Configurator(env, SMOKE_METRICS, SMOKE_LEVERS,
                            steps_per_episode=5, episodes_per_update=4,
                            window_s=WINDOW_S, device_loop="on",
                            reward_mode="slo", seed=1)
        for i in range(updates):
            stats = cfgr.run_update()
            require(math.isfinite(stats["mean_return"]),
                    f"pallas: update {i} return {stats['mean_return']}")
        require(finite([r.p99_ms for r in cfgr.history]
                       + [r.reward for r in cfgr.history]),
                "pallas: non-finite records")
        T_loop = cfgr._device_runner()._hw_T
        # the observe path's longest window: every cluster at the lowest
        # tick length (0.25 s) over a 240 s window -> 960 ticks
        cfgs = env.current_configs()
        for c in cfgs:
            c["batch_interval_s"] = 0.25
        env.apply_configs(cfgs)
        s = env.observe_stats(WINDOW_S)
        T_obs = env._dev._hw[("T", True)]
        require(T_obs == top_T, f"pallas: observe ran T={T_obs}")
        require(finite(list(s["mean_ms"]) + list(s["p99_ms"])),
                "pallas: non-finite observe stats")
    finally:
        del os.environ["REPRO_REQUIRE_COMPILED"]
    print(f"[smoke] pallas: fused loop T={T_loop}, observe T={T_obs}, "
          f"dispatch counts {DISPATCH_COUNTS}")
    require(DISPATCH_COUNTS["mosaic"] > 0 and DISPATCH_COUNTS["interpret"] == 0,
            f"pallas: dispatch counts {DISPATCH_COUNTS}")


def phase_oracle(fleet=256):
    """Phase 4: window stats of both device backends vs the numpy oracle."""
    from chaos_harness import (assert_window_stats_equivalent,
                               collect_window_stats)
    from repro.data.workloads import SwitchingWorkload
    from repro.engine import FleetEnv

    def fleet_env(backend):
        # de-phased 300-573 s periods: the windows see both regimes
        wls = [SwitchingWorkload(period_s=300.0 + 7.0 * (i % 40))
               for i in range(fleet)]
        return FleetEnv(wls, seeds=list(range(fleet)), backend=backend)

    ref = collect_window_stats(fleet_env("numpy"))
    print(f"[smoke] oracle numpy: {ref!r}")
    for backend in ("jax", "pallas"):
        got = collect_window_stats(fleet_env(backend))
        print(f"[smoke] oracle {backend}: {got!r}")
        assert_window_stats_equivalent(got, ref)


def phase_serve(fleet=256, cycles=3, more=5):
    """Phase 5: serve plane, then a resume that carries the cycle count."""
    from repro.launch import serve

    out = OUT / "serve"
    argv = ["--quick", "--fleet", str(fleet), "--backend", "jax",
            "--device-loop", "on", "--window", str(WINDOW_S),
            "--out", str(out)]
    cycle = re.compile(r"^\[cycle\s+(\d+)\].*p99 (\S+) ms", re.M)
    with captured() as buf:
        serve.main(argv + ["--cycles", str(cycles)])
    first = buf.getvalue()
    with captured() as buf:
        serve.main(argv + ["--cycles", str(more), "--resume"])
    second = buf.getvalue()
    for name, text in (("run", first), ("resume", second)):
        require("fused device loop (§10): ACTIVE" in text,
                f"serve {name}: the fused loop did not report ACTIVE")
    c1 = [int(c) for c, _ in cycle.findall(first)]
    c2 = [int(c) for c, _ in cycle.findall(second)]
    require(c1 == list(range(1, cycles + 1)), f"serve: cycles {c1}")
    require("[resume] restored checkpoint" in second,
            "serve: resume restored no checkpoint")
    require(c2 == list(range(cycles + 1, cycles + more + 1)),
            f"serve: resumed cycles {c2}")
    p99 = [float(p) for _, p in cycle.findall(first + second)]
    require(finite(p99), f"serve: live p99 {p99}")


def phase_sharded(fleet=8192, updates=3, chips=4, steps=5):
    """--chips 4: the fused loop on an explicit 4-device mesh vs the same
    fleet on one device, in this process."""
    import numpy as np
    from chaos_harness import assert_loop_equivalent

    from repro.core.configurator import Configurator
    from repro.data.workloads import PoissonWorkload
    from repro.distribution.sharding import fleet_mesh
    from repro.engine import FleetEnv

    mesh = fleet_mesh(chips)
    require(mesh is not None and mesh.size == chips,
            f"sharded: fleet_mesh({chips}) = {mesh}")
    frozen = dict(split_after=10**9, extend_after=10**9, merge_after=10**9)
    streams = {}
    for name, m in (("1dev", "off"), (f"{chips}dev", mesh)):
        env = FleetEnv([PoissonWorkload(10_000, 0.5) for _ in range(fleet)],
                       seeds=list(range(fleet)), backend="jax")
        # the serve presets alone: the default tick length (T=48 windows)
        cfgr = Configurator(env, SMOKE_METRICS, SMOKE_LEVERS[:5],
                            steps_per_episode=steps, window_s=WINDOW_S,
                            device_loop="on", bin_kw=frozen, mesh=m)
        t0 = time.perf_counter()
        for _ in range(updates):
            cfgr.run_update()
        wall = time.perf_counter() - t0
        runner = cfgr._device_runner()
        if m != "off":
            require(runner.mesh is not None and runner.mesh.size == chips,
                    f"sharded: runner mesh {runner.mesh}")
            carries = {"config_idx": runner._config_idx,
                       "per_node": runner._per_node,
                       "backlog": env._dev._backlog,
                       "sfree": env._dev._sfree_rel}
            for k, v in carries.items():
                n_dev = len(v.sharding.device_set)
                print(f"[smoke] sharded carry {k}: {tuple(v.shape)} on "
                      f"{n_dev} devices")
                require(n_dev == chips, f"sharded: {k} on {n_dev} devices")
        streams[name] = (np.array([r.reward for r in cfgr.history]),
                         np.array([r.p99_ms for r in cfgr.history]))
        print(f"[smoke] sharded {name}: {updates} updates in {wall!r} s, "
              f"median reward {float(np.median(streams[name][0]))!r}, "
              f"median p99 {float(np.median(streams[name][1]))!r} ms")
    (r1, p1), (r4, p4) = streams["1dev"], streams[f"{chips}dev"]
    assert_loop_equivalent(r1, p1, r4, p4, steps=steps)


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run only the cluster-sharded fused loop on a "
                         "four-chip host")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)   # readable after a kill
    devs = device_check(args.chips)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT / "tests"))      # chaos_harness tolerances
    from repro.utils import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}")
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    clock = CompileClock()
    if args.chips == 1:
        phases = [("tune", phase_tune), ("pallas", phase_pallas),
                  ("oracle", phase_oracle), ("serve", phase_serve)]
    else:
        phases = [("sharded", lambda: phase_sharded(chips=args.chips))]
    failed = []
    t_all = time.perf_counter()
    for name, fn in phases:
        t0, (c0, n0, h0) = time.perf_counter(), clock.snapshot()
        try:
            fn()
            status = "ok"
        except Exception:   # report, run the remaining phases, fail at the end
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        c1, n1, h1 = clock.snapshot()
        print(f"[smoke] phase {name}: {status} — wall "
              f"{time.perf_counter() - t0!r} s, compile {c1 - c0!r} s over "
              f"{n1 - n0} programs, {h1 - h0} persistent-cache hits",
              flush=True)
    secs, programs, hits = clock.snapshot()
    print(f"[smoke] total: wall {time.perf_counter() - t_all!r} s, compile "
          f"{secs!r} s over {programs} programs, {hits} persistent-cache hits")
    if failed:
        print(f"[smoke] FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
