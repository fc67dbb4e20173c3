"""Auto-tuning launcher: the paper's full pipeline against either env.

    PYTHONPATH=src python -m repro.launch.tune --env sim --collect 1200 \
        --updates 8 --f 0.8 --out experiments/tune

    # fleet-parallel offline phase + N-parallel REINFORCE episodes
    PYTHONPATH=src python -m repro.launch.tune --env sim --fleet 16 \
        --fleet-mix --collect 1200 --updates 8 --out experiments/tune_fleet

Prints the Fig-5-style latency trajectory and writes analysis + history JSON.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np


def main(argv=None):
    """Run the pipeline; returns the tuned ``Configurator`` (None when
    interrupted before the first record)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", choices=["sim", "local"], default="sim")
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--workload", default="poisson_low")
    ap.add_argument("--fleet", type=int, default=1,
                    help="simulate N clusters in one batched FleetEnv "
                         "(sim env only; paper's ~80-cluster sweep)")
    ap.add_argument("--fleet-mix", action="store_true",
                    help="heterogeneous fleet over the FLEET_MIX workload "
                         "roster instead of N copies of --workload")
    ap.add_argument("--backend", choices=["numpy", "jax", "pallas"],
                    default="numpy",
                    help="fleet tick engine (DESIGN.md §9): numpy reference "
                         "oracle, or the device-resident jax/pallas engine "
                         "(1000+-cluster fleets; statistical equivalence)")
    ap.add_argument("--device-loop", choices=["auto", "on", "off"],
                    default="auto",
                    help="fused Algorithm-1 training loop (DESIGN.md §10/§11):"
                         " one jitted episode program + one jitted update per "
                         "outer iteration, sharded across devices when more "
                         "than one is visible. 'auto' uses it whenever the "
                         "env supports it (jax/pallas backend, device-"
                         "packable workloads) and logs the fallback reason "
                         "once; 'on' fails loudly with that reason")
    ap.add_argument("--reward", choices=["neg_mean", "neg_p99", "neg_inv",
                                         "slo"],
                    default="neg_mean",
                    help="episode reward shaping (DESIGN.md §1/§12): 'slo' "
                         "adds a hinge penalty on p99 over --slo-ms plus a "
                         "breach-duration term")
    ap.add_argument("--slo-ms", type=float, default=1000.0,
                    help="latency SLO for --reward slo (ms)")
    ap.add_argument("--safe", action="store_true",
                    help="safe exploration (DESIGN.md §16): trust-region "
                         "shield over the lever lattice + breach-risk "
                         "fallback to last-known-good configs (needs "
                         "--reward slo)")
    ap.add_argument("--trust-radius", type=int, default=2,
                    help="--safe: initial ±bin trust radius around the "
                         "last-known-good config")
    ap.add_argument("--breach-budget", type=int, default=4,
                    help="--safe: per-episode SLO-breach budget per cluster; "
                         "exhaustion pins the cluster to last-known-good "
                         "for the rest of the episode")
    ap.add_argument("--collect", type=int, default=1200)
    ap.add_argument("--updates", type=int, default=8)
    ap.add_argument("--steps-per-episode", type=int, default=5)
    ap.add_argument("--episodes", type=int, default=4)
    ap.add_argument("--f", type=float, default=0.8)
    ap.add_argument("--window", type=float, default=240.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="experiments/tune")
    args = ap.parse_args(argv)

    from repro.utils import enable_compile_cache

    enable_compile_cache()
    from repro.core import AutoTuner
    from repro.data.workloads import fleet_workloads, get_workload
    from repro.engine import FleetEnv, LocalEngine, SimCluster

    wl = get_workload(args.workload)
    if args.backend != "numpy" and not (args.env == "sim" and args.fleet > 1):
        raise SystemExit(
            f"--backend {args.backend} needs --env sim --fleet N>1: the "
            "device engine is the fleet tick backend (DESIGN.md §9); serial "
            "SimCluster and LocalEngine are numpy-only")
    if args.env == "sim" and args.fleet > 1:
        wls = (fleet_workloads(args.fleet, seed=args.seed) if args.fleet_mix
               else [get_workload(args.workload) for _ in range(args.fleet)])
        env = FleetEnv(wls, seed=args.seed, backend=args.backend)
        window = args.window
        print(f"[fleet] {args.fleet} clusters "
              f"({'mixed roster' if args.fleet_mix else args.workload}, "
              f"{args.backend} backend)")
    elif args.env == "sim":
        env = SimCluster(wl, seed=args.seed)
        window = args.window
    else:
        env = LocalEngine(wl, seed=args.seed, arch=args.arch)
        window = min(args.window, 6.0)  # real seconds on CPU

    fleet = args.env == "sim" and args.fleet > 1
    if args.device_loop == "on":
        # env-level gates are checkable NOW — fail before the collect
        # budget is spent (reward-mode gate re-checked post-analysis)
        from repro.core.device_loop import env_device_reason

        env_reason = env_device_reason(env)
        if env_reason is not None:
            raise SystemExit(f"--device-loop=on but the fused device loop "
                             f"cannot run: {env_reason}")
    tuner = AutoTuner(env, seed=args.seed, window_s=window)
    print(f"[collect] {args.collect} windows …")
    tuner.collect(args.collect)
    mets, levs = tuner.analyse()
    print(f"[analyse] metrics k={tuner.selection.k} "
          f"(reduction {tuner.selection.reduction:.0%}): {mets}")
    print(f"[analyse] ranked levers: {levs}")

    env.reset()
    if fleet:
        # fleet-mean baseline: under --fleet-mix the clusters carry different
        # workloads, so comparing the cross-fleet best against any single
        # cluster's default would misstate the gain
        base_p99 = float(np.mean([w.p99_ms for w in env.observe(window)]))
        steps_per_update = args.steps_per_episode * max(env.n_clusters,
                                                        args.episodes)
    else:
        base_p99 = env.observe(window).p99_ms
        steps_per_update = args.steps_per_episode * args.episodes
    print(f"[tune] default p99 = {base_p99:.0f} ms")
    if args.safe and args.reward != "slo":
        raise SystemExit("--safe needs --reward slo (the shield's breach "
                         "signal is the in-trace window breach fraction)")
    cfgr = tuner.build_configurator(
        steps_per_episode=args.steps_per_episode,
        episodes_per_update=args.episodes, window_s=window, f_exploit=args.f,
        device_loop=args.device_loop, reward_mode=args.reward,
        slo_ms=args.slo_ms, safe=args.safe,
        shield_kw=(dict(trust_radius=args.trust_radius,
                        breach_budget=args.breach_budget)
                   if args.safe else None))
    if args.safe:
        print(f"[tune] safe exploration (§16): shield ACTIVE — trust radius "
              f"±{args.trust_radius} bins, breach budget "
              f"{args.breach_budget}/episode")
    reason = cfgr.device_loop_reason()
    if args.device_loop == "on" and reason is not None:
        # fail BEFORE the tuning loop starts, with the supported() reason —
        # a silent host-loop fallback here would burn the whole --updates
        # budget at per-step host speed without anyone noticing
        raise SystemExit(f"--device-loop=on but the fused device loop "
                         f"cannot run: {reason}")
    if args.device_loop == "auto" and reason is not None:
        print(f"[tune] fused device loop (§10): off — {reason} "
              "(per-step host loop)")
    if fleet and reason is None:
        runner = cfgr._device_runner()
        mesh = runner.mesh
        print("[tune] fused device loop (§10): ACTIVE — one episode program "
              "+ one update program per outer iteration"
              + (f", cluster axis sharded over {mesh.size} devices (§11)"
                 if mesh is not None else ""))

    def cb(i, stats, history):
        last = history[-steps_per_update:]
        print(f"[tune] update {i}: p99 mean {np.mean([r.p99_ms for r in last]):.0f} "
              f"min {np.min([r.p99_ms for r in last]):.0f} ms  "
              f"return {stats['mean_return']:.2f}")

    from repro.monitoring import ChaosCounters, flush_guard, spans

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    def metrics_text():
        runner = cfgr._runner
        chaos = runner.chaos if runner is not None else ChaosCounters()
        text = chaos.prometheus_text()
        if args.safe:
            text += cfgr.shield_counters.prometheus_text()
        return text + spans.prometheus_text()

    # the guard (shared with launch/serve.py) remaps SIGTERM to
    # KeyboardInterrupt and writes the dump in its finally — a Ctrl-C'd or
    # killed tune run always leaves a final metrics.prom behind
    interrupted = False
    try:
        with flush_guard(out / "metrics.prom", metrics_text):
            cfgr.tune(args.updates, callback=cb)
    except KeyboardInterrupt:
        interrupted = True
        print(f"[interrupted] final metrics dump at {out}/metrics.prom")
    if interrupted and not cfgr.history:
        return None
    best = min(cfgr.history, key=lambda r: r.p99_ms)
    print(f"[done] best p99 {best.p99_ms:.0f} ms "
          f"({100 * (1 - best.p99_ms / base_p99):.0f}% below default)")

    tuner.save_analysis(out / "analysis.json")
    hist = [
        dict(lever=r.lever, direction=r.direction, reward=r.reward,
             p99_ms=r.p99_ms, clock_s=r.clock_s, phases=r.phases)
        for r in cfgr.history
    ]
    (out / "history.json").write_text(json.dumps(
        {"default_p99_ms": base_p99, "best_p99_ms": best.p99_ms,
         "best_config": best.config, "history": hist}, indent=2))
    print(f"[done] wrote {out}/analysis.json and {out}/history.json")
    return cfgr


if __name__ == "__main__":
    main()
