"""The general traffic drivers: one per ``kind`` a traffic file names.

A traffic file (``traffic/<name>.json``) is data: the job's kind and its
parameters (fleet size, backend, steps per episode, ...). The driver builds
the system under test from the deployment's configuration file and the
traffic's parameters, drives its first updates (or serve cycles) through the
same call the window uses, warms up the rest of the cell's shapes, and then
runs the closed loop: each update or cycle starts when the previous one
ends, as the launchers run them.

* ``train_loop``  — ``Configurator.tune`` with a per-update callback, as
  ``repro.launch.tune`` calls it, fused device loop on, bin adaptation live.
* ``serve_plane`` — ``ServeController.run_cycle``, built as
  ``repro.launch.serve --quick`` builds it, with checkpoints and the
  episode history written to a scratch directory.

Any other kind is ``drivers/<kind>.py``'s ``DRIVER`` (``driver_for``), and
any workload law not built in here is ``laws/<law>.py``
(``make_workloads``), both under the cell's benchmark directory.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

import compare
import lookup
from reference import Precision, init_policy


#: the §4.2 stabilisation wait is at most three minutes (``reference.py``)
LONGEST_STABILISATION_S = 180.0


class WindowClosed(Exception):
    """Raised from the per-update callback once the window has closed."""


def warm_up(cfgr, traffic: dict, one_unit) -> int:
    """Run one unit on each rung of the episode program's bin-table padding
    ladder above the one the fleet has reached (each rung compiles the
    episode program again; the §2.4.1 splits climb the ladder by a path
    that differs from seed to seed, so every seed is taken over every
    rung), then ``warmup_units`` more. Returns the units run."""
    from repro.core.device_loop import _BIN_BUCKETS

    runner = cfgr._runner
    n = 0
    for rung in _BIN_BUCKETS:
        if rung > runner._hw_B:
            runner._hw_B = rung
            runner._table = None          # repack the tables at this rung
            one_unit()
            n += 1
    for _ in range(int(traffic["warmup_units"])):
        one_unit()
        n += 1
    return n


def program_seed(seed: int) -> int:
    """The program's own seed (its streams take up to 31 bits), drawn from
    the benchmark's seed."""
    return int(np.random.default_rng([seed, 0]).integers(1, 2**30))


def make_workloads(law: dict, n: int, seed: int, bench: Path) -> list:
    """The ``n`` clusters' workloads of ``law``. A law that is not built in
    is the ``program`` half of ``laws/<law>.py`` under the cell's benchmark
    directory ``bench``, given the program's seed (the built-in laws have no
    use for it)."""
    from repro.data.workloads import (PoissonWorkload, SwitchingWorkload,
                                      YahooAdsWorkload)

    if law["law"] == "poisson":
        return [PoissonWorkload(float(law["rate"]),
                                float(law["event_size_mb"]))
                for _ in range(n)]
    if law["law"] == "diurnal":
        return [YahooAdsWorkload(base_rate=float(law["rate"]),
                                 diurnal_amp=float(law["amplitude"]),
                                 day_s=float(law["day_s"]),
                                 event_size_mb=float(law["event_size_mb"]),
                                 n_campaigns=int(law["campaigns"]))
                for _ in range(n)]
    if law["law"] == "switching":
        return [SwitchingWorkload(a, b, period_s=float(law["period_s"]))
                for a, b in zip(make_workloads(law["a"], n, seed, bench),
                                make_workloads(law["b"], n, seed, bench))]
    out = list(lookup.load(bench, "laws", law["law"]).program(law, n, seed))
    if len(out) != n:
        raise ValueError(f"bench: law {law['law']!r} built {len(out)} "
                         f"workloads for {n} clusters")
    return out


def sim_spec(deploy: dict):
    from repro.engine.simcluster import SimSpec

    cl = dict(deploy["cluster"])
    cl["straggler_slow"] = tuple(cl["straggler_slow"])
    return SimSpec(**cl)


def served_model(deploy: dict):
    from repro import configs

    return configs.get(deploy["served_model"]["name"])


class _UpdateSpy:
    """Records the first ``n`` policy updates' batches, losses, first
    optimizer moments and final parameters, through the agent's own update
    call, and the state encoder's inputs before the first episode (the
    per-node metrics and the running range the episode program is handed);
    detaches itself after ``n``."""

    def __init__(self, cfgr, n: int):
        self.agent, self.n = cfgr.agent, n
        self.batches, self.losses = [], []
        self.nu1 = self.params_end = self.enc = None
        self._orig = self.agent.update_batch_async
        self.agent.update_batch_async = self._call
        runner = cfgr._device_runner()
        fresh = runner._fresh_inputs

        def first_inputs():
            args = fresh()
            del runner._fresh_inputs             # back to the class method
            lo, hi, per_node = args[6:9]
            self.enc = {"lo": np.asarray(lo, np.float64),
                        "hi": np.asarray(hi, np.float64),
                        "per_node": np.asarray(per_node, np.float64)}
            return args

        runner._fresh_inputs = first_inputs

    def _call(self, states, actions, rewards, mask=None):
        self.batches.append((np.asarray(states, np.float64),
                             np.asarray(actions, np.int64),
                             np.asarray(rewards, np.float64)))
        pending = self._orig(states, actions, rewards, mask)
        k = len(self.batches)
        if k >= self.n:
            del self.agent.update_batch_async     # back to the class method

        def stats():
            st = pending()
            self.losses.append(float(st["pg_loss"]))
            ag = self.agent
            if k == 1:
                self.nu1 = {a: np.asarray(b) for a, b in
                            ag.opt_state["nu"].items()}
            if k == self.n:
                self.params_end = {a: np.asarray(b)
                                   for a, b in ag.params.items()}
            return st

        return stats


def install_levers(env, levers: dict) -> list:
    """Deploy the configuration's initial lever values on every cluster
    (the rest keep the simulator's defaults); returns the configs."""
    configs = [dict(c, **levers) for c in env.current_configs()]
    env.configs = [dict(c) for c in configs]
    env.invalidate()
    return configs


def _install_init(agent, seed: int) -> dict:
    """Seeded initial policy weights, handed to the program."""
    import jax.numpy as jnp

    init = init_policy(agent.state_dim, agent.n_actions,
                       np.random.default_rng([seed, 1]))
    agent.params = {k: jnp.asarray(v) for k, v in init.items()}
    agent.opt_state = agent.opt.init(agent.params)
    return init


def checked_numbers(driver) -> dict:
    """The numbers ``compare.py`` reads for a driver's checked updates (or
    cycles): the replay of the first of them, the encode
    and decode of the first, the apply check and the actions over all, and
    the policy updates, against the configuration's reference."""
    t = driver.traffic
    ref = compare.reference_for(driver.deploy, driver.bench)
    out = compare.simulation_numbers(
        driver.deploy, t, driver.checked_records, driver.configs0,
        n=driver.n, s=driver.s,
        rng=np.random.default_rng([driver.seed, 2]), ref=ref,
        bench=driver.bench)
    out["apply_errors"] = compare.apply_errors(
        driver.checked_records, driver.configs0, driver.deploy["ranked_levers"],
        n=driver.n, s=driver.s, units=int(t["checked_units"]))
    sp = driver.spy
    ranked = driver.deploy["ranked_levers"]
    states, actions, _ = sp.batches[0]
    out.update(compare.compare_act(
        compare.act_program(driver.checked_records, states, n=driver.n,
                            s=driver.s, ranked=ranked),
        compare.act_reference(driver.deploy, sp.enc, actions,
                              driver.configs0,
                              q=Precision("float64"), ref=ref)))
    out["act_z"] = compare.act_z(driver.init, sp.batches,
                                 f=float(t["f_exploit"]),
                                 warmup=int(t["f_warmup_updates"]), ref=ref)
    out.update(compare.update_numbers(driver.init, sp.batches, sp.losses,
                                      sp.nu1, sp.params_end, ref=ref))
    return out


class TrainLoop:
    unit = "update"

    def __init__(self, deploy: dict, traffic: dict, seed: int, workdir: Path,
                 bench: Path):
        from repro.core.configurator import Configurator
        from repro.engine import FleetEnv

        self.deploy, self.traffic, self.seed = deploy, traffic, seed
        self.bench = bench
        ps = program_seed(seed)
        self.n = int(traffic["fleet"])
        self.s = int(traffic["steps_per_episode"])
        env = FleetEnv(make_workloads(deploy["workload"], self.n, ps, bench),
                       model=served_model(deploy), spec=sim_spec(deploy),
                       seed=ps, backend=traffic["backend"])
        self.env = env
        self.cfgr = Configurator(
            env, deploy["selected_metrics"], deploy["ranked_levers"],
            f_exploit=float(traffic["f_exploit"]), steps_per_episode=self.s,
            episodes_per_update=int(traffic["episodes_per_update"]),
            window_s=float(deploy["window_s"]), device_loop="on",
            reward_mode=deploy["reward"]["mode"],
            slo_ms=float(deploy["slo_ms"]),
            slo_hinge_w=float(deploy["reward"]["hinge_w"]),
            slo_breach_w=float(deploy["reward"]["breach_w"]),
            seed=ps, mesh=traffic["mesh"])
        self.configs0 = install_levers(env, deploy["initial_levers"])
        self.init = _install_init(self.cfgr.agent, seed)
        self.windows_per_unit = self.n * self.s
        self.failed = 0

    def _tune(self, n: int, on_unit) -> None:
        def cb(i, stats, history):
            if not math.isfinite(float(stats["mean_return"])):
                self.failed += 1
            on_unit()

        try:
            self.cfgr.tune(n, callback=cb)
        except WindowClosed:
            pass

    def check_units(self) -> None:
        """Drive the checked updates through the window's own call."""
        k = int(self.traffic["checked_units"])
        self.spy = _UpdateSpy(self.cfgr, k)
        self._tune(k, lambda: None)
        self.checked_records = list(self.cfgr.history[:k * self.n * self.s])

    def setup(self) -> None:
        self.check_units()
        self.warmed = warm_up(self.cfgr, self.traffic,
                              lambda: self._tune(1, lambda: None))
        self.failed = 0

    def run(self, on_unit) -> None:
        self._tune(10**9, on_unit)

    def span_targets(self) -> list:
        """(object, method, span name) of the layer boundaries a traced
        run annotates."""
        r, ag = self.cfgr._runner, self.cfgr.agent
        return [(self.cfgr, "run_update", "run_update"),
                (r, "run_async", "episode dispatch"),
                (r, "finalize", "finalize"),
                (r, "_materialise", "_materialise"),
                (r, "_fresh_inputs", "_fresh_inputs"),
                (ag, "update_batch_async", "update dispatch")]

    def numbers(self) -> dict:
        return checked_numbers(self)

    def close(self) -> None:
        self.cfgr = self.env = None


class ServePlane:
    unit = "cycle"

    def __init__(self, deploy: dict, traffic: dict, seed: int, workdir: Path,
                 bench: Path):
        self.deploy, self.traffic, self.seed = deploy, traffic, seed
        self.bench = bench
        self.n = int(traffic["fleet"])
        self.s = int(traffic["steps_per_episode"])
        self.workdir = Path(workdir)
        self.kw = dict(
            metrics=deploy["selected_metrics"], levers=deploy["ranked_levers"],
            backend=traffic["backend"], seed=program_seed(seed),
            window_s=float(deploy["window_s"]), steps_per_episode=self.s,
            f_exploit=float(traffic["f_exploit"]),
            reward_mode=deploy["reward"]["mode"],
            slo_ms=float(deploy["slo_ms"]),
            slo_hinge_w=float(deploy["reward"]["hinge_w"]),
            slo_breach_w=float(deploy["reward"]["breach_w"]),
            k_promote=int(traffic["k_promote"]),
            margin=float(traffic["margin"]),
            eval_windows=int(traffic["eval_windows"]),
            canary_pairs=int(traffic["canary_pairs"]),
            n_live=int(traffic["live"]), device_loop="on",
            mesh=traffic["mesh"])
        self.ctl = self._controller(self.workdir)
        self.configs0 = self.ctl.shadow_env.current_configs()
        self.init = _install_init(self.ctl.cfgr.agent, seed)
        self.windows_per_unit = self.n * self.s
        self.failed = 0

    def _controller(self, workdir: Path):
        """A controller built as the serve launcher builds it, keeping its
        checkpoints and episode history under ``workdir``."""
        from repro.serve import ServeController

        env_kw = dict(model=served_model(self.deploy),
                      spec=sim_spec(self.deploy))
        ctl = ServeController(make_workloads(self.deploy["workload"], self.n,
                                             self.kw["seed"], self.bench),
                              checkpoint_dir=workdir / "ck",
                              history_path=workdir / "history.jsonl",
                              **self.kw)
        for env in (ctl.shadow_env, ctl.canary_env, ctl.live_env):
            install_levers(env, self.deploy["initial_levers"])
        ctl.incumbent = dict(ctl.incumbent, **self.deploy["initial_levers"])
        # the deployment's cluster spec and served model on all three fleets
        for env in (ctl.shadow_env, ctl.canary_env, ctl.live_env):
            assert env.spec == env_kw["spec"] and \
                env.models[0].name == env_kw["model"].name, \
                "the serve plane's fleets differ from the deployment"
        return ctl

    def _cycles(self, n: int, on_unit) -> None:
        for _ in range(n):
            s = self.ctl.run_cycle()
            if not math.isfinite(float(s["live_reward"])):
                self.failed += 1
            on_unit()

    def check_units(self) -> None:
        """Drive the checked cycles through the window's own call."""
        k = int(self.traffic["checked_units"])
        self.spy = _UpdateSpy(self.ctl.cfgr, k)
        self._cycles(k, lambda: None)
        self.checked_records = list(self.ctl.cfgr.history[:k * self.n * self.s])

    def setup(self) -> None:
        self.check_units()
        self.warmed = warm_up(self.ctl.cfgr, self.traffic,
                              lambda: self._cycles(1, lambda: None))
        self._warm_rare_paths()
        self.failed = 0

    def _warm_rare_paths(self) -> None:
        """Shapes that only rare cycles use, warmed on a throwaway controller
        so that they never compile inside the window: a promotion deploys a
        new incumbent on the live fleet, whose next window runs a few small
        programs no other cycle needs; and a challenger far from the
        incumbent prerolls its canary window for up to the longest
        stabilisation wait, which takes the window program up to a longer
        tick bucket."""
        ctl = self._controller(self.workdir / "warm")
        canary = ctl.canary_env
        canary.observe_stats(ctl.window_s, preroll_s=np.full(
            canary.n_clusters, LONGEST_STABILISATION_S))
        other = next((r.config for r in self.checked_records
                      if r.config != ctl.incumbent), None)
        if other is not None:
            ctl._promote(dict(other), 0.0)
            ctl._live_window()

    def run(self, on_unit) -> None:
        try:
            while True:
                self._cycles(1, on_unit)
        except WindowClosed:
            pass

    def span_targets(self) -> list:
        ctl = self.ctl
        r, ag = ctl.cfgr._runner, ctl.cfgr.agent
        return [(ctl, "run_cycle", "run_cycle"),
                (ctl.cfgr, "run_cycle", "shadow"),
                (r, "run_async", "episode dispatch"),
                (r, "finalize", "finalize"),
                (r, "_materialise", "_materialise"),
                (r, "_fresh_inputs", "_fresh_inputs"),
                (ag, "update_batch_async", "update dispatch"),
                (ctl, "_adopt_challenger", "adopt challenger"),
                (ctl, "_canary_eval", "canary"),
                (ctl, "_live_window", "live"),
                (ctl, "checkpoint", "checkpoint"),
                (ctl.history, "append", "history append")]

    def guarantee_numbers(self) -> dict:
        """§13: no promoted config breached in canary; the latest
        promotion's checkpoint restores the promoted incumbent."""
        rows = [json.loads(line) for line in
                (self.workdir / "history.jsonl").read_text().splitlines()
                if line.strip()]
        key = lambda c: json.dumps(c, sort_keys=True)
        breached, bad = set(), 0
        for r in sorted(rows, key=lambda r: r["cycle"]):
            if r["role"] == "canary" and r.get("breached"):
                breached.add(key(r["config"]))
            elif r["role"] == "promote" and key(r["config"]) in breached:
                bad += 1
        promos = [r for r in rows if r["role"] == "promote"]
        errors = 0
        if promos:
            last = max(promos, key=lambda r: r["cycle"])
            fresh = self._controller(self.workdir)
            cycle = fresh.restore(self.ctl.store)
            errors = int(key(fresh.incumbent) != key(last["config"])) \
                + int(cycle != last["cycle"])
        self.promotions = len(promos)
        return {"promoted_breached": bad, "restore_errors": errors}

    def numbers(self) -> dict:
        return dict(checked_numbers(self), **self.guarantee_numbers())

    def close(self) -> None:
        self.ctl = None


DRIVERS = {"train_loop": TrainLoop, "serve_plane": ServePlane}


def driver_for(kind: str, bench: Path):
    """The driver class of a traffic ``kind``: a built-in one, else
    ``drivers/<kind>.py``'s ``DRIVER`` under the cell's benchmark directory
    ``bench``. Each is built as ``(deploy, traffic, seed, workdir, bench)``."""
    if kind in DRIVERS:
        return DRIVERS[kind]
    return lookup.load(bench, "drivers", kind).DRIVER
