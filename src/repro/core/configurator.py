"""RL configuration feedback loop (paper Fig 3 bottom, §3, §4.2).

``TuningEnv`` is the protocol both environments implement (the analytic
``SimCluster`` and the real ``LocalEngine``; DESIGN.md §2). The configurator
drives the paper's episode loop against it:

  observe heat-maps -> pick (lever, direction) -> discretise -> apply config
  -> buffer events during loading -> wait for stabilisation -> measure
  latency -> reward -> (end of episode) REINFORCE update.

Each step records its simulated loading and stabilisation seconds for the
Fig 6 execution-breakdown reproduction; the host's own phases are named
spans (``repro.monitoring.spans``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Protocol, Sequence

import numpy as np

from repro.core.discretize import (DeviceLeverTable, LeverDiscretiser,
                                   LeverSpec, ShieldSpec, shield_update)
from repro.core.heatmap import HeatmapEncoder, HeatmapSpec
from repro.core.policy import ReinforceAgent, Trajectory
from repro.monitoring.spans import span, step


class MetricsWindow(Protocol):
    per_node: dict[str, np.ndarray]   # metric -> (n_nodes,) window average
    latencies_ms: np.ndarray          # per-event end-to-end latency sample
    p99_ms: float
    clock_s: float                    # environment clock (simulated or real)


class TuningEnv(Protocol):
    """Implemented by repro.engine.simcluster.SimCluster and
    repro.engine.local.LocalEngine."""

    lever_specs: Sequence[LeverSpec]
    metric_names: Sequence[str]
    n_nodes: int

    def reset(self) -> None: ...
    def current_config(self) -> dict: ...
    def apply_config(self, config: dict) -> dict:
        """Install a config. Returns {'load_s': float, 'rebooted': bool}."""
    def observe(self, window_s: float) -> MetricsWindow:
        """Advance the environment by window_s and return the window metrics."""
    def stabilisation_time(self) -> float:
        """Seconds until latency variance trend flattens (paper: <3 min p99)."""


class FleetTuningEnv(Protocol):
    """The plural twin of ``TuningEnv``: N clusters stepped as one batch
    (repro.engine.fleet.FleetEnv; DESIGN.md §2a). The configurator runs the
    Algorithm-1 episode batch as N *parallel* episodes — one per cluster —
    and the tuner's §2.1 exploration sweeps the whole fleet per window."""

    lever_specs: Sequence[LeverSpec]
    metric_names: Sequence[str]
    n_nodes: int
    n_clusters: int

    def reset(self) -> None: ...
    def current_configs(self) -> list[dict]: ...
    def apply_configs(self, configs: Sequence[dict],
                      changed_levers: Optional[Sequence] = None,
                      copy: bool = True) -> list[dict]:
        """Install one config per cluster; list of {'load_s', 'rebooted'}.
        ``changed_levers`` optionally names each cluster's moved levers so
        the env can skip the full config diff; ``copy=False`` hands over
        ownership of the dicts (hot-loop contract, DESIGN.md §9)."""
    def observe(self, window_s, preroll_s=None) -> list[MetricsWindow]:
        """Advance all clusters by window_s (scalar or per-cluster array);
        ``preroll_s`` prepends a stabilisation wait excluded from the
        window (fused on-device for jax/pallas backends)."""
    def advance(self, window_s) -> None:
        """observe() without building window summaries (stabilisation waits)."""
    def stabilisation_times(self) -> np.ndarray:
        """(N,) seconds until each cluster's latency trend flattens."""
    def runnable_mask(self, configs: Sequence[dict]) -> np.ndarray:
        """(N,) bool — the paper's allow-list, vectorised."""


def is_fleet_env(env) -> bool:
    """True when env speaks the batched FleetTuningEnv protocol (any N ≥ 1)."""
    return getattr(env, "n_clusters", 0) >= 1 and hasattr(env, "apply_configs")


@dataclass
class StepRecord:
    lever: str
    direction: int
    config: dict
    reward: float
    p99_ms: float
    clock_s: float
    phases: dict  # simulated loading/stabilisation seconds


@dataclass
class EpisodeResult:
    steps: list[StepRecord]
    mean_return: float


def reward_from_latency(latencies_ms: np.ndarray, mode: str = "neg_mean", *,
                        slo_ms: float = 1000.0, hinge_w: float = 1.0,
                        breach_w: float = 1.0) -> float:
    """Paper's delay-dependent reward. The text writes sum(-1/T_e) but states
    the cumulative reward equals negative summed latency (gamma=1); we default
    to -mean(T) and keep the literal form as an option (DESIGN.md §1).
    ``neg_p99`` targets the tail SLO directly; on device backends both it and
    ``neg_mean`` read the window's device-computed statistic instead of
    materialising the latency sample on host.

    ``slo`` (DESIGN.md §12) is the SLO-aware shaping used for chaos
    recovery: -mean latency, minus a hinge penalty whenever the window p99
    breaches ``slo_ms``, minus a breach-*duration* term. On this host path
    the duration proxy is the fraction of latency samples above the SLO;
    the fused device loop uses the fraction of window ticks whose analytic
    mean breaches it (``stats["breach_frac"]``) — same shaping, tick-level
    granularity."""
    lat = np.asarray(latencies_ms, float)
    lat = lat[np.isfinite(lat) & (lat > 0)]
    if lat.size == 0:
        return -1e4  # failed window: strongly negative
    if mode == "neg_mean":
        return float(-lat.mean() / 1000.0)
    if mode == "neg_p99":
        return float(-np.percentile(lat, 99.0) / 1000.0)
    if mode == "neg_sum":
        return float(-lat.sum() / 1000.0)
    if mode == "neg_inv":  # the literal Σ -1/T form from the paper text
        return float(np.sum(-1.0 / np.maximum(lat, 1e-3)))
    if mode == "slo":
        p99 = float(np.percentile(lat, 99.0))
        breach = float((lat > slo_ms).mean())
        return float(-lat.mean() / 1000.0
                     - hinge_w * max(p99 - slo_ms, 0.0) / 1000.0
                     - breach_w * breach)
    raise ValueError(mode)


class Configurator:
    """Paper §3: runs tuning phases made of episodes of N configuration steps.

    ``device_loop`` selects the §10 fused training loop over a device-backed
    fleet: ``"auto"`` (default) uses it whenever ``device_loop_reason()``
    is None, ``"on"`` fails loudly when it can't, ``"off"`` always runs the
    per-step host loop.

    ``mesh`` shards the fused loop's cluster axis across devices
    (DESIGN.md §11): ``"auto"`` (default) uses
    ``repro.distribution.sharding.fleet_mesh()`` whenever the fleet size
    divides the visible device count, ``"off"``/None pins single-device,
    or pass an explicit 1-D ``jax.sharding.Mesh``.

    ``reward_mode="slo"`` (DESIGN.md §12) shapes the reward against a
    latency SLO: ``slo_ms`` is the p99 target, ``slo_hinge_w`` weights the
    hinge penalty on a window-p99 breach and ``slo_breach_w`` weights the
    breach-duration term. The fused device loop computes the breach
    fraction in-trace (``stats["breach_frac"]``); the host loops proxy it
    with the fraction of latency samples above the SLO."""

    def __init__(
        self,
        env: TuningEnv,
        selected_metrics: Sequence[str],
        ranked_levers: Sequence[str],
        *,
        f_exploit: float = 0.8,
        gamma: float = 1.0,
        lr: float = 1e-3,
        steps_per_episode: int = 10,
        episodes_per_update: int = 4,
        window_s: float = 120.0,
        reward_mode: str = "neg_mean",
        slo_ms: float = 1000.0,
        slo_hinge_w: float = 1.0,
        slo_breach_w: float = 1.0,
        seed: int = 0,
        bin_kw: Optional[dict] = None,
        device_loop: str = "auto",
        mesh="auto",
        safe: bool = False,
        shield_kw: Optional[dict] = None,
    ):
        assert device_loop in ("auto", "on", "off"), device_loop
        self.env = env
        self.fleet = is_fleet_env(env)
        self.device_loop = device_loop
        self.mesh_opt = mesh
        self._runner = None            # lazy DeviceEpisodeRunner (§10)
        self.levers = [l for l in ranked_levers if l in {s.name for s in env.lever_specs}]
        assert self.levers, "no ranked lever matches the environment's lever set"
        self.disc = LeverDiscretiser(list(env.lever_specs), seed=seed,
                                     **(bin_kw or {}))
        self.hspec = HeatmapSpec(list(selected_metrics), list(self.levers),
                                 env.n_nodes)
        self.encoder = HeatmapEncoder(self.hspec)
        self.agent = ReinforceAgent(
            self.hspec.state_dim, self.levers, f_exploit=f_exploit, gamma=gamma,
            lr=lr, seed=seed)
        self.steps_per_episode = steps_per_episode
        self.episodes_per_update = episodes_per_update
        self.window_s = window_s
        self.reward_mode = reward_mode
        self.slo_ms = float(slo_ms)
        self.slo_hinge_w = float(slo_hinge_w)
        self.slo_breach_w = float(slo_breach_w)
        #: §16 safety shield (DESIGN.md §16): None = unshielded exploration,
        #: a ShieldSpec = trust-region masked sampling + fallback-to-LKG +
        #: per-episode breach budget, on BOTH the fused device loop and the
        #: per-step host loop (its numpy twin below)
        self.shield = ShieldSpec(**(shield_kw or {})) if safe else None
        if self.shield is not None and reward_mode != "slo":
            raise ValueError(
                "safe exploration needs reward_mode='slo': the shield's "
                "breach-risk carry reads the window breach fraction")
        from repro.monitoring.metrics import ShieldCounters
        self.shield_counters = ShieldCounters()
        self._host_shield = None   # numpy twin carry (sig, lkg, radius, ...)
        self.history: list[StepRecord] = []
        self._last_window: Optional[MetricsWindow] = None
        self._last_fleet_windows: Optional[list] = None
        try:  # selected-metric columns in registry order (dense encodes)
            self._sel_cols = [list(env.metric_names).index(m)
                              for m in self.hspec.metric_names]
        except ValueError:
            self._sel_cols = None

    # -- state encoding -------------------------------------------------------
    def _lever_fracs(self, config: dict) -> dict[str, float]:
        out = {}
        for name in self.levers:
            spec = self.disc.specs[name]
            if spec.kind == "choice":
                out[name] = spec.choices.index(config[name]) / max(len(spec.choices) - 1, 1)
            elif spec.kind == "bool":
                out[name] = float(bool(config[name]))
            else:
                dyn = self.disc.bins[name]
                out[name] = dyn.bin_of(float(config[name])) / max(dyn.n_bins - 1, 1)
        return out

    def _encode(self, window: MetricsWindow, config: dict) -> np.ndarray:
        return self.encoder.encode(window.per_node, self._lever_fracs(config))

    def _encode_fleet(self, windows, configs) -> np.ndarray:
        """(N, state_dim) fleet state batch with ONE running-range update for
        the whole fleet (``HeatmapEncoder.encode_fleet``) — the normalisation
        the fused device program uses, so host-loop and device-loop policies
        see identical states. Falls back to the per-cluster path when a
        window lacks the dense node matrix."""
        mats = [getattr(w, "node_matrix", None) for w in windows]
        if self._sel_cols is None or any(m is None for m in mats):
            return np.stack([self._encode(w, c)
                             for w, c in zip(windows, configs)])
        raw = np.stack(mats)[:, :, self._sel_cols]       # (N, nodes, M_sel)
        fracs = np.array([[self._lever_fracs(c)[l] for l in self.levers]
                          for c in configs])
        return self.encoder.encode_fleet(raw, fracs)

    # -- the loop ---------------------------------------------------------------
    def run_episode(self, *, explore: bool = True) -> tuple[Trajectory, list[StepRecord]]:
        traj = Trajectory()
        records: list[StepRecord] = []
        config = self.env.current_config()
        window = self._last_window or self.env.observe(self.window_s)
        for _ in range(self.steps_per_episode):
            state = self._encode(window, config)
            a = self.agent.act(state, explore=explore)
            lever, direction = self.agent.action_decode(a)

            new_config = self.disc.apply(config, lever, direction)
            report = self.env.apply_config(new_config)
            stab_s = self.env.stabilisation_time()
            if stab_s > 0:
                # paper §4.2: wait for stabilisation; the reward is measured
                # on the window AFTER it, so skip summaries when the env can
                getattr(self.env, "advance", self.env.observe)(stab_s)
            window = self.env.observe(self.window_s)
            reward = reward_from_latency(window.latencies_ms, self.reward_mode,
                                         slo_ms=self.slo_ms,
                                         hinge_w=self.slo_hinge_w,
                                         breach_w=self.slo_breach_w)

            traj.add(state, a, reward)
            records.append(StepRecord(
                lever=lever, direction=direction, config=dict(new_config),
                reward=reward, p99_ms=window.p99_ms, clock_s=window.clock_s,
                phases={"loading_s": report["load_s"],
                        "stabilisation_s": stab_s},
            ))
            config = new_config
        self._last_window = window
        return traj, records

    def run_fleet_episodes(self, *, explore: bool = True
                           ) -> tuple[list[Trajectory], list[StepRecord]]:
        """Algorithm 1's episode batch as N *parallel* episodes — one per
        fleet cluster. Each step: one vmapped policy dispatch over all cluster
        states, one batched apply/stabilise/observe across the fleet. The
        trajectories then feed the same per-step-baseline REINFORCE update as
        the serial path (the batch axis is the episode axis).

        Over a device-backed fleet (``env.backend`` jax/pallas, DESIGN.md §9)
        the step tightens further: action sampling is one fused device
        program (``act_batch_device``), the §4.2 stabilisation wait is fused
        into the observation window (``observe(..., preroll_s=...)``), and
        rewards come from the device-computed window means instead of
        materialising every cluster's latency sample on the host."""
        env = self.env
        N = env.n_clusters
        device = getattr(env, "backend", "numpy") != "numpy"
        trajs = [Trajectory() for _ in range(N)]
        records: list[list[StepRecord]] = [[] for _ in range(N)]
        configs = env.current_configs()
        windows = self._last_fleet_windows or env.observe(self.window_s)
        spec = self.shield
        if spec is not None:
            # §16 numpy twin of the fused loop's shield: walk the SAME
            # integerised table (frozen for the episode; §2.4.1 replay at
            # the end, like the device materialise), carry LKG/radius/
            # streak/risk across episodes keyed on the bin-edge signature
            table = DeviceLeverTable.from_discretiser(self.disc)
            names = table.names
            ranked = np.asarray([table.index_of[n] for n in self.levers])
            idx = table.index_configs(configs)
            rows = np.arange(N)
            sig = tuple(e.tobytes() if e is not None else b""
                        for e in table._edges)
            if self._host_shield is not None and self._host_shield[0] == sig:
                _, lkg, radius, streak, risk = self._host_shield
            else:
                lkg = idx.copy()
                radius = np.full(N, spec.trust_radius, np.int32)
                streak = np.zeros(N, np.int32)
                risk = np.zeros(N, np.float32)
            budget = np.full(N, spec.breach_budget, np.int32)
            ex_any = np.zeros(N, bool)
            replay_l: list = []
            replay_b: list = []
        for _ in range(self.steps_per_episode):
            states = self._encode_fleet(windows, configs)
            mask = (table.shield_mask(idx, lkg, radius, ranked)
                    if spec is not None else None)
            if device:
                actions = np.asarray(self.agent.act_batch_device(
                    states, explore=explore, mask=mask))
            else:
                actions = self.agent.act_batch(states, explore=explore,
                                               mask=mask)
            if spec is not None:
                # the device twin's diversion signal: a step counts as
                # clamped when the mask removed the action the policy's
                # own argmax would have taken (the deterministic
                # counterfactual — no extra RNG draws, mirroring the
                # device loop's same-key counterfactual pick); folded into
                # clamped_actions together with hard-clamp landings below
                a_free = self.agent.act_batch(states, greedy=True)
                diverted = ~mask[rows, a_free]
            decoded = [self.agent.action_decode(int(a)) for a in actions]
            if spec is None:
                new_configs = [self.disc.apply(c, lever, direction)
                               for c, (lever, direction)
                               in zip(configs, decoded)]
                changed = [(l,) for l, _ in decoded]
            else:
                # integerised apply + hard trust-region clamp + risk/budget
                # fallback-to-LKG — index-for-index the device loop's §16
                # shield arithmetic
                l_idx = ranked[actions // 2]
                direction = np.where(actions % 2 == 0, 1, -1)
                prev_idx = idx.copy()
                raw = table.step_index(idx[rows, l_idx], l_idx, direction)
                nb = table.shield_clamp(raw, lkg[rows, l_idx], radius, l_idx)
                fallback = (risk > spec.risk_threshold) | (budget <= 0)
                idx[rows, l_idx] = nb
                idx = np.where(fallback[:, None], lkg, idx)
                self.shield_counters.clamped_actions += int(
                    (diverted | (nb != raw)).sum())
                self.shield_counters.fallbacks += int(fallback.sum())
                replay_l.append(l_idx.copy())
                replay_b.append(idx[rows, l_idx].copy())
                new_configs = []
                changed = []
                for i in range(N):
                    cfg = dict(configs[i])
                    moved = np.nonzero(idx[i] != prev_idx[i])[0]
                    for li in moved:
                        cfg[names[li]] = table.value_of(int(li),
                                                        int(idx[i, li]))
                    new_configs.append(cfg)
                    changed.append(tuple(names[int(li)] for li in moved))
            reports = env.apply_configs(new_configs, changed_levers=changed)
            stabs = env.stabilisation_times()
            # paper §4.2: reward measured on the window after stabilisation
            windows = env.observe(self.window_s, preroll_s=stabs)
            if device and self.reward_mode in ("neg_mean", "neg_p99"):
                # the window's device-computed statistic — no per-cluster
                # latency sample ever materialises on host
                if self.reward_mode == "neg_mean":
                    rewards = [-w.mean_ms / 1000.0 for w in windows]
                else:
                    rewards = [-w.p99_ms / 1000.0 for w in windows]
            else:
                rewards = [reward_from_latency(w.latencies_ms,
                                               self.reward_mode,
                                               slo_ms=self.slo_ms,
                                               hinge_w=self.slo_hinge_w,
                                               breach_w=self.slo_breach_w)
                           for w in windows]
            if spec is not None:
                # host breach-fraction proxy (the slo reward's): fraction
                # of the window's latency samples above the SLO
                bf = np.empty(N, np.float32)
                for i, w in enumerate(windows):
                    lat = np.asarray(w.latencies_ms, float)
                    lat = lat[np.isfinite(lat) & (lat > 0)]
                    bf[i] = float((lat > self.slo_ms).mean()) \
                        if lat.size else 1.0
                lkg, radius, streak, risk, budget, b_out = shield_update(
                    bf, lkg, idx, radius, streak, risk, budget, spec,
                    xp=np)
                ex_any |= np.asarray(b_out)
            for i in range(N):
                reward = rewards[i]
                trajs[i].add(states[i], int(actions[i]), reward)
                lever, direction = decoded[i]
                records[i].append(StepRecord(
                    lever=lever, direction=direction,
                    config=dict(new_configs[i]), reward=reward,
                    p99_ms=windows[i].p99_ms, clock_s=windows[i].clock_s,
                    phases={"loading_s": reports[i]["load_s"],
                            "stabilisation_s": float(stabs[i])},
                ))
            configs = new_configs
        if spec is not None:
            self._host_shield = (sig, lkg, radius, streak, risk)
            self.shield_counters.budget_exhaustions += int(ex_any.sum())
            self.shield_counters.trust_radius = float(radius.mean())
            # §2.4.1 replay, step-major like the device materialise (the
            # table stayed frozen for the whole episode)
            lever_sm = np.concatenate(replay_l)
            bin_sm = np.concatenate(replay_b)
            for li in np.unique(lever_sm):
                dyn = self.disc.bins.get(names[li])
                if dyn is not None:
                    dyn.record_many(bin_sm[lever_sm == li])
        self._last_fleet_windows = windows
        return trajs, [r for cluster in records for r in cluster]

    def contract_shield(self) -> None:
        """Collapse the shield's trust region to its floor and reset the
        clean-window streaks, on whichever path (fused runner / numpy twin)
        holds shield state. The serve loop's breach-budget trip (DESIGN.md
        §16): exploration continues, but confined to ±radius_min bins
        around the last-known-good configs until clean windows re-earn the
        radius through the normal expand schedule."""
        spec = self.shield
        if spec is None:
            return
        runner = self._runner
        if runner is not None and runner._shield is not None:
            import jax.numpy as jnp

            lkg, radius, streak, risk = runner._shield
            runner._shield = (lkg, jnp.full_like(radius, spec.radius_min),
                              jnp.zeros_like(streak), risk)
        if self._host_shield is not None:
            sig, lkg, radius, streak, risk = self._host_shield
            self._host_shield = (sig, lkg,
                                 np.full_like(radius, spec.radius_min),
                                 np.zeros_like(streak), risk)
        self.shield_counters.trust_radius = float(spec.radius_min)

    # -- the fused device loop (DESIGN.md §10) ----------------------------------
    def _device_runner(self):
        if self._runner is None:
            from repro.core.device_loop import DeviceEpisodeRunner

            self._runner = DeviceEpisodeRunner(self)
        return self._runner

    def device_loop_reason(self) -> Optional[str]:
        """None when the fused device training loop will run; otherwise why
        the per-step host loop is used instead."""
        if self.device_loop == "off":
            return "device_loop='off'"
        if not self.fleet:
            return "serial TuningEnv (the fused loop is fleet-shaped)"
        return self._device_runner().supported()

    def run_fleet_episodes_device(self, *, explore: bool = True,
                                  greedy: bool = False):
        """The whole Algorithm-1 episode batch as ONE jitted device program
        (repro.core.device_loop): encode → act → integerised lever-apply →
        loading/stabilisation → fused observation window → reward, scanned
        over the episode steps with the queueing state carried through the
        recurrence. Returns ``(batch, records)``: ``batch`` holds the
        device-resident (N, S) states/actions/rewards ready for
        ``ReinforceAgent.update_batch`` (the outer iteration's only other
        device program); ``records`` are host ``StepRecord``s materialised
        once per batch. ``explore=False`` (or ``greedy=True``) takes the
        deterministic argmax action — exactly replayable against the host
        oracle (tests/test_device_loop.py)."""
        reason = self.device_loop_reason()
        if reason is not None:
            raise RuntimeError(f"fused device loop unavailable: {reason}")
        return self._device_runner().run(explore=explore, greedy=greedy)

    def run_update(self) -> dict:
        """One Algorithm-1 outer iteration: N episodes then a policy update.
        Against a FleetTuningEnv the N episodes run in parallel, one per
        cluster (as ≤2 fused device programs per pass when the §10 loop is
        available); serially otherwise."""
        with step("tune.update", self.agent.n_updates):
            return self._run_update()

    def _run_update(self) -> dict:
        with span("tune.check"):
            device = self.fleet and self.device_loop != "off" \
                and self.device_loop_reason() is None
        if self.device_loop == "on" and not device:
            raise RuntimeError(
                f"device_loop='on' but: {self.device_loop_reason()}")
        if device:
            return self._run_update_device()
        if self.fleet:
            # small fleets still need a real episode batch: Algorithm 1's
            # per-step baseline is the across-episode mean, which degenerates
            # (zero advantages) with a single episode — run enough fleet
            # passes to reach episodes_per_update episodes
            passes = max(1, -(-self.episodes_per_update // self.env.n_clusters))
            trajs, all_records = [], []
            for _ in range(passes):
                t, r = self.run_fleet_episodes()
                trajs.extend(t)
                all_records.extend(r)
        else:
            trajs, all_records = [], []
            for _ in range(self.episodes_per_update):
                t, r = self.run_episode()
                trajs.append(t)
                all_records.extend(r)
        stats = self.agent.update(trajs)
        return self._finish_update(stats, all_records)

    def _run_update_device(self) -> dict:
        """§10 outer iteration: one fused episode program per pass + ONE
        jitted update — the (N, T) episode batch never bounces to host.

        Double-buffered dispatch (§11): the passes chain device-side
        (``run_async``), the update program is enqueued on their
        device-resident outputs, and only THEN does the host block and
        materialise records / replay §2.4.1 bins
        (``DeviceEpisodeRunner.run_cycle``) — the host-side adaptation
        work overlaps the device update."""
        runner = self._device_runner()
        passes = max(1, -(-self.episodes_per_update // self.env.n_clusters))
        stats, all_records = runner.run_cycle(passes=passes)
        return self._finish_update(stats, all_records)

    def _finish_update(self, stats: dict, all_records: list) -> dict:
        self.history.extend(all_records)
        stats["p99_ms"] = all_records[-1].p99_ms if all_records else float("nan")
        return stats

    def run_cycle(self) -> dict:
        """One serve-loop shadow pass (DESIGN.md §13): a single
        ``run_update`` outer iteration whose freshly-appended
        ``StepRecord``s ride back under ``stats["records"]`` — the serve
        controller picks its challenger from them without rescanning
        ``self.history``."""
        n0 = len(self.history)
        stats = self.run_update()
        stats["records"] = self.history[n0:]
        return stats

    def tune(self, n_updates: int, *, callback=None) -> list[StepRecord]:
        for i in range(n_updates):
            stats = self.run_update()
            if callback:
                callback(i, stats, self.history)
        return self.history

    def tune_pipelined(self, n_updates: int, *, depth: int = 2,
                       callback=None) -> list[StepRecord]:
        """``tune`` with a depth-``depth`` pipelined actor/learner
        (DESIGN.md §14): update k's jitted program runs while batch k+1's
        episode scan explores — device-to-device handoff of params and
        returns through the dispatch queue, host record materialisation
        deferred to one finalize per call (so §2.4.1 bin adaptation replays
        once per call, not per update, and episodes act on
        (depth-1)-update-stale params — IMPALA-style).

        ``depth=1`` IS the sequential schedule: it delegates to ``tune``
        and is pinned bitwise-equal to it. Requires the fused device loop."""
        if depth <= 1 or n_updates <= 0:
            return self.tune(n_updates, callback=callback)
        reason = self.device_loop_reason()
        if reason is not None:
            raise RuntimeError(
                f"pipelined tuning needs the fused device loop: {reason}")
        runner = self._device_runner()
        passes = max(1, -(-self.episodes_per_update // self.env.n_clusters))
        stats_list, records = runner.run_pipelined(
            n_updates, passes=passes, depth=depth)
        per = len(records) // n_updates if records else 0
        for k, stats in enumerate(stats_list):
            recs = records[k * per:(k + 1) * per] if per else []
            stats = self._finish_update(stats, recs)
            if callback:
                callback(k, stats, self.history)
        return self.history

    def run_epoch(self, k: int = 8, *, records: str = "full") -> list[dict]:
        """``k`` outer Algorithm-1 iterations as ONE jitted device program
        — the epoch mega-scan (DESIGN.md §15): episode batch → reward →
        policy update composed K times inside a single ``lax.scan``, zero
        host round-trips between updates. §2.4.1 bin adaptation defers to
        the epoch boundary (binning is frozen inside); ``records="full"``
        materialises the sequential path's exact ``StepRecord`` stream
        into ``history``, ``"summary"``/``"off"`` skip the record stream
        and return per-update convergence stats only. Requires the fused
        device loop. Returns the per-update stats dicts."""
        reason = self.device_loop_reason()
        if reason is not None:
            raise RuntimeError(
                f"epoch mega-scan needs the fused device loop: {reason}")
        runner = self._device_runner()
        passes = max(1, -(-self.episodes_per_update // self.env.n_clusters))
        stats_list, recs = runner.run_epoch(k, passes=passes,
                                            records=records)
        if recs:
            # same history bookkeeping as the sequential schedule
            per = len(recs) // max(len(stats_list), 1)
            for i, stats in enumerate(stats_list):
                self._finish_update(stats, recs[i * per:(i + 1) * per])
        return stats_list

    def tune_megascan(self, n_updates: int, *, k: int = 8,
                      records: str = "full",
                      callback=None) -> list[StepRecord]:
        """``tune`` over epoch mega-scans (DESIGN.md §15): ``n_updates``
        outer iterations dispatched as ⌈n/k⌉ fused K-update epochs instead
        of n separate program pairs. The callback fires per update, after
        the epoch containing it lands (epoch-granular collect: inside an
        epoch there is nothing host-visible to call back on)."""
        done = 0
        while done < n_updates:
            kk = min(k, n_updates - done)
            for j, stats in enumerate(self.run_epoch(kk, records=records)):
                if callback:
                    callback(done + j, stats, self.history)
            done += kk
        return self.history
