"""Device-resident Algorithm 1: the fused episode batch (DESIGN.md §10, §11).

PR 2 put the *simulator* on device; the online loop still ran as a per-step
Python loop — encode states cluster-by-cluster on host, decode actions in
Python, apply levers through the dict-based discretiser, ship ``(N, T)``
arrays back for the REINFORCE update. At N=1024 that control loop, not the
engine, is the bottleneck. This module fuses ONE full Algorithm-1 episode
batch (S steps × N parallel episodes) into a single jitted device program:

    for each step (lax.scan over S):
      encode    heat-map states from the carried per-node window metrics +
                integerised lever fractions (fleet-batch running-range
                normalisation carried through the scan; under a mesh the
                range reduction is a cross-device ``pmin``/``pmax``)
      act       ``repro.core.policy._sample_actions`` (f-gated sampling, or
                argmax when greedy) — same params, no host round-trip
      apply     integerised lever move (``DeviceLeverTable`` index
                arithmetic) + packed-coefficient gather, loading-time
                buffering, reconfiguration accounting
      stabilise paper-§4.2 wait from the on-device service-term delta
      observe   ``repro.engine.fleet_jax.build_step_window`` — the
                scan-composable window program (preroll + window + selected
                metric emission) carrying backlog/server-occupancy/clock;
                arrival rates are evaluated in-trace from the packed
                ``DeviceWorkloadTable`` (§11), so Trapezoid ramps and
                SwitchingWorkload regime flips run fused end-to-end
      reward    the window's device-computed mean (``neg_mean``), p99
                (``neg_p99``) or SLO-shaped penalty (``slo``: hinge on the
                window-p99 breach plus the in-trace breach-duration
                fraction, DESIGN.md §12); no latency sample ever
                materialises

The program returns the full ``(N, S)`` states/actions/rewards batch (for
``ReinforceAgent.update_batch`` — the second and last device program of an
outer iteration) plus the per-step bookkeeping (lever, bin, load, stab, p99)
from which ``StepRecord``s are materialised ONCE per episode batch.

Division of labour with the host oracle (DESIGN.md §10): the dict-based
``LeverDiscretiser`` stays authoritative for §2.4.1 *adaptation* — after
each fused batch the chosen (lever, bin) assignments are replayed into its
``DynamicBins`` host-side, and the next batch re-packs the table from the
adapted binning. Inside a batch the binning is frozen.

**Multi-device fleets (§11).** When more than one jax device is visible
(``repro.distribution.sharding.fleet_mesh``) and N divides the device
count, the episode program runs under ``shard_map`` with the cluster axis
sharded ``P("fleet")``: policy params and lever/workload tables replicate,
every per-cluster array lives shard-local, the per-shard RNG key is
decorrelated with ``fold_in(key, axis_index)``, and the only cross-cluster
coupling — the heat-map running range — is a per-step ``pmin``/``pmax``
of an (M_sel,) vector. Loop-state buffers are donated, so an outer
iteration runs as per-device programs with no host round-trips inside it.

**Double-buffered dispatch (§11).** ``run_async`` enqueues the episode
program and returns the device-resident batch immediately; ``finalize``
blocks, adopts the queueing state and materialises the host bookkeeping
(StepRecords + the §2.4.1 bin replay). ``Configurator._run_update_device``
dispatches the policy-update program *between* the two, so the host-side
adaptation work overlaps the device update. With multiple passes per
update the passes chain device-side (pass k+1 is dispatched from pass k's
carried state before pass k's records exist); their bin replay is deferred
to the iteration boundary — the one-step-stale binning this implies is the
documented IMPALA-style decoupling trade.

**Fault scenarios (§12).** When the fleet carries a ``DeviceFaultTable``
(``FleetEnv(..., faults=...)``), the packed table rides into the episode
program as sharded arrays: straggler/failure/backlog-shock events are
evaluated in-trace by the fused observation window, and
``DeployLatencyFault`` clusters run the config they *requested R steps
ago* — a device-carried config-index history ring indexed per cluster —
while the encoder state still shows the requested knobs (the policy knows
what it asked for; the engine lags, paper §4.4).

**Epoch mega-scan (§15).** ``run_epoch(K)`` composes K whole outer
iterations — episode batch → reward → policy update — into ONE jitted
``lax.scan`` over updates: policy params, optimizer state, RNG offsets,
the fleet loop state, the deploy-history ring and a compact (lever, bin)
count tensor carry device-to-device with donated buffers, so an epoch
costs O(1) program dispatches instead of O(K). Inside an epoch the
``DeviceLeverTable`` is frozen and §2.4.1 adaptation defers to the epoch
boundary (the contract chained passes already established); StepRecords
become optional per epoch (``records="full"|"summary"|"off"``), with a
device-side (K, N) reward/p99 summary replacing the bulk pull when only
convergence curves are needed.

Remaining gates (``DeviceEpisodeRunner.supported``): a device backend
(jax or pallas — the pallas window kernel is scan-composable since §11),
device-packable workloads (closed-form rate laws; IoT's precomputed burst
schedule is the one roster member that falls back to the host loop), and a
reward mode with a device-computed statistic (``neg_mean``, ``neg_p99``
or ``slo``).
"""
from __future__ import annotations

import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.discretize import (MAX_SPLIT_BINS, DeviceLeverTable,
                                   shield_update)
from repro.core.heatmap import node_grid_shape
from repro.core.policy import _sample_actions
from repro.data.workloads import pack_device_workloads, device_workload_reason
from repro.engine.simcluster import (_LEVER_TO_PACKED, _PACKERS,
                                     service_terms_arrays)
from repro.monitoring.spans import count, span

#: static-bundle -> times the episode program was traced; the §10 no-retrace
#: test pins that re-running outer iterations never grows these.
TRACE_COUNTS: dict = {}

#: epoch mega-scan program invocations (DESIGN.md §15): ``run_epoch(K)``
#: bumps this once per warm-up segment — the dispatch-count regression test
#: pins O(1) (not O(K)) dispatches per epoch.
EPOCH_DISPATCHES = [0]

#: padded tick budget when ``batch_interval_s`` is in the action set (the
#: episode can walk it low, shrinking the tick length mid-batch); clusters
#: that walk it below (window+stab)/TICK_BUDGET see a truncated window —
#: the documented §10 deviation.
TICK_BUDGET = 192


#: the episode program's outputs the host reads back after each batch (the
#: states stay on the device for the update program)
_RECORD_OUTS = ("lever", "bin", "rewards", "p99_ms", "clock_s", "load_s",
                "stab_s", "actions", "breach_frac", "shield_clamped",
                "shield_fallback", "budget_out")


#: padded bin-table ladder: §2.4.1 splits double a lever's bin count between
#: episode batches, which would change the packed-table shapes (and recompile
#: the episode program) every batch — tables are padded up this ladder
#: instead, so adaptation only recompiles on a ladder crossing. Indices are
#: clipped to ``n_valid`` so padded slots are unreachable.
_BIN_BUCKETS = (16, 32, 64, 128, 256, MAX_SPLIT_BINS)


def build_packed_tables(table: DeviceLeverTable,
                        pad_to: int = 0) -> list[tuple]:
    """Compile the service-model lever extractors (``_PACKERS``) into per-bin
    coefficient tables: entry ``tab[b]`` is the packed value of the source
    lever's bin b, so the device config -> ``cc`` arrays is one gather per
    packed key. Each packed key reads exactly one lever (the
    ``_LEVER_TO_PACKED`` contract), which is what makes this table-izable.
    ``pad_to`` edge-pads every table to one shape (see ``_BIN_BUCKETS``)."""
    out = []
    for lever, keys in _LEVER_TO_PACKED.items():
        li = table.index_of[lever]
        vals = [table.value_of(li, b) for b in range(int(table.n_valid[li]))]
        for key in keys:
            tab = np.array([_PACKERS[key]({lever: v}) for v in vals],
                           np.float32)
            if pad_to > len(tab):
                tab = np.pad(tab, (0, pad_to - len(tab)), mode="edge")
            out.append((key, li, tab))
    return out


def env_device_reason(env) -> Optional[str]:
    """The environment-level half of ``DeviceEpisodeRunner.supported`` —
    usable BEFORE a configurator exists, so launchers with
    ``--device-loop=on`` can fail fast instead of burning the offline
    collect budget first (the per-configurator half adds the reward-mode
    check)."""
    if getattr(env, "n_clusters", 0) < 1:
        return "serial TuningEnv (the fused loop is fleet-shaped)"
    if getattr(env, "backend", "numpy") not in ("jax", "pallas"):
        return (f"backend={getattr(env, 'backend', 'numpy')} "
                "(needs jax or pallas)")
    reason = device_workload_reason(env.workloads)
    if reason is not None:
        return f"workloads not device-packable ({reason})"
    return None


class DeviceEpisodeRunner:
    """Owns the fused episode program for one ``Configurator`` (lazy-built,
    cached per static shape bundle) and the host-side handoff around it."""

    def __init__(self, cfgr):
        self.cfgr = cfgr
        self.env = cfgr.env
        self._programs: dict = {}
        self._per_node = None          # device (N, nodes, M_sel) carry
        self._clock_mark: Optional[np.ndarray] = None
        self._config_idx = None        # device (N, n_levers) int carry
        self._table: Optional[DeviceLeverTable] = None
        self._bins_sig = None
        self._disc_sig = None          # oracle edge hash: re-pack skip
        self._hw_T = 0
        self._hw_B = 0
        self._wl_dev: Optional[dict] = None
        self._mc_arg: Optional[dict] = None
        self._ft_dev: Optional[dict] = None   # packed DeviceFaultTable (§12)
        self._delays = None                   # (N,) per-cluster deploy lag
        self._R_max = 0                       # static history depth
        self._hist = None                     # carried config-index history
        #: §16 safety-shield carry across batches: (lkg_idx (N, L) i32,
        #: radius (N,) i32, streak (N,) i32, risk (N,) f32); None until the
        #: first safe-mode batch packs it (or after a table re-index)
        self._shield = None
        self._idx0 = None                     # pre-batch indices (shield sync)
        #: double-buffer state: the not-yet-adopted device carry and the
        #: dispatched-but-not-materialised episode batches of this epoch
        self._carry = None
        self._inflight: list[dict] = []
        self._epoch_configs: Optional[list] = None
        self._epoch_t0 = 0.0
        self.last_wall_s = 0.0
        from repro.monitoring.metrics import ChaosCounters, ShieldCounters
        self.chaos = ChaosCounters()
        #: one counter object per configurator — the host-loop twin feeds
        #: the same instance, so serve/benchmark readers see one ledger
        self.shield = getattr(cfgr, "shield_counters", None) or ShieldCounters()
        self.mesh = self._resolve_mesh()

    def _resolve_mesh(self):
        """The cluster-sharding mesh (DESIGN.md §11): an explicit ``Mesh``
        from the configurator, or (``"auto"``) ``fleet_mesh()`` whenever the
        fleet size divides the visible device count. Resolved once per
        runner; an ``"auto"`` fallback to one device says why."""
        opt = getattr(self.cfgr, "mesh_opt", "auto")
        if opt in (None, "off"):
            return None
        from repro.distribution.sharding import fleet_mesh

        mesh = fleet_mesh() if opt == "auto" else opt
        if mesh is not None and self.env.n_clusters % mesh.size != 0:
            reason = (f"fleet N={self.env.n_clusters} does not divide the "
                      f"{mesh.size}-device mesh")
            if opt != "auto":
                raise ValueError(reason)
            print(f"[device-loop] cluster sharding (§11): off — {reason} "
                  "(single-device program)")
            mesh = None
        return mesh

    # ------------------------------------------------------------------ gates
    def supported(self) -> Optional[str]:
        """None when the fused loop can run; otherwise the reason for the
        per-step host-loop fallback."""
        reason = env_device_reason(self.env)
        if reason is not None:
            return reason
        if self.cfgr.reward_mode not in ("neg_mean", "neg_p99", "slo"):
            return f"reward_mode={self.cfgr.reward_mode} has no device statistic"
        return None

    # -------------------------------------------------------------- geometry
    def _tick_budget(self) -> tuple[int, int]:
        env, cfgr = self.env, self.cfgr
        packed = env.packed()
        T_b = packed["T_b"]
        need = int(np.max(np.round(cfgr.window_s / T_b)
                          + np.ceil(180.0 / T_b))) + 1
        from repro.engine.fleet_jax import _bucket
        if "batch_interval_s" in cfgr.levers:
            # the policy can walk the tick length mid-batch: CLAMP the scan
            # to TICK_BUDGET (clusters past it see truncated windows, §10)
            # instead of chasing ever-smaller T_b with ever-longer programs
            need = TICK_BUDGET
        T = max(_bucket(need), self._hw_T)
        self._hw_T = T
        E = _bucket(int(np.ceil(cfgr.window_s / 60.0)) + 1,
                    (1, 2, 4, 6, 8, 12, 16, 24, 32))
        return T, E

    # -------------------------------------------------------------- programs
    def _episode_fn(self, skey: tuple, consts: dict):
        """The raw traceable episode closure for one static bundle — shared
        by the per-update program (``_program`` jit/shard_map-wraps it) and
        the epoch mega-scan (``_epoch_program``, which composes the same
        body, one episode group per update, inside its K-update scan)."""
        (S, T, E, sel_cols, exploit, greedy, reward_mode, win_s,
         pallas, ndev, slo_sig, R_max, has_ft, shield) = skey
        from repro.engine.fleet_jax import (build_step_window,
                                            workload_rate_grid)

        env = self.env
        spec = env.spec
        slo_ms, hinge_w, breach_w = slo_sig if slo_sig else (0.0, 0.0, 0.0)
        step_window = build_step_window(env, sel_cols, T, E, pallas=pallas,
                                        slo_ms=slo_ms)
        nodes = env.n_nodes
        r, c = node_grid_shape(nodes)
        rc = r * c
        M_sel = len(sel_cols)
        cc_pairs = consts["cc_pairs"]            # [(key, lever_idx)] static
        ranked_g = consts["ranked_g"]            # (n_ranked,) global lever idx
        mesh = self.mesh if ndev else None
        ax = mesh.axis_names[0] if mesh is not None else None

        def program(params, key, config_idx, backlog, sfree, clock,
                    last_service, reconfigs, lo, hi, per_node, wl, f,
                    tabs, kind_code, n_valid, reboot_f, rejit_f, mc, emitF,
                    ft, delays, hist, *sh):
            TRACE_COUNTS[skey] = TRACE_COUNTS.get(skey, 0) + 1
            # decorrelate the per-shard RNG streams; the unsharded program
            # folds shard ordinal 0 so a 1-device mesh replays it exactly
            # (the shard_map-plumbing pin in tests/test_device_loop.py)
            key = jax.random.fold_in(
                key, jax.lax.axis_index(ax) if ax is not None else 0)
            N = config_idx.shape[0]
            rows = jnp.arange(N)
            ranked = jnp.asarray(ranked_g, jnp.int32)
            frac_den = jnp.maximum(n_valid[ranked].astype(jnp.float32) - 1.0,
                                   1.0)

            def step(carry, t):
                (config_idx, backlog, sfree, clock, last_service, reconfigs,
                 lo, hi, per_node) = carry[:9]
                pos = 10 if R_max else 9
                hist = carry[9] if R_max else None
                if shield:
                    lkg_idx, radius, streak, risk, budget_left = \
                        carry[pos:pos + 5]
                k = jax.random.fold_in(key, t)
                k_act, k_load, k_win = jax.random.split(k, 3)

                # ---- encode: fleet-batch running range + heat-map grids ----
                raw = jnp.transpose(per_node, (0, 2, 1))   # (N, M_sel, nodes)
                lo = jnp.minimum(lo, raw.min(axis=(0, 2)))
                hi = jnp.maximum(hi, raw.max(axis=(0, 2)))
                if ax is not None:   # fleet-global range across the shards
                    lo = jax.lax.pmin(lo, ax)
                    hi = jax.lax.pmax(hi, ax)
                span = jnp.where(hi > lo, hi - lo, 1.0)
                lo_eff = jnp.where(jnp.isfinite(lo), lo, 0.0)
                normed = jnp.clip(
                    jnp.nan_to_num((raw - lo_eff[None, :, None])
                                   / span[None, :, None]), 0.0, 1.0)
                grids = jnp.pad(normed, ((0, 0), (0, 0), (0, rc - nodes)))
                fracs = config_idx[:, ranked].astype(jnp.float32) / frac_den
                states = jnp.concatenate(
                    [grids.reshape(N, M_sel * rc), fracs],
                    axis=1).astype(jnp.float32)

                # ---- act (policy forward + f-gated sampling / argmax) ----
                if shield:
                    # §16 trust-region mask: reallocate probability mass to
                    # in-region moves BEFORE sampling (adds no RNG draws —
                    # the shield-off trace stays bitwise the pre-shield
                    # program); the hard clamp below is the guarantee. The
                    # counterfactual UNMASKED pick (same key, so no extra
                    # draws either) feeds the clamped_actions counter: a
                    # diversion is a step where the unshielded policy would
                    # have left the trust region
                    mask = self._table.shield_mask(
                        config_idx, lkg_idx, radius, ranked, xp=jnp,
                        n_valid=n_valid, kind_code=kind_code)
                    a_free = _sample_actions(params, states, k_act, f,
                                             exploit, greedy)
                    a = _sample_actions(params, states, k_act, f, exploit,
                                        greedy, mask=mask)
                    sh_diverted = ~jnp.take_along_axis(
                        mask, a_free[:, None], axis=1)[:, 0]
                else:
                    a = _sample_actions(params, states, k_act, f, exploit,
                                        greedy)
                direction = 1 - 2 * (a % 2).astype(jnp.int32)
                l_idx = ranked[a // 2]

                # ---- integerised lever apply: the ONE implementation the
                # host sweep uses and test_device_table pins, traced with
                # the device copies of the kind/validity arrays ----
                cur = config_idx[rows, l_idx]
                new_bin = self._table.step_index(
                    cur, l_idx, direction, xp=jnp, n_valid=n_valid,
                    kind_code=kind_code)
                if shield:
                    # hard trust-region clamp, then the risk/budget
                    # fallback: a cluster whose carried breach risk crossed
                    # the threshold (or whose episode budget is spent)
                    # deploys its whole LKG row instead of the sampled move
                    clamped = self._table.shield_clamp(
                        new_bin, lkg_idx[rows, l_idx], radius, l_idx,
                        xp=jnp, n_valid=n_valid, kind_code=kind_code)
                    sh_clamped = sh_diverted | (clamped != new_bin)
                    fallback = ((risk > jnp.float32(shield.risk_threshold))
                                | (budget_left <= 0))
                    stepped = config_idx.at[rows, l_idx].set(clamped)
                    config_idx = jnp.where(fallback[:, None], lkg_idx,
                                           stepped)
                    new_bin = config_idx[rows, l_idx]
                else:
                    config_idx = config_idx.at[rows, l_idx].set(new_bin)
                if R_max:
                    # §12 deploy latency: the engine runs the config each
                    # cluster requested `delays[i]` steps ago; the encoder
                    # above still shows the requested knobs
                    hist = jnp.roll(hist, 1, axis=0).at[0].set(config_idx)
                    eff_idx = jnp.take_along_axis(
                        hist, jnp.broadcast_to(delays[None, :, None],
                                               (1,) + config_idx.shape),
                        axis=0)[0]
                else:
                    eff_idx = config_idx
                cc = {kk: tabs[kk][eff_idx[:, li]] for kk, li in cc_pairs}

                # ---- loading (Kafka buffers arrivals, paper §4.2) ----
                rate_now, _ = workload_rate_grid(wl, clock)
                z = jax.random.normal(k_load, (N,))
                load_s = (10.0 + 60.0 * reboot_f[l_idx]
                          + 8.0 * rejit_f[l_idx]) \
                    * (1.0 + spec.noise * jnp.abs(z))
                backlog = backlog + rate_now * load_s
                clock = clock + load_s
                sfree = jnp.maximum(sfree - load_s, 0.0)
                reconfigs = reconfigs + 1.0

                # ---- stabilisation wait from the service-term delta (rates
                # re-evaluated at the post-load clock, like the host's
                # stabilisation_times after apply_configs) ----
                rate_st, size_st = workload_rate_grid(wl, clock)
                s_new = service_terms_arrays(cc, mc, spec, env.chips,
                                             rate_st, size_st,
                                             xp=jnp)["service"]
                prev = jnp.where(last_service < 0.0, s_new, last_service)
                rel = jnp.abs(s_new - prev) / jnp.maximum(prev, 1e-6)
                stab = jnp.clip(30.0 + 240.0 * rel, 30.0, 180.0)
                last_service = s_new

                # ---- fused preroll + observation window + reward ----
                (backlog, sfree, clock), stats = step_window(
                    k_win, backlog, sfree, clock, cc, wl, stab,
                    reconfigs, win_s, mc=mc, F=emitF,
                    ft=ft if has_ft else None)
                per_node = stats["per_node"]
                if reward_mode == "neg_p99":
                    reward = -stats["p99_ms"] / 1000.0
                elif reward_mode == "slo":
                    reward = (-stats["mean_ms"] / 1000.0
                              - hinge_w * jnp.maximum(
                                  stats["p99_ms"] - slo_ms, 0.0) / 1000.0
                              - breach_w * stats["breach_frac"])
                else:
                    reward = -stats["mean_ms"] / 1000.0

                out = {"states": states, "actions": a, "rewards": reward,
                       "p99_ms": stats["p99_ms"], "clock_s": clock,
                       "load_s": load_s, "stab_s": stab,
                       "lever": l_idx, "bin": new_bin}
                if slo_sig:
                    out["breach_frac"] = stats["breach_frac"]
                if shield:
                    (lkg_idx, radius, streak, risk, budget_left,
                     budget_out) = shield_update(
                        stats["breach_frac"], lkg_idx, config_idx, radius,
                        streak, risk, budget_left, shield, xp=jnp)
                    out["shield_clamped"] = sh_clamped
                    out["shield_fallback"] = fallback
                    out["budget_out"] = budget_out
                carry = (config_idx, backlog, sfree, clock, last_service,
                         reconfigs, lo, hi, per_node)
                if R_max:
                    carry = carry + (hist,)
                if shield:
                    carry = carry + (lkg_idx, radius, streak, risk,
                                     budget_left)
                return carry, out

            carry0 = (config_idx, backlog, sfree, clock, last_service,
                      reconfigs, lo, hi, per_node)
            if R_max:
                # fresh epoch (hist is None): the pre-episode config is what
                # is deployed at every history depth
                h0 = hist if hist is not None else jnp.broadcast_to(
                    config_idx[None], (R_max + 1,) + config_idx.shape)
                carry0 = carry0 + (h0,)
            if shield:
                # per-episode breach budget: fresh at every episode start
                # (chained passes and epoch updates alike), so the budget
                # leaf is scan-ephemeral and dropped from the carry below
                carry0 = carry0 + tuple(sh) + (
                    jnp.full((N,), shield.breach_budget, jnp.int32),)
            carry, outs = jax.lax.scan(step, carry0, jnp.arange(S))
            if shield:
                carry = carry[:-1]
            # (S, N) -> (N, S): the episode axis leads, ready for the update
            outs = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), outs)
            return carry, outs

        return program

    def _shard_wrap(self, fn, r_max: int, shield: bool = False):
        """Wrap an episode closure in the fleet ``shard_map`` — specs come
        from ``fleet_episode_specs``, the ONE definition shared with the
        epoch mega-scan (whose shard_map sits inside its scan body)."""
        from repro.distribution.sharding import fleet_episode_specs

        in_specs, out_specs = fleet_episode_specs(self.mesh, r_max, shield)
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _program(self, skey: tuple, consts: dict):
        if skey in self._programs:
            return self._programs[skey]
        program = self._episode_fn(skey, consts)
        ndev, R_max, shield = skey[9], skey[11], skey[13]
        # config_idx .. per_node (loop state) + the config-index history
        # (+ the shield state leaves, which chain batch-to-batch just like
        # the loop state and are re-fed from the returned carry)
        donate = tuple(range(2, 11)) + (22,)
        if shield:
            donate = donate + (23, 24, 25, 26)
        if ndev:
            program = self._shard_wrap(program, R_max, bool(shield))
        prog = jax.jit(program, donate_argnums=donate)
        self._programs[skey] = prog
        return prog

    def _epoch_program(self, ekey: tuple, consts: dict):
        """ONE jitted program for a whole epoch (DESIGN.md §15): a
        ``lax.scan`` over K outer Algorithm-1 iterations whose body runs
        ``passes`` chained episode groups through the SAME traced episode
        closure the per-update program compiles, then composes the agent's
        un-jitted ``_update_step`` — policy params, optimizer state, RNG
        offset, fleet loop state, the deploy-history ring and the
        (lever, bin) count tensor all carry device-to-device; nothing
        touches the host inside the epoch. Per-(update, pass) RNG keys fold
        ``draws0 + k·passes + p``, bitwise the sequential schedule's
        ``_next_key`` stream."""
        if ekey in self._programs:
            return self._programs[ekey]
        _, skey, K, passes, rec_mode = ekey
        ndev, slo_sig, R_max = skey[9], skey[10], skey[11]
        shield = skey[13]
        episode = self._episode_fn(skey, consts)
        if ndev:
            # shard_map wraps the episode body INSIDE the scan; the update
            # math stays plain (GSPMD), exactly like the sequential split
            episode = self._shard_wrap(episode, R_max, bool(shield))
        upd = self.cfgr.agent._update_step
        slo_ms = float(self.cfgr.slo_ms)

        def epoch(params, opt_state, key, draws0, loop, hist, counts,
                  wl, f, tabs, kind_code, n_valid, reboot_f, rejit_f,
                  mc, emitF, ft, delays, sh):
            TRACE_COUNTS[ekey] = TRACE_COUNTS.get(ekey, 0) + 1

            def body(carry, k):
                params, opt_state, loop, hist, counts, sh = carry
                groups = []
                for p in range(passes):
                    kk = jax.random.fold_in(
                        key, draws0 + jnp.uint32(k * passes + p))
                    ep_carry, outs = episode(
                        params, kk, *loop, wl, f, tabs, kind_code,
                        n_valid, reboot_f, rejit_f, mc, emitF, ft,
                        delays, hist, *(sh if shield else ()))
                    loop = tuple(ep_carry[:9])
                    hist = ep_carry[9] if R_max else None
                    sh = tuple(ep_carry[-4:]) if shield else None
                    groups.append(outs)
                if len(groups) == 1:
                    b = groups[0]
                else:
                    b = {k2: jnp.concatenate([g[k2] for g in groups],
                                             axis=0)
                         for k2 in groups[0]}
                if counts is not None:
                    counts = counts.at[b["lever"].ravel(),
                                       b["bin"].ravel()].add(1)
                mask = jnp.ones(b["actions"].shape, jnp.float32)
                params, opt_state, loss, first = upd(
                    params, opt_state, b["states"],
                    b["actions"].astype(jnp.int32), b["rewards"], mask)
                y = {"pg_loss": loss, "mean_return": first}
                if rec_mode == "full":
                    y.update({k2: v for k2, v in b.items()
                              if k2 != "states"})
                else:
                    y["reward_sum"] = b["rewards"].sum()
                    y["p99_max"] = b["p99_ms"].max()
                    if slo_sig:
                        y["breach_windows"] = \
                            (b["breach_frac"] > 0.0).sum()
                        y["breach_frac_sum"] = b["breach_frac"].sum()
                    elif slo_ms > 0.0:
                        y["breach_windows"] = (b["p99_ms"] > slo_ms).sum()
                    if shield:
                        y["shield_clamped"] = b["shield_clamped"].sum()
                        y["shield_fallbacks"] = b["shield_fallback"].sum()
                        y["budget_exhaustions"] = \
                            b["budget_out"].any(axis=1).sum()
                    if rec_mode == "summary":
                        y["reward_mean"] = b["rewards"].mean(axis=1)
                        y["p99_mean"] = b["p99_ms"].mean(axis=1)
                        y["p99_last"] = b["p99_ms"][:, -1]
                return (params, opt_state, loop, hist, counts, sh), y

            carry = (params, opt_state, loop, hist, counts, sh)
            carry, ys = jax.lax.scan(body, carry, jnp.arange(K))
            return carry, ys

        donate = (0, 1, 4, 5, 6) + ((18,) if shield else ())
        prog = jax.jit(epoch, donate_argnums=donate)
        self._programs[ekey] = prog
        return prog

    # ------------------------------------------------------------------- run
    def run(self, *, explore: bool = True, greedy: bool = False):
        """One fused episode batch, synchronously. Returns ``(batch,
        records)`` where ``batch`` holds the device-resident (N, S)
        states/actions/rewards for ``ReinforceAgent.update_batch`` and
        ``records`` are the host-materialised ``StepRecord``s
        (cluster-major, matching the per-step host loop's ordering)."""
        batch = self.run_async(explore=explore, greedy=greedy)
        return batch, self.finalize()

    def run_cycle(self, *, passes: int = 1):
        """One serve-loop shadow cycle (DESIGN.md §13) — exactly one outer
        Algorithm-1 iteration as the SAME ≤2 jitted programs the batch
        tuner compiles (§10/§11): ``passes`` chained episode programs plus
        one update program, double-buffered so the host's record
        materialisation and bin replay overlap the in-flight update.
        Returns ``(stats, records)``. An always-on loop calling
        this per cycle never retraces (the no-retrace pin in
        tests/test_serve.py watches ``TRACE_COUNTS`` across cycles)."""
        b = self._dispatch_group(passes)
        pending = self.cfgr.agent.update_batch_async(
            b["states"], b["actions"], b["rewards"])
        records = self.finalize()   # host work, device update in flight
        with span("tune.wait"):
            stats = pending()
        return stats, records

    def _dispatch_group(self, passes: int) -> dict:
        """Dispatch one update's worth of chained episode batches and stack
        them along the episode axis, still on device."""
        batches = [self.run_async() for _ in range(max(1, passes))]
        if len(batches) == 1:
            return batches[0]
        return {k: jnp.concatenate([x[k] for x in batches], axis=0)
                for k in batches[0]}

    def run_pipelined(self, updates: int, *, passes: int = 1,
                      depth: int = 2):
        """``updates`` outer iterations as a depth-``depth`` pipelined
        actor/learner (DESIGN.md §14): the jitted update program for batch k
        is enqueued while batch k+1's episode scan explores.

        The pipeline is pure dispatch-order scheduling on the device queue —
        ``run_async`` reads ``agent.params`` at dispatch time and
        ``update_batch_async`` rebinds them to the update's not-yet-ready
        device outputs, so dispatching episode group k+1 BEFORE update k
        hands update k-1's params straight to it device-to-device: episodes
        run (depth-1)-updates stale (IMPALA-style), returns hand off
        device-to-device, and no host round-trip sits on the critical path
        (the single deferred ``finalize`` materialises every batch's records
        and replays §2.4.1 bins once per pipelined epoch, not per update —
        binning is frozen across it, exactly like chained passes within one
        update).

        ``depth=1`` IS the sequential schedule: it delegates to
        ``run_cycle`` per update and is pinned bitwise-equal to it
        (tests/test_pallas_compiled.py). Returns ``(stats_list,
        records)``."""
        if updates <= 0:
            return [], []
        if depth <= 1:
            out, recs = [], []
            for _ in range(updates):
                stats, records = self.run_cycle(passes=passes)
                out.append(stats)
                recs.extend(records)
            return out, recs
        agent = self.cfgr.agent
        ahead = depth - 1
        groups: list = []
        thunks: list = []
        nxt = 0
        for k in range(updates):
            # keep `ahead` episode groups dispatched past the current update
            while nxt <= min(k + ahead, updates - 1):
                groups.append(self._dispatch_group(passes))
                nxt += 1
            b = groups[k]
            thunks.append(agent.update_batch_async(
                b["states"], b["actions"], b["rewards"]))
            groups[k] = None          # drop the host ref once enqueued
        records = self.finalize()     # blocks on the tail episode batch
        with span("tune.wait"):
            stats_list = [t() for t in thunks]
        return stats_list, records

    # ---------------------------------------------------------- epoch (§15)
    def run_epoch(self, k: int, *, passes: int = 1,
                  records: str = "full", explore: bool = True):
        """``k`` full outer Algorithm-1 iterations — episode batch → reward
        → policy update — as ONE jitted device program per warm-up segment
        (DESIGN.md §15): zero host round-trips inside an epoch.

        Inside the epoch the ``DeviceLeverTable`` is FROZEN; §2.4.1 bin
        adaptation defers to the epoch boundary, where it replays in one
        host pass (and the next epoch re-packs the table only if the replay
        changed a bin edge — see ``_fresh_inputs``). ``records`` controls
        the host materialisation: ``"full"`` pulls the per-step tensors and
        emits the sequential path's exact ``StepRecord`` stream;
        ``"summary"`` pulls a (K, N·passes) reward/p99 summary (convergence
        curves, no records); ``"off"`` pulls per-update loss scalars only.

        An epoch crossing the agent's exploit warm-up boundary splits into
        two program calls (the exploit gate is a static of the episode
        trace) — still O(1) dispatches, never O(K). Returns
        ``(stats_list, records)``; ``records`` is ``[]`` unless
        ``records="full"``."""
        if k <= 0:
            return [], []
        if records not in ("full", "summary", "off"):
            raise ValueError(f"records={records!r} (full|summary|off)")
        if self._inflight or self._carry is not None:
            raise RuntimeError("run_epoch with episode batches in flight")
        cfgr, env = self.cfgr, self.env
        agent, dev = cfgr.agent, env._dev
        N, S = env.n_clusters, cfgr.steps_per_episode
        if explore:
            w = min(max(agent.f_warmup_updates - agent.n_updates, 0), k)
            segments = [(kk, ex) for kk, ex in ((w, False), (k - w, True))
                        if kk > 0]
        else:
            segments = [(k, False)]
        greedy = not explore

        loop = self._fresh_inputs()
        sh_spec = getattr(cfgr, "shield", None)
        sh = self._shield if sh_spec is not None else None
        # shield runs ALSO need the pre-epoch indices in "full" mode: a
        # fallback step reverts a whole row to LKG, which the per-lever
        # record stream can't express — final configs re-sync from indices
        idx0 = (None if records == "full" and sh_spec is None
                else np.asarray(loop[0]))
        hist = self._hist
        if self._R_max and hist is None:
            # materialise the deploy-history ring host-side: the scan carry
            # needs a concrete leaf (the sequential program builds the same
            # broadcast in-trace from its donated config_idx)
            hist = jnp.broadcast_to(
                loop[0][None], (self._R_max + 1,) + loop[0].shape) + 0
        counts = None
        if records != "full":
            counts = jnp.zeros((len(self._table.specs), self._hw_B),
                               jnp.int32)
        T, E = self._tick_budget()
        pallas = bool(getattr(dev, "pallas", False))
        slo_sig = ((float(cfgr.slo_ms), float(cfgr.slo_hinge_w),
                    float(cfgr.slo_breach_w))
                   if cfgr.reward_mode == "slo" else None)
        consts = {"cc_pairs": self._cc_pairs, "ranked_g": self._ranked_g}

        params, opt_state = agent.params, agent.opt_state
        key, draws0 = dev._key, dev._draws
        ys_segs: list = []
        self._epoch_t0 = time.perf_counter()
        for k_seg, exploit in segments:
            skey = (S, T, E, self._sel_cols, exploit, greedy,
                    cfgr.reward_mode, float(cfgr.window_s), pallas,
                    self.mesh.size if self.mesh is not None else 0,
                    slo_sig, self._R_max, self._ft_dev is not None,
                    sh_spec)
            prog = self._epoch_program(
                ("epoch", skey, k_seg, passes, records), consts)
            EPOCH_DISPATCHES[0] += 1
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers")
                (params, opt_state, loop, hist, counts, sh), ys = prog(
                    params, opt_state, key, jnp.uint32(draws0), loop,
                    hist, counts, self._wl_dev, jnp.float32(agent.f),
                    self._tabs, self._kind_code, self._n_valid,
                    self._reboot_f, self._rejit_f, self._mc_arg,
                    self._emitF, self._ft_dev, self._delays, sh)
            draws0 += k_seg * passes
            ys_segs.append((k_seg, ys))
        jax.block_until_ready((params, loop))
        self.last_wall_s = time.perf_counter() - self._epoch_t0
        dev._draws = draws0
        agent.adopt_update(params, opt_state, k)
        self.chaos.add_wall(self.last_wall_s)

        # ---- adopt the final loop state (the finalize() contract) ----
        (config_idx_f, backlog_f, sfree_f, clock_f, last_service_f,
         reconfigs_f, lo_f, hi_f, per_node_f) = loop
        self._hist = hist
        if sh_spec is not None:
            self._shield = tuple(sh)
            self.shield.trust_radius = float(np.asarray(sh[1]).mean())
        env._dev.adopt_loop_state(backlog_f, sfree_f, clock_f)
        env.reconfigs[:] = np.asarray(reconfigs_f, np.int64)
        env.last_service[:] = np.asarray(last_service_f, np.float64)
        rng_range = cfgr.encoder._range
        rng_range.lo = np.asarray(lo_f, np.float64)
        rng_range.hi = np.asarray(hi_f, np.float64)
        self._per_node = per_node_f
        self._config_idx = config_idx_f
        self._clock_mark = env.clock.copy()

        if records == "full":
            stats_list, recs = self._epoch_full(ys_segs, N, S, passes)
            if sh_spec is not None:
                touched = np.zeros((N, self._table.n_levers), bool)
                rows = np.arange(N)[:, None]
                for k_seg, ys in ys_segs:
                    lv = np.asarray(ys["lever"]).reshape(k_seg * passes, N, S)
                    for chunk in lv:
                        touched[rows, chunk] = True
                self._sync_configs(idx0, np.asarray(config_idx_f), touched)
        else:
            stats_list = self._epoch_summary(ys_segs, counts, idx0,
                                             config_idx_f, N, S, passes)
            recs = []
        cfgr._last_fleet_windows = None   # host-loop cache is stale now
        return stats_list, recs

    def _epoch_full(self, ys_segs, N, S, passes):
        """Materialise a ``records="full"`` epoch by replaying
        ``_materialise`` per (update, pass) chunk — record order, §2.4.1
        replay order and chaos accounting match the sequential schedule
        exactly."""
        env = self.env
        configs = self._epoch_configs
        stats_list: list = []
        recs: list = []
        for k_seg, ys in ys_segs:
            ys = {k2: np.asarray(v) for k2, v in ys.items()}
            for i in range(k_seg):
                for p in range(passes):
                    sl = slice(p * N, (p + 1) * N)
                    outs = {k2: v[i, sl] for k2, v in ys.items()
                            if k2 not in ("pg_loss", "mean_return")}
                    configs = self._materialise(
                        {"outs": outs, "S": S}, configs, recs,
                        i * passes + p)
                stats_list.append(
                    {"pg_loss": float(ys["pg_loss"][i]),
                     "mean_return": float(ys["mean_return"][i]),
                     "episodes": N * passes, "steps": N * passes * S})
        env.configs = configs
        env.invalidate()
        return stats_list, recs

    def _epoch_summary(self, ys_segs, counts, idx0, config_idx_f,
                       N, S, passes):
        """Host pass for ``records="summary"|"off"``: fold the per-update
        scalars into ``ChaosCounters``, replay the device-side (lever, bin)
        count tensor into the adaptive oracle in ONE pass, and rebuild
        ``env.configs`` from the final integerised indices (levers still at
        their initial index keep their original dict value).

        The count tensor compresses away the assignment ORDER the §2.4.1
        streak rules watch, so the replay reconstructs the maximum-entropy
        order consistent with the counts: each bin's occurrences spread
        evenly across the epoch. A same-bin streak then survives only when
        one bin truly dominated the epoch's choices — a sorted
        ``np.repeat`` replay would instead fabricate a run per bin and
        fire spurious splits (halving ``_hits`` each time)."""
        cfgr, env, table = self.cfgr, self.env, self._table
        stats_list: list = []
        for k_seg, ys in ys_segs:
            ys = {k2: np.asarray(v) for k2, v in ys.items()}
            self.chaos.windows += k_seg * passes * N * S
            self.chaos.reward_sum += float(ys["reward_sum"].sum())
            self.chaos.p99_max_ms = max(self.chaos.p99_max_ms,
                                        float(ys["p99_max"].max()))
            if "breach_windows" in ys:
                self.chaos.breached_windows += int(
                    ys["breach_windows"].sum())
            if "breach_frac_sum" in ys:
                self.chaos.breach_frac_sum += float(
                    ys["breach_frac_sum"].sum())
            if "shield_clamped" in ys:
                self.shield.clamped_actions += int(
                    ys["shield_clamped"].sum())
                self.shield.fallbacks += int(ys["shield_fallbacks"].sum())
                self.shield.budget_exhaustions += int(
                    ys["budget_exhaustions"].sum())
            for i in range(k_seg):
                st = {"pg_loss": float(ys["pg_loss"][i]),
                      "mean_return": float(ys["mean_return"][i]),
                      "episodes": N * passes, "steps": N * passes * S}
                if "reward_mean" in ys:
                    st["reward_mean"] = float(ys["reward_mean"][i].mean())
                    st["p99_mean_ms"] = float(ys["p99_mean"][i].mean())
                    st["p99_ms"] = float(ys["p99_last"][i][-1])
                stats_list.append(st)
        # ---- one-pass §2.4.1 replay from the device count tensor ----
        bins = cfgr.disc.bins
        counts_np = np.asarray(counts)
        names = table.names
        for li in np.nonzero(counts_np.any(axis=1))[0]:
            dyn = bins.get(names[li])
            if dyn is not None:
                c = counts_np[li]
                reps = np.repeat(np.arange(c.size), c)
                pos = np.concatenate([(np.arange(ci) + 0.5) / ci
                                      for ci in c if ci])
                dyn.record_many(reps[np.argsort(pos, kind="stable")])
        # ---- final configs from the integerised indices ----
        idx_f = np.asarray(config_idx_f)
        configs = [dict(c) for c in self._epoch_configs]
        val_cache: dict = {}
        for ci, li in zip(*np.nonzero(idx_f != idx0)):
            kv = (int(li), int(idx_f[ci, li]))
            val = val_cache.get(kv)
            if val is None:
                val = val_cache[kv] = table.value_of(*kv)
            configs[ci][names[li]] = val
        env.configs = configs
        env.invalidate()
        return stats_list

    def run_async(self, *, explore: bool = True, greedy: bool = False):
        """Dispatch one fused episode batch WITHOUT blocking on it and
        return the device-resident (N, S) batch. Consecutive calls before
        ``finalize`` chain on the device-carried loop state (no host
        round-trip between passes); ``finalize`` adopts the final state and
        materialises every pending batch's host bookkeeping."""
        cfgr, env = self.cfgr, self.env
        dev = env._dev
        N = env.n_clusters
        S = cfgr.steps_per_episode

        sh_spec = getattr(cfgr, "shield", None)
        if self._carry is None:
            args = self._fresh_inputs()
            hist = self._hist          # survives epochs while configs do
            sh = tuple(self._shield) if sh_spec is not None else ()
            if sh_spec is not None:
                # pre-batch indices: a shield fallback reverts whole rows
                # to LKG, so finalize re-syncs configs from index diffs
                self._idx0 = np.asarray(args[0])
            self._epoch_t0 = time.perf_counter()
        else:
            # chained pass: everything per-cluster continues from the carry;
            # tables/workloads are the epoch's (binning frozen until the
            # finalize replay — the §11 double-buffer contract)
            args = tuple(self._carry[:9])
            pos = 9
            hist = None
            if self._R_max:
                hist = self._carry[9]
                pos = 10
            sh = (tuple(self._carry[pos:pos + 4])
                  if sh_spec is not None else ())

        with span("tune.dispatch.episode") as sp, \
                warnings.catch_warnings():
            T, E = self._tick_budget()
            exploit = cfgr.agent.exploit_ready(explore=explore)
            greedy = bool(greedy or not explore)
            pallas = bool(getattr(dev, "pallas", False))
            slo_sig = ((float(cfgr.slo_ms), float(cfgr.slo_hinge_w),
                        float(cfgr.slo_breach_w))
                       if cfgr.reward_mode == "slo" else None)
            skey = (S, T, E, self._sel_cols, exploit, greedy,
                    cfgr.reward_mode, float(cfgr.window_s), pallas,
                    self.mesh.size if self.mesh is not None else 0,
                    slo_sig, self._R_max, self._ft_dev is not None, sh_spec)
            prog = self._program(skey, {"cc_pairs": self._cc_pairs,
                                        "ranked_g": self._ranked_g})

            traces0 = sum(TRACE_COUNTS.values())
            # fresh-epoch inputs arrive host-committed; their donation only
            # becomes effective once the carried buffers chain device-side
            warnings.filterwarnings("ignore", message="Some donated buffers")
            carry, outs = prog(
                cfgr.agent.params, dev._next_key(), *args,
                self._wl_dev, jnp.float32(cfgr.agent.f), self._tabs,
                self._kind_code, self._n_valid, self._reboot_f,
                self._rejit_f, self._mc_arg, self._emitF,
                self._ft_dev, self._delays, hist, *sh)
            traced = sum(TRACE_COUNTS.values()) - traces0
            sp.set_metadata(traced=traced)
        count("tune.programs_traced", traced)
        self._carry = carry
        self._inflight.append({"outs": outs, "S": S})
        return {"states": outs["states"], "actions": outs["actions"],
                "rewards": outs["rewards"]}

    def _fresh_inputs(self) -> tuple:
        """Host-side packing for the first batch of an epoch: re-pack the
        integerised lever table from the (possibly adapted) oracle, pack the
        workload table, borrow the engine's queueing state."""
        with span("tune.pack"):
            return self._pack_inputs()

    def _pack_inputs(self) -> tuple:
        cfgr, env = self.cfgr, self.env
        dev = env._dev

        # re-pack the integerised table from the (possibly adapted) oracle,
        # padded up the bin ladder so between-batch splits keep the shapes
        # (and the compiled program) stable — UNLESS the last §2.4.1 replay
        # changed no bin edge (exact edge-array hash): steady-state batches
        # then skip the whole O(N·109) rebuild and reuse the device tables
        disc_sig = tuple(d._edges.tobytes()
                         for d in cfgr.disc.bins.values())
        repack = self._table is None or disc_sig != self._disc_sig
        self._disc_sig = disc_sig
        if repack:
            count("tune.repacks")
            with span("tune.pack.repack"):
                table = DeviceLeverTable.from_discretiser(cfgr.disc)
                self._table = table
                from repro.engine.fleet_jax import _bucket
                B_pad = max(_bucket(table.max_bins, _BIN_BUCKETS),
                            self._hw_B)
                self._hw_B = B_pad
                packed_tabs = build_packed_tables(table, pad_to=B_pad)
                self._cc_pairs = tuple((k, li) for k, li, _ in packed_tabs)
                self._tabs = {k: jnp.asarray(tab)
                              for k, li, tab in packed_tabs}
                self._kind_code = jnp.asarray(table.kind_code)
                self._n_valid = jnp.asarray(table.n_valid)
                self._reboot_f = jnp.asarray(
                    [1.0 if s.reboot else 0.0 for s in table.specs],
                    jnp.float32)
                self._rejit_f = jnp.asarray(
                    [1.0 if s.group in ("kernel", "memory", "parallel")
                     else 0.0 for s in table.specs], jnp.float32)
                self._ranked_g = tuple(table.index_of[n]
                                       for n in cfgr.levers)
        table = self._table
        if self._wl_dev is None:
            tbl = pack_device_workloads(env.workloads)
            self._wl_dev = {k: jnp.asarray(v)
                            for k, v in tbl.asdict().items()}
            # §12 fault table: tick effects ride the window program; deploy
            # lags drive the config-index history ring
            ftab = getattr(env, "_faults", None)
            self._R_max = 0 if ftab is None else int(ftab.max_deploy_delay())
            self.chaos.fault_events = (0 if ftab is None
                                       else int((ftab.kind != 0).sum()))
            if ftab is not None and ftab.has_tick_effects():
                self._ft_dev = {k: jnp.asarray(v)
                                for k, v in ftab.asdict().items()}
            if self._R_max:
                self._delays = jnp.asarray(
                    np.clip(ftab.deploy_delays(), 0, self._R_max))
        configs = env.current_configs()
        self._epoch_configs = configs
        # re-indexing N configs through 109 levers costs ~0.1 s at N=1024;
        # between consecutive fused batches the configs are exactly what the
        # previous batch wrote, so reuse its final index array unless the
        # binning adapted (exact edge-array signature — counts or summary
        # stats could alias after net-zero split+merge sequences) or someone
        # else stepped the env (clock)
        sig = tuple(e.tobytes() if e is not None else b""
                    for e in table._edges)
        if (self._config_idx is not None and sig == self._bins_sig
                and self._clock_mark is not None
                and np.array_equal(self._clock_mark, env.clock)):
            config_idx = self._config_idx
        else:
            count("tune.index_rebuilds")
            with span("tune.pack.index"):
                config_idx = jnp.asarray(table.index_configs(configs))
            self._hist = None   # stale config history can't be replayed
            self._shield = None  # LKG indices refer to the old ladder
        self._bins_sig = sig
        sh_spec = getattr(cfgr, "shield", None)
        if sh_spec is not None and self._shield is None:
            # fresh shield state: LKG = the current (pre-exploration)
            # config, full initial trust radius, clean streak/risk. The
            # `+ 0` copy keeps the LKG buffer distinct from the donated
            # config_idx argument.
            n = config_idx.shape[0]
            self._shield = (config_idx + 0,
                            jnp.full((n,), sh_spec.trust_radius, jnp.int32),
                            jnp.zeros((n,), jnp.int32),
                            jnp.zeros((n,), jnp.float32))

        self._sel_cols = tuple(env.metric_names.index(m)
                               for m in cfgr.hspec.metric_names)
        # per-cluster emission factors for the selected columns — a program
        # ARG (not a closure) so the mesh path can shard its cluster axis
        self._emitF = jnp.asarray(
            env._emit_factor[:, :, np.asarray(self._sel_cols)], jnp.float32)
        if self.mesh is not None:
            # pre-place the static inputs in their program shardings so the
            # per-dispatch path never re-broadcasts them (engine-owned model
            # constants get a sharded shadow copy, made once)
            from jax.sharding import NamedSharding

            from repro.distribution.sharding import fleet_sharding

            rep = NamedSharding(self.mesh, P())
            shd = fleet_sharding(self.mesh)
            if repack:
                self._tabs = jax.device_put(self._tabs, rep)
                self._kind_code = jax.device_put(self._kind_code, rep)
                self._n_valid = jax.device_put(self._n_valid, rep)
                self._reboot_f = jax.device_put(self._reboot_f, rep)
                self._rejit_f = jax.device_put(self._rejit_f, rep)
            self._wl_dev = jax.device_put(self._wl_dev, shd)
            self._emitF = jax.device_put(self._emitF, shd)
            if self._ft_dev is not None:
                self._ft_dev = jax.device_put(self._ft_dev, shd)
            if self._delays is not None:
                self._delays = jax.device_put(self._delays, shd)
            if self._shield is not None:
                self._shield = tuple(jax.device_put(x, shd)
                                     for x in self._shield)
            if self._mc_arg is None:
                self._mc_arg = jax.device_put(dev._mc_dev, shd)
        else:
            self._mc_arg = dev._mc_dev
        # carried per-node metrics: reuse the previous batch's final window
        # unless someone stepped the env in between (clock moved)
        if (self._per_node is None or self._clock_mark is None
                or not np.array_equal(self._clock_mark, env.clock)):
            stats = env.observe_stats(cfgr.window_s)
            self._per_node = jnp.asarray(
                np.asarray(stats["per_node"])[:, :, np.asarray(self._sel_cols)])
        per_node = self._per_node

        backlog, sfree, clock = dev.loop_state()
        last_service = np.where(np.isnan(env.last_service), -1.0,
                                env.last_service)
        rng_range = cfgr.encoder._range
        return (config_idx, backlog, sfree, clock,
                jnp.asarray(last_service, jnp.float32),
                jnp.asarray(env.reconfigs, jnp.float32),
                jnp.asarray(rng_range.lo, jnp.float32),
                jnp.asarray(rng_range.hi, jnp.float32), per_node)

    # -------------------------------------------------------------- finalize
    def finalize(self) -> list:
        """Block on the epoch's dispatched batches, hand the queueing state
        back to the engine, materialise every batch's ``StepRecord``s and
        replay the chosen bins into the adaptive oracle (§2.4.1, batch
        order). Returns the records, cluster-major per batch."""
        if not self._inflight:
            return []
        cfgr, env = self.cfgr, self.env
        inflight, self._inflight = self._inflight, []
        carry, self._carry = self._carry, None
        with span("tune.wait"):
            jax.block_until_ready(inflight[-1]["outs"])
        self.last_wall_s = time.perf_counter() - self._epoch_t0
        self.chaos.add_wall(self.last_wall_s)

        # ---- hand the queueing state back to the engine -------------------
        (config_idx_f, backlog_f, sfree_f, clock_f, last_service_f,
         reconfigs_f, lo_f, hi_f, per_node_f) = carry[:9]
        pos = 9
        self._hist = None
        if self._R_max:
            self._hist = carry[9]
            pos = 10
        sh_spec = getattr(cfgr, "shield", None)
        pulled = [clock_f, reconfigs_f, last_service_f, lo_f, hi_f]
        with span("tune.pull") as sp:
            if sh_spec is not None:
                self._shield = tuple(carry[pos:pos + 4])
                self.shield.trust_radius = float(
                    np.asarray(self._shield[1]).mean())
                pulled.append(self._shield[1])
            env._dev.adopt_loop_state(backlog_f, sfree_f, clock_f)
            env.reconfigs[:] = np.asarray(reconfigs_f, np.int64)
            env.last_service[:] = np.asarray(last_service_f, np.float64)
            rng_range = cfgr.encoder._range
            rng_range.lo = np.asarray(lo_f, np.float64)
            rng_range.hi = np.asarray(hi_f, np.float64)
            nbytes = sum(int(x.nbytes) for x in pulled)
            sp.set_metadata(bytes=nbytes)
        count("tune.pull_bytes", nbytes)
        self._per_node = per_node_f
        self._config_idx = config_idx_f
        self._clock_mark = env.clock.copy()

        configs = self._epoch_configs
        records: list = []
        for k, entry in enumerate(inflight):
            configs = self._materialise(entry, configs, records, k)
        env.configs = configs
        env.invalidate()
        if sh_spec is not None:
            N = env.n_clusters
            touched = np.zeros((N, self._table.n_levers), bool)
            rows = np.arange(N)[:, None]
            for entry in inflight:
                touched[rows, np.asarray(entry["outs"]["lever"])] = True
            self._sync_configs(self._idx0, np.asarray(config_idx_f),
                               touched)
        cfgr._last_fleet_windows = None   # host-loop cache is stale now
        return records

    def _sync_configs(self, idx0: np.ndarray, idx_f: np.ndarray,
                      touched: np.ndarray | None = None) -> None:
        """Exact final config dicts under the shield: a fallback step
        reverts a cluster's WHOLE row to LKG, which the per-lever
        ``StepRecord`` stream cannot express (a record's config dict shows
        the recorded lever only on such steps). The authoritative final
        state is the device index array — rebuild ``env.configs`` from its
        diff against the pre-batch indices, the ``_epoch_summary`` decode.

        ``touched`` (N, L bool) marks levers the batch's action stream
        visited: those are re-decoded even when they returned to their
        initial bin (idx_f == idx0), because the record path decodes every
        visited bin and a neutral shield must replay shield-off configs
        bit for bit — the stored default value of an untouched lever need
        not be a bin-decoded value."""
        table = self._table
        names = table.names
        configs = [dict(c) for c in self._epoch_configs]
        stale = idx_f != idx0
        if touched is not None:
            stale = stale | touched
        val_cache: dict = {}
        for ci, li in zip(*np.nonzero(stale)):
            kv = (int(li), int(idx_f[ci, li]))
            val = val_cache.get(kv)
            if val is None:
                val = val_cache[kv] = table.value_of(*kv)
            configs[ci][names[li]] = val
        self.env.configs = configs
        self.env.invalidate()

    def _materialise(self, entry: dict, configs: list, records: list,
                     batch: int) -> list:
        """StepRecords + §2.4.1 bin replay for ONE batch (``batch``: its
        place among the batches materialised together, which tags the
        spans); returns the batch's final config dicts (the next chained
        batch starts there)."""
        env, table = self.env, self._table
        outs, S = entry["outs"], entry["S"]
        N = env.n_clusters
        # bulk device->host pulls of what the host reads, all up front
        with span("tune.pull", batch=batch) as sp:
            o = {k: np.asarray(outs[k]) for k in _RECORD_OUTS if k in outs}
            nbytes = sum(a.nbytes for a in o.values())
            sp.set_metadata(bytes=nbytes)
        count("tune.pull_bytes", nbytes)
        from repro.core.configurator import StepRecord

        with span("tune.records", batch=batch, records=N * S):
            lever, new_bin = o["lever"], o["bin"]            # (N, S)
            self.chaos.record_batch(o["rewards"], o["p99_ms"],
                                    o.get("breach_frac"),
                                    slo_ms=self.cfgr.slo_ms)
            if "shield_fallback" in o:
                self.shield.clamped_actions += int(o["shield_clamped"].sum())
                self.shield.fallbacks += int(o["shield_fallback"].sum())
                # one exhaustion per (cluster, episode) whose budget ran dry
                self.shield.budget_exhaustions += int(
                    o["budget_out"].any(axis=1).sum())
            # C-speed list conversion: the record loop below touches every
            # element once and python-float access via tolist() is ~5x
            # cheaper than per-element np scalar indexing
            lever_l, bin_l = lever.tolist(), new_bin.tolist()
            rewards = o["rewards"].tolist()
            p99 = o["p99_ms"].tolist()
            clock_s = o["clock_s"].tolist()
            load_s = o["load_s"].tolist()
            stab_s = o["stab_s"].tolist()
            directions = (1 - 2 * (o["actions"] % 2)).tolist()

            # the action set only reaches a few levers × bins: memoise the
            # decode instead of 5k+ value_of calls per batch
            val_cache: dict = {}
            names = table.names
            final_configs = []
            for i in range(N):
                cfg = configs[i]
                lv_i, bn_i, dir_i = lever_l[i], bin_l[i], directions[i]
                rw_i, p_i, ck_i = rewards[i], p99[i], clock_s[i]
                ld_i, st_i = load_s[i], stab_s[i]
                for t in range(S):
                    li, b = lv_i[t], bn_i[t]
                    val = val_cache.get((li, b))
                    if val is None:
                        val = val_cache[(li, b)] = table.value_of(li, b)
                    cfg = dict(cfg)
                    cfg[names[li]] = val
                    records.append(StepRecord(
                        lever=names[li], direction=dir_i[t],
                        config=cfg, reward=rw_i[t],
                        p99_ms=p_i[t], clock_s=ck_i[t],
                        phases={"loading_s": ld_i[t],
                                "stabilisation_s": st_i[t]}))
                final_configs.append(dict(cfg))
        count("tune.records_built", N * S)

        # ---- replay the chosen bins into the adaptive oracle ---------------
        # (paper-§2.4.1 split/extend/merge runs host-side BETWEEN batches;
        # the next epoch re-packs the table from the adapted binning).
        # Step-major, like the host loop visits assignments; each lever's
        # subsequence goes through ONE batched record_many (which falls back
        # to the exact per-assignment loop whenever a rule could fire
        # mid-batch) instead of N·S python record() calls.
        bins = self.cfgr.disc.bins
        replayed = 0
        with span("tune.replay", batch=batch) as sp:
            lever_sm = lever.T.ravel()        # (S·N,) step-major
            bin_sm = new_bin.T.ravel()
            for li in np.unique(lever_sm):
                dyn = bins.get(names[li])
                if dyn is not None:
                    chosen = bin_sm[lever_sm == li]
                    dyn.record_many(chosen)
                    replayed += chosen.size
            sp.set_metadata(assignments=replayed)
        count("tune.replayed", replayed)
        return final_configs
