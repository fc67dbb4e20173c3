"""Device-resident Algorithm 1 (DESIGN.md §10, §11): the fused training loop
must (1) execute one outer iteration as ≤2 jitted device programs — the
episode scan and the update — with no retracing across steady-state
iterations (including on time-varying fleets), (2) stay *statistically*
pinned to the per-step numpy-oracle loop on rewards/returns — for constant
AND variable-rate (Trapezoid / Switching) fleets, on BOTH device backends —
and (3) under greedy acting (explore=False) be *exactly* replayable through
the host oracle: same argmax actions from the same states, same integerised
lever moves, same decoded config values.

The statistical pins use MEDIANS and trimmed means, not raw means: a
cluster that random-walks its config into a saturating corner produces a
retention-capped ~300 s latency window, and a handful of those dominate a
96-sample mean — the two loops draw different action paths by design, so
where the blow-ups land is coin-flip luck, while the bulk of the
distribution (what the medians pin) tracks within a few percent. The
discipline (tolerances + comparison helpers) is shared with
tests/test_fleet_jax.py and tests/test_faults.py via
tests/chaos_harness.py (DESIGN.md §12).

§11 mesh coverage lives in ``test_mesh_*`` (skipped on single-device
hosts; CI forces 8 CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``): a 1-device mesh
must replay the unsharded program EXACTLY (both fold shard ordinal 0 into
the RNG key, so the only difference is the shard_map plumbing), and the
8-device run must stay in-distribution and hand its state back.
"""
import numpy as np
import pytest
from chaos_harness import assert_loop_equivalent, rel

from repro.core.configurator import Configurator, reward_from_latency
from repro.core.discretize import LeverDiscretiser
from repro.data.workloads import (IoTWorkload, PoissonWorkload,
                                  SwitchingWorkload, TrapezoidWorkload)
from repro.engine import FleetEnv

METRICS = ["latency_p99_ms", "latency_mean_ms", "queue_depth", "device_util",
           "sched_queue_depth"]
LEVERS = ["max_batch_events", "prefetch_depth", "driver_memory_gb",
          "sink_partitions", "backup_tasks"]
FROZEN = dict(split_after=10**9, extend_after=10**9, merge_after=10**9)


def _wl(kind, i):
    """Stable-regime fleets: rates sized so the default config keeps up —
    saturation turns the statistical pins into alignment-luck coin flips
    (see module docstring). Switching periods are de-phased per cluster so
    fleet medians average over flip alignment."""
    if kind == "poisson":
        return PoissonWorkload(10_000, 0.5)
    if kind == "trapezoid":
        return TrapezoidWorkload(peak=10_000, base=4_000, ramp_s=600.0,
                                 plateau_s=1200.0)
    if kind == "switching":
        return SwitchingWorkload(PoissonWorkload(6_000, 0.5),
                                 PoissonWorkload(12_000, 0.5),
                                 period_s=700.0 + 60.0 * i)
    raise ValueError(kind)


def _fleet(backend, n, seed=0, kind="poisson"):
    return FleetEnv([_wl(kind, i) for i in range(n)],
                    seeds=[seed + i for i in range(n)], backend=backend)


def _cfgr(env, *, device_loop="auto", seed=0, steps=3, ridge=True, **kw):
    bin_kw = dict(FROZEN)
    if not ridge:
        bin_kw["ridge_frac"] = 0.0
    # mesh defaults to "off" so the algorithm pins here are identical on
    # single- and forced-multi-device hosts (mesh="auto" would silently
    # shard + re-key the RNG under XLA_FLAGS); the §11 mesh behaviour has
    # its own dedicated test_mesh_* coverage below
    kw.setdefault("mesh", "off")
    return Configurator(env, METRICS, LEVERS, seed=seed,
                        steps_per_episode=steps, window_s=240.0,
                        device_loop=device_loop, bin_kw=bin_kw, **kw)


# --------------------------------------------------------------------------
# gates: what the fused loop accepts since §11
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("kind", ["poisson", "trapezoid", "switching"])
def test_supported_for_variable_rate_fleets(backend, kind):
    cfgr = _cfgr(_fleet(backend, 4, kind=kind), device_loop="on")
    assert cfgr.device_loop_reason() is None


def test_unsupported_reasons_name_the_gate():
    assert "needs jax or pallas" in _cfgr(
        _fleet("numpy", 4), device_loop="on").device_loop_reason()
    env = FleetEnv([PoissonWorkload(10_000, 0.5), IoTWorkload()],
                   seeds=[0, 1], backend="jax")
    assert "iot" in _cfgr(env, device_loop="on").device_loop_reason()
    assert "reward_mode" in _cfgr(_fleet("jax", 4), device_loop="on",
                                  reward_mode="neg_inv").device_loop_reason()


# --------------------------------------------------------------------------
# ≤2 device programs per outer iteration, no retrace across iterations
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["poisson", "switching"])
def test_outer_iteration_is_two_programs_no_retrace(kind):
    from repro.core import device_loop as dl
    from repro.core import policy as pol

    base = dict(dl.TRACE_COUNTS)   # keys other tests' configurators traced
    env = _fleet("jax", 6, kind=kind)
    cfgr = _cfgr(env, device_loop="on")
    assert cfgr.device_loop_reason() is None
    # warm through the compile phase INCLUDING the one-time f-exploitation
    # flip at n_updates == f_warmup_updates (it bakes a new static)
    for _ in range(cfgr.agent.f_warmup_updates + 2):
        cfgr.run_update()
    episode_traces = dict(dl.TRACE_COUNTS)
    update_traces = pol.UPDATE_TRACE_COUNT[0]
    # the episode scan compiled exactly twice (pre/post warm-up exploit
    # gate), the update program once — and steady state adds NOTHING,
    # including on the variable-rate path (the workload table is a traced
    # arg, never a trace constant)
    for _ in range(3):
        cfgr.run_update()
    assert dl.TRACE_COUNTS == episode_traces, (episode_traces,
                                               dl.TRACE_COUNTS)
    assert pol.UPDATE_TRACE_COUNT[0] == update_traces
    # ≤2 program kinds per iteration: one episode-scan static bundle per
    # exploit phase + the single update program
    keys_now = [k for k, v in dl.TRACE_COUNTS.items()
                if v > base.get(k, 0)]
    assert len(keys_now) <= 2


def test_device_loop_falls_back_when_unsupported():
    env = _fleet("numpy", 4)
    cfgr = _cfgr(env, device_loop="auto")
    assert cfgr.device_loop_reason() is not None
    stats = cfgr.run_update()          # per-step host loop still works
    assert stats["episodes"] == 4
    with pytest.raises(RuntimeError):
        _cfgr(_fleet("numpy", 4), device_loop="on").run_update()


# --------------------------------------------------------------------------
# statistical equivalence: fused loop vs the numpy-oracle per-step loop
# --------------------------------------------------------------------------

def _loop_rewards(backend, device_loop, n=24, updates=2, seed=0,
                  kind="poisson", steps=3):
    env = _fleet(backend, n, seed=seed, kind=kind)
    cfgr = _cfgr(env, device_loop=device_loop, seed=seed, steps=steps)
    for _ in range(updates):
        cfgr.run_update()
    r = np.array([rec.reward for rec in cfgr.history])
    p = np.array([rec.p99_ms for rec in cfgr.history])
    return r, p


def test_fused_loop_statistically_matches_oracle_loop():
    """Fleet-median rewards (window mean latency), p99 and returns from the
    fused device loop must agree with the numpy-oracle per-step loop — the
    two loops draw different RNG streams and pick different exploratory
    actions, so this is a distributional pin, not a bitwise one."""
    r_ref, p_ref = _loop_rewards("numpy", "off")
    r_dev, p_dev = _loop_rewards("jax", "on")
    assert_loop_equivalent(r_ref, p_ref, r_dev, p_dev)


@pytest.mark.parametrize("kind", ["trapezoid", "switching"])
def test_fused_variable_rate_matches_oracle_loop(kind):
    """§11 acceptance: Trapezoid and Switching fleets run fused end-to-end
    and stay statistically pinned to the numpy-oracle host loop — the
    in-trace ``workload_rate_grid`` evaluation vs the oracle's per-tick
    python ``rate()`` calls."""
    r_ref, p_ref = _loop_rewards("numpy", "off", n=16, kind=kind)
    r_dev, p_dev = _loop_rewards("jax", "on", n=16, kind=kind)
    assert_loop_equivalent(r_ref, p_ref, r_dev, p_dev)


def test_fused_pallas_variable_rate_matches_oracle_loop():
    """The scan-composable pallas window (§11): the fused loop over the
    ``backend="pallas"`` engine (interpret mode off-TPU), on a
    SwitchingWorkload fleet, against the numpy oracle."""
    r_ref, p_ref = _loop_rewards("numpy", "off", n=8, kind="switching")
    r_dev, p_dev = _loop_rewards("pallas", "on", n=8, kind="switching")
    assert_loop_equivalent(r_ref, p_ref, r_dev, p_dev)


def test_fused_loop_learns_like_the_oracle_loop():
    """Both loops drive the same update math (``ReinforceAgent
    .update_batch``): after matched updates the policies must have moved —
    n_updates advanced, params changed — on both paths."""
    env = _fleet("jax", 8)
    cfgr = _cfgr(env, device_loop="on")
    w0 = np.asarray(cfgr.agent.params["w2"]).copy()
    stats = cfgr.run_update()
    assert stats["episodes"] == 8 and stats["steps"] == 24
    assert np.isfinite(stats["pg_loss"]) and np.isfinite(stats["mean_return"])
    assert cfgr.agent.n_updates == 1
    assert not np.allclose(w0, np.asarray(cfgr.agent.params["w2"]))


# --------------------------------------------------------------------------
# greedy (explore=False): exact host-oracle replay
# --------------------------------------------------------------------------

def test_greedy_action_sequence_exactly_replayable():
    env = _fleet("jax", 5)
    cfgr = _cfgr(env, device_loop="on", ridge=False)
    configs0 = env.current_configs()
    batch, records = cfgr.run_fleet_episodes_device(explore=False)
    N, S = 5, cfgr.steps_per_episode
    states = np.asarray(batch["states"])       # (N, S, D)
    actions = np.asarray(batch["actions"])
    assert len(records) == N * S
    # 1) the device's greedy actions ARE the host argmax of the same states
    for t in range(S):
        host_a = cfgr.agent.act_batch(states[:, t], greedy=True)
        assert np.array_equal(host_a, actions[:, t]), t
    # 2) the lever moves decode exactly like the host oracle's apply
    disc = LeverDiscretiser(list(env.lever_specs), seed=0, ridge_frac=0.0,
                            **FROZEN)
    for i in range(N):
        cfg = dict(configs0[i])
        for t in range(S):
            rec = records[i * S + t]
            lever, direction = cfgr.agent.action_decode(int(actions[i, t]))
            assert rec.lever == lever and rec.direction == direction
            cfg = disc.apply(cfg, lever, direction, jitter=False)
            got = rec.config[lever]
            if isinstance(got, float):
                assert got == pytest.approx(cfg[lever], rel=1e-5), (i, t)
            else:
                assert got == cfg[lever], (i, t)
            # the env adopted the device trajectory's final configs
        if isinstance(cfg[lever], float):
            assert env.current_configs()[i][lever] == pytest.approx(
                cfg[lever], rel=1e-5)


# --------------------------------------------------------------------------
# §11 mesh: cluster-sharded episode programs (multi-device hosts only)
# --------------------------------------------------------------------------

def _device_count():
    import jax

    return jax.device_count()


needs_devices = pytest.mark.skipif(
    _device_count() < 2,
    reason="needs >1 jax device "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@needs_devices
def test_mesh_one_device_replays_unsharded_exactly():
    """The shard_map plumbing pin: both the unsharded program and every
    shard fold their shard ordinal into the RNG key, so a 1-device mesh is
    the SAME program modulo the shard_map wrapper (in_specs/out_specs
    alignment, the pmin/pmax range reduction, donation) — trajectories must
    match bit-for-bit. The mesh is built directly (``fleet_mesh`` returns
    None for single-device requests by design), and the runner must
    actually take the sharded path."""
    import jax
    from jax.sharding import Mesh

    from repro.distribution.sharding import FLEET_AXIS

    mesh1 = Mesh(np.asarray(jax.devices()[:1]), (FLEET_AXIS,))

    def run(mesh):
        env = _fleet("jax", 8, kind="switching")
        cfgr = _cfgr(env, device_loop="on", mesh=mesh)
        runner = cfgr._device_runner()
        for _ in range(2):
            cfgr.run_update()
        return np.array([rec.reward for rec in cfgr.history]), runner

    r_off, runner_off = run("off")
    r_m1, runner_m1 = run(mesh1)
    assert runner_off.mesh is None and runner_m1.mesh is mesh1
    assert np.array_equal(r_off, r_m1)


@needs_devices
def test_mesh_sharded_run_stays_in_distribution_and_hands_back_state():
    """Full-device-count sharded run on a variable-rate fleet: per-shard
    RNG streams differ from the single-device run by design, so the pin is
    distributional (medians), plus the §10 state-handoff invariants."""
    ndev = _device_count()
    n = 4 * ndev

    def run(mesh):
        env = _fleet("jax", n, kind="switching")
        cfgr = _cfgr(env, device_loop="on", mesh=mesh)
        runner = cfgr._device_runner()
        for _ in range(2):
            cfgr.run_update()
        return (np.array([rec.reward for rec in cfgr.history]),
                env, runner)

    r1, _, runner1 = run("off")
    r8, env, runner8 = run("auto")
    assert runner1.mesh is None and runner8.mesh is not None
    assert runner8.mesh.size == ndev
    assert rel(np.median(r8), np.median(r1)) < 0.15, (
        np.median(r1), np.median(r8))
    # sharded loop state hands back cleanly: reconfig accounting advanced
    # and a later plain observe on the (still sharded) engine state works
    assert env.reconfigs.tolist() == [2 * 3] * n
    stats = env.observe_stats(240.0)
    assert np.isfinite(np.asarray(stats["mean_ms"])).all()


# --------------------------------------------------------------------------
# satellites: neg_p99 reward, fused-loop bookkeeping invariants
# --------------------------------------------------------------------------

def test_reward_neg_p99_mode():
    lat = np.linspace(100.0, 10_000.0, 200)
    assert reward_from_latency(lat, "neg_p99") == pytest.approx(
        -np.percentile(lat, 99.0) / 1000.0)


@pytest.mark.parametrize("device_loop", ["off", "on"])
def test_neg_p99_uses_device_statistic(device_loop):
    """reward == -p99/1000 bin-for-bin on BOTH device paths: the per-step
    host loop's device shortcut and the fused loop read the window's
    device-computed p99 directly."""
    env = _fleet("jax", 4)
    cfgr = _cfgr(env, device_loop=device_loop, reward_mode="neg_p99")
    cfgr.run_update()
    assert cfgr.history
    for rec in cfgr.history:
        assert rec.reward == pytest.approx(-rec.p99_ms / 1000.0, rel=1e-6)


def test_fused_records_and_state_handoff():
    """StepRecords carry the §10 phase bookkeeping, the engine's clock/
    reconfig counters advance exactly one loading+window cycle per step, and
    a later plain observe() on the same env still works (state handed back)."""
    env = _fleet("jax", 3)
    cfgr = _cfgr(env, device_loop="on", steps=2, episodes_per_update=3)
    clock0 = env.clocks().copy()
    cfgr.run_update()
    assert env.reconfigs.tolist() == [2, 2, 2]
    assert (env.clocks() > clock0).all()
    for rec in cfgr.history:
        assert set(rec.phases) == {"loading_s", "stabilisation_s"}
        assert rec.phases["loading_s"] >= 10.0
        assert 30.0 <= rec.phases["stabilisation_s"] <= 180.0
        assert np.isfinite(rec.reward) and rec.p99_ms > 0
    stats = env.observe_stats(240.0)
    assert np.isfinite(np.asarray(stats["mean_ms"])).all()


def test_double_buffered_dispatch_matches_sync_runs():
    """The §11 double-buffer machinery — TWO episode batches chained
    device-side via ``run_async`` (no finalize between), the policy-update
    program dispatched on their device-resident outputs, host bookkeeping
    only afterwards — must produce bit-for-bit the records and final env
    state of two synchronous ``run()`` calls (greedy acting, frozen bins:
    the state round-trips through the host between sync runs are exact, so
    any chaining/adoption bug shows up as a hard mismatch)."""
    import jax.numpy as jnp

    def run(mode):
        env = _fleet("jax", 4, kind="trapezoid")
        cfgr = _cfgr(env, device_loop="on", steps=2)
        runner = cfgr._device_runner()
        if mode == "sync":
            _, r1 = runner.run(explore=False)
            _, r2 = runner.run(explore=False)
            recs = r1 + r2
        else:
            b1 = runner.run_async(explore=False)
            b2 = runner.run_async(explore=False)   # chained on device
            assert len(runner._inflight) == 2 and recs_pending(runner)
            b = {k: jnp.concatenate([b1[k], b2[k]], axis=0) for k in b1}
            pending = cfgr.agent.update_batch_async(
                b["states"], b["actions"], b["rewards"])
            recs = runner.finalize()               # update still in flight
            stats = pending()
            assert stats["episodes"] == 8 and cfgr.agent.n_updates == 1
        return recs, env.current_configs()

    def recs_pending(runner):
        return runner._carry is not None

    recs_s, cfgs_s = run("sync")
    recs_a, cfgs_a = run("async")
    assert len(recs_s) == len(recs_a) == 16
    for a, b in zip(recs_s, recs_a):
        assert a.lever == b.lever and a.reward == b.reward
        assert a.clock_s == b.clock_s and a.config == b.config
    assert cfgs_s == cfgs_a


# --------------------------------------------------------------------------
# §15 epoch mega-scan: K outer iterations in ONE device program
# --------------------------------------------------------------------------

def test_epoch_compiles_once_and_dispatches_o1():
    """The dispatch-count regression pin: ``run_epoch(K)`` past the exploit
    warm-up compiles ONE epoch program per (K, records) shape, dispatches
    exactly ONE executable per epoch (never O(K)), and steady-state epochs
    of the same shape add zero traces and zero update-program traces (the
    update math is scan-composed, not separately dispatched)."""
    from repro.core import device_loop as dl
    from repro.core import policy as pol

    cfgr = _cfgr(_fleet("jax", 6), device_loop="on")
    for _ in range(cfgr.agent.f_warmup_updates):   # past the exploit flip
        cfgr.run_update()
    base = dict(dl.TRACE_COUNTS)
    d0 = dl.EPOCH_DISPATCHES[0]
    cfgr.run_epoch(4, records="full")
    keys_new = [k for k, v in dl.TRACE_COUNTS.items()
                if v > base.get(k, 0)]
    epochs = [k for k in keys_new if k[0] == "epoch"]
    assert len(epochs) == 1
    # the only other trace bump is the episode CLOSURE, traced INSIDE the
    # epoch jit — not a separately dispatched executable
    assert all(k == epochs[0][1] for k in keys_new if k[0] != "epoch")
    assert dl.EPOCH_DISPATCHES[0] - d0 == 1
    traces = dict(dl.TRACE_COUNTS)
    # the update math traces ONCE, inside the epoch program (the counter
    # bumps at trace time whether jitted standalone or scan-composed)...
    upd_traces = pol.UPDATE_TRACE_COUNT[0]
    cfgr.run_epoch(4, records="full")   # steady state: no retrace
    assert dl.TRACE_COUNTS == traces
    assert dl.EPOCH_DISPATCHES[0] - d0 == 2
    # ...and steady-state epochs re-trace neither it nor the episode body
    assert pol.UPDATE_TRACE_COUNT[0] == upd_traces
    assert cfgr.agent.n_updates == cfgr.agent.f_warmup_updates + 8


def test_epoch_crossing_warmup_is_at_most_two_programs():
    """An epoch that crosses the exploit warm-up boundary splits into two
    segments (the exploit gate is a trace static) — ≤2 compiled programs,
    2 dispatches, and the update count still lands exactly."""
    from repro.core import device_loop as dl

    cfgr = _cfgr(_fleet("jax", 6), device_loop="on")
    assert cfgr.agent.n_updates == 0
    base = dict(dl.TRACE_COUNTS)
    d0 = dl.EPOCH_DISPATCHES[0]
    k = cfgr.agent.f_warmup_updates + 2
    stats = cfgr.run_epoch(k, records="full")
    keys_new = [kk for kk, v in dl.TRACE_COUNTS.items()
                if v > base.get(kk, 0)]
    epochs = [kk for kk in keys_new if kk[0] == "epoch"]
    skeys = {kk[1] for kk in epochs}
    assert len(epochs) == 2
    assert all(kk in skeys for kk in keys_new if kk[0] != "epoch")
    assert dl.EPOCH_DISPATCHES[0] - d0 == 2
    assert len(stats) == k and cfgr.agent.n_updates == k


def test_epoch_summary_and_off_modes_skip_records():
    """``records="summary"|"off"`` must not grow the history, yet still
    advance the fleet state, the update count, the chaos window accounting
    and the §2.4.1 bin hits (replayed from the device count tensor); the
    summary stats carry per-update convergence curves."""
    env = _fleet("jax", 5)
    cfgr = _cfgr(env, device_loop="on")
    clock0 = env.clocks().copy()
    stats = cfgr.run_epoch(3, records="summary")
    assert cfgr.history == []
    assert len(stats) == 3 and cfgr.agent.n_updates == 3
    assert (env.clocks() > clock0).all()
    for st in stats:
        assert np.isfinite(st["pg_loss"]) and np.isfinite(st["reward_mean"])
        assert st["p99_mean_ms"] > 0 and st["episodes"] == 5
    runner = cfgr._runner
    assert runner.chaos.windows == 3 * 5 * 3      # K * N * S
    off = cfgr.run_epoch(2, records="off")
    assert cfgr.history == [] and len(off) == 2
    assert "reward_mean" not in off[0]
    assert cfgr.agent.n_updates == 5
    with pytest.raises(ValueError):
        cfgr.run_epoch(1, records="nope")


def test_mesh_fallback_is_reported_not_silent(monkeypatch, capsys):
    """mesh="auto" on a device count that N does not divide runs on one
    device and prints why; an explicit mesh N does not divide raises."""
    from repro.core.device_loop import DeviceEpisodeRunner
    from repro.distribution import sharding

    class ThreeDevices:
        size = 3

    monkeypatch.setattr(sharding, "fleet_mesh", lambda: ThreeDevices())
    env = _fleet("jax", 4)
    runner = DeviceEpisodeRunner(_cfgr(env, device_loop="on", mesh="auto"))
    assert runner.mesh is None
    assert "N=4 does not divide the 3-device mesh" in capsys.readouterr().out
    with pytest.raises(ValueError, match="does not divide"):
        DeviceEpisodeRunner(_cfgr(env, device_loop="on",
                                  mesh=ThreeDevices()))


def test_epoch_summary_bin_replay_matches_full_mode():
    """The device-side (lever, bin) count tensor replayed at the epoch
    boundary must land the same §2.4.1 hit totals as full-mode
    materialisation (identical twins, same episode stream)."""
    a = _cfgr(_fleet("jax", 4), device_loop="on")
    b = _cfgr(_fleet("jax", 4), device_loop="on")
    a.run_epoch(3, records="full")
    b.run_epoch(3, records="summary")
    for name, dyn in a.disc.bins.items():
        assert dyn._hits.sum() == b.disc.bins[name]._hits.sum(), name


def test_epoch_skips_repack_when_bins_unchanged():
    """Satellite: with no edge change from the boundary replay (frozen
    bins here), the next epoch must reuse the packed ``DeviceLeverTable``
    wholesale — and a mutated edge array must force a re-pack."""
    cfgr = _cfgr(_fleet("jax", 4), device_loop="on")
    cfgr.run_epoch(2, records="summary")
    runner = cfgr._runner
    table, tabs = runner._table, runner._tabs
    cfgr.run_epoch(2, records="summary")
    assert runner._table is table and runner._tabs is tabs
    # sequential batches ride the same skip
    cfgr.run_update()
    assert runner._table is table and runner._tabs is tabs
    # an adapted bin (edge change) invalidates the signature
    dyn = cfgr.disc.bins["max_batch_events"]
    dyn._extend(top=True)
    cfgr.run_epoch(1, records="summary")
    assert runner._table is not table


def test_epoch_rejects_inflight_batches():
    cfgr = _cfgr(_fleet("jax", 4), device_loop="on")
    runner = cfgr._device_runner()
    runner.run_async()
    with pytest.raises(RuntimeError, match="in flight"):
        runner.run_epoch(2)
    runner.finalize()
    stats, recs = runner.run_epoch(1)
    assert len(stats) == 1 and len(recs) == 4 * 3


@needs_devices
def test_mesh_epoch_matches_unsharded_on_one_device():
    """§11 × §15: the epoch scan with the shard_map'd episode body on a
    1-device mesh must replay the unsharded epoch bitwise (same plumbing
    pin as test_mesh_one_device_replays_unsharded_exactly)."""
    import jax
    from jax.sharding import Mesh

    from repro.distribution.sharding import FLEET_AXIS

    mesh1 = Mesh(np.asarray(jax.devices()[:1]), (FLEET_AXIS,))

    def run(mesh):
        env = _fleet("jax", 8, kind="switching")
        cfgr = _cfgr(env, device_loop="on", mesh=mesh)
        cfgr.run_epoch(3, records="full")
        return np.array([rec.reward for rec in cfgr.history])

    assert np.array_equal(run("off"), run(mesh1))


@needs_devices
def test_mesh_epoch_sharded_stays_in_distribution():
    """Full-device-count epoch scan: per-shard RNG streams differ from the
    single-device epoch by design — distributional pin plus state handoff,
    like the per-update sharded test."""
    import jax

    ndev = jax.device_count()
    n = 4 * ndev

    def run(mesh):
        env = _fleet("jax", n, kind="switching")
        cfgr = _cfgr(env, device_loop="on", mesh=mesh)
        cfgr.run_epoch(2, records="full")
        return np.array([rec.reward for rec in cfgr.history]), env

    r1, _ = run("off")
    r8, env = run("auto")
    assert rel(np.median(r8), np.median(r1)) < 0.15
    assert env.reconfigs.tolist() == [2 * 3] * n
    stats = env.observe_stats(240.0)
    assert np.isfinite(np.asarray(stats["mean_ms"])).all()

# --------------------------------------------------------------------------
# §16 shield bitwise pins: off ≡ neutral, radius 0 confines
# --------------------------------------------------------------------------

def _slo_rewards_and_configs(safe, shield_kw=None, updates=2, n=6):
    env = _fleet("jax", n)
    cfgr = _cfgr(env, device_loop="on", reward_mode="slo", slo_ms=5_000.0,
                 safe=safe, shield_kw=shield_kw)
    for _ in range(updates):
        cfgr.run_update()
    return (np.array([rec.reward for rec in cfgr.history]),
            [dict(c) for c in env.configs])


def test_neutral_shield_replays_shield_off_bitwise():
    """§16 acceptance: the shield is a pure refinement of the pre-§16
    program. A shield whose trust region covers the whole ladder and whose
    risk/budget thresholds can never fire leaves an all-True mask — and
    masked categorical sampling with the SAME fold-in key under an all-True
    mask draws the identical action stream, so rewards AND final decoded
    configs replay the shield-off run bit for bit. (Shield *off* trivially
    traces the exact pre-§16 program: the mask branch is static python.)"""
    neutral = dict(trust_radius=64, radius_min=64, radius_max=64,
                   risk_threshold=2.0, breach_budget=10**6)
    r_off, c_off = _slo_rewards_and_configs(False)
    r_neu, c_neu = _slo_rewards_and_configs(True, neutral)
    assert np.array_equal(r_off, r_neu)
    assert c_off == c_neu


def test_zero_radius_shield_confines_to_lkg():
    """The opposite extreme: radius 0 pins every lever to its last-known-
    good bin, and with no clean window able to move LKG past the sampled
    configs (they never leave it), the fleet's integerised lattice state
    must finish exactly where it started. (Config DICT values may still be
    re-decoded onto the bin ladder for touched levers — same bins, decoded
    representation — so the pin is on the index array, not the dicts.)"""
    env = _fleet("jax", 4)
    cfgr = _cfgr(env, device_loop="on", reward_mode="slo", slo_ms=5_000.0,
                 safe=True, shield_kw=dict(trust_radius=0, radius_min=0,
                                           radius_max=0))
    cfgr.run_update()
    runner = cfgr._runner
    assert np.array_equal(np.asarray(runner._config_idx),
                          np.asarray(runner._idx0))
    # every sampled move was diverted or clamped back onto LKG
    assert cfgr.shield_counters.clamped_actions > 0
