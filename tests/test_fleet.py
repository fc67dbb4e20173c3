"""FleetEnv: batched stepping must be bit-for-bit identical to serial
SimCluster runs with matched seeds, and the fleet plumbing (collect,
parallel episodes, workload roster) must stay deterministic."""
import numpy as np
import pytest

from repro.core import AutoTuner
from repro.core.configurator import is_fleet_env
from repro.data.workloads import (FLEET_MIX, IoTWorkload, PoissonWorkload,
                                  fleet_workloads)
from repro.engine import FleetEnv, SimCluster
from repro.monitoring.metrics import FleetSeriesStore, METRIC_NAMES


def _matched_pair(n, seed=0):
    fleet = FleetEnv(fleet_workloads(n, seed=seed),
                     seeds=[seed + i for i in range(n)])
    serial = [SimCluster(w, seed=seed + i)
              for i, w in enumerate(fleet_workloads(n, seed=seed))]
    return fleet, serial


def _assert_windows_equal(wf, ws):
    for a, b in zip(wf, ws):
        assert np.array_equal(a.latencies_ms, b.latencies_ms)
        assert a.p99_ms == b.p99_ms
        assert a.clock_s == b.clock_s
        assert set(a.per_node) == set(b.per_node)
        for m in a.per_node:
            assert np.array_equal(a.per_node[m], b.per_node[m]), m


def test_fleet_observe_matches_serial_bitwise():
    n = 6
    fleet, serial = _matched_pair(n)
    wf = fleet.observe(240.0)
    ws = [e.observe(240.0) for e in serial]
    _assert_windows_equal(wf, ws)


def test_fleet_full_loop_matches_serial_bitwise():
    """apply (heterogeneous T_b -> ragged tick counts) + stabilisation +
    advance + observe, twice, including the changed-lever fast path."""
    n = 6
    fleet, serial = _matched_pair(n)
    cfgs = fleet.current_configs()
    for i, c in enumerate(cfgs):
        c["batch_interval_s"] = [10.0, 5.0, 2.5, 0.9, 16.0, 7.0][i]
        c["prefetch_depth"] = i % 5
        c["backup_tasks"] = i % 2 == 0
    rf = fleet.apply_configs(cfgs)
    rs = [e.apply_config(c) for e, c in zip(serial, cfgs)]
    for a, b in zip(rf, rs):
        assert a == b
    assert np.array_equal(fleet.stabilisation_times(),
                          np.array([e.stabilisation_time() for e in serial]))
    stabs = fleet.stabilisation_times()
    fleet.advance(stabs)
    for e, s in zip(serial, stabs):
        e.advance(float(s))
    _assert_windows_equal(fleet.observe(240.0),
                          [e.observe(240.0) for e in serial])
    # second change through the changed_levers hint (incremental repack)
    cfgs2 = [dict(c) for c in cfgs]
    for i, c in enumerate(cfgs2):
        c["compute_dtype"] = "f32" if i % 2 else "bf16"
    fleet.apply_configs(cfgs2, changed_levers=[("compute_dtype",)] * n)
    [e.apply_config(c) for e, c in zip(serial, cfgs2)]
    _assert_windows_equal(fleet.observe(180.0),
                          [e.observe(180.0) for e in serial])


def test_fleet_per_cluster_windows():
    n = 4
    fleet, serial = _matched_pair(n)
    wins = np.array([60.0, 120.0, 240.0, 90.0])
    wf = fleet.observe(wins)
    ws = [e.observe(float(w)) for e, w in zip(serial, wins)]
    _assert_windows_equal(wf, ws)
    assert np.array_equal(fleet.clocks(),
                          np.array([e.clock for e in serial]))


def test_fleet_reset_and_runnable_mask():
    fleet = FleetEnv(n=4, seed=0)
    cfgs = fleet.current_configs()
    for c in cfgs:
        c["batch_interval_s"] = 1.0
    fleet.apply_configs(cfgs)
    fleet.observe(60.0)
    fleet.reset()
    assert np.all(fleet.clocks() == 0.0)
    assert fleet.current_configs()[0]["batch_interval_s"] == 10.0
    ok = fleet.runnable_mask(fleet.current_configs())
    assert ok.shape == (4,) and ok.dtype == bool and ok.all()
    # a hopeless config (huge interval, tiny batch cap) must be rejected
    bad = [dict(c, batch_interval_s=30.0, max_batch_events=100.0)
           for c in fleet.current_configs()]
    assert not fleet.runnable_mask(bad).any()


def test_workload_roster_deterministic_across_replication():
    """fleet_workloads is fully determined by (n, seed, mix): replicating a
    fleet replays identical arrival processes per (seed, window)."""
    a = fleet_workloads(12, seed=3)
    b = fleet_workloads(12, seed=3)
    ts = np.linspace(0.0, 7200.0, 97)
    for wa, wb in zip(a, b):
        assert type(wa) is type(wb)
        assert [wa.rate(t) for t in ts] == [wb.rate(t) for t in ts]
        assert [wa.mean_size(t) for t in ts] == [wb.mean_size(t) for t in ts]
    # different seeds move the stochastic members (IoT burst schedule)
    c = fleet_workloads(12, seed=4)
    iot_a = next(w for w in a if isinstance(w, IoTWorkload))
    iot_c = next(w for w in c if isinstance(w, IoTWorkload))
    assert any(iot_a.rate(t) != iot_c.rate(t) for t in ts)
    assert len(FLEET_MIX) >= 4  # the roster really is heterogeneous


def test_fleet_collect_fills_matrix_rows():
    env = FleetEnv(n=5, seed=0)
    tuner = AutoTuner(env, seed=0, window_s=240.0)
    assert is_fleet_env(env)
    tuner.collect(10, windows_per_cluster=2)
    assert len(tuner.matrix.metric_rows) == 10
    assert set(tuner.matrix.metric_rows[0]) == set(METRIC_NAMES)
    assert len(tuner.matrix.lever_rows) == 10
    assert all(np.isfinite(t) for t in tuner.matrix.target)
    # budget honoured exactly even when n_clusters does not divide it
    tuner2 = AutoTuner(FleetEnv(n=5, seed=1), seed=1, window_s=240.0)
    tuner2.collect(7, windows_per_cluster=0)
    assert len(tuner2.matrix.metric_rows) == 7


def test_fleet_configurator_runs_parallel_episodes():
    env = FleetEnv(n=4, seed=0)
    tuner = AutoTuner(env, seed=0, window_s=240.0)
    tuner.collect(8, windows_per_cluster=0)
    tuner.analyse()
    cfgr = tuner.build_configurator(steps_per_episode=2, window_s=240.0)
    stats = cfgr.run_update()
    assert stats["episodes"] == 4          # one episode per cluster
    assert stats["steps"] == 8             # 4 episodes x 2 steps
    assert len(cfgr.history) == 8
    ph = cfgr.history[-1].phases
    assert set(ph) == {"loading_s", "stabilisation_s"}


def test_act_batch_matches_action_space():
    env = FleetEnv(n=3, seed=0)
    tuner = AutoTuner(env, seed=0, window_s=240.0)
    tuner.collect(6, windows_per_cluster=0)
    tuner.analyse()
    cfgr = tuner.build_configurator(steps_per_episode=1, window_s=240.0)
    states = np.zeros((16, cfgr.hspec.state_dim), np.float32)
    acts = cfgr.agent.act_batch(states)
    assert acts.shape == (16,)
    assert ((0 <= acts) & (acts < cfgr.agent.n_actions)).all()


def test_fleet_series_store_ring_and_window():
    store = FleetSeriesStore(["a", "b"], n_clusters=3, n_nodes=2, capacity=4)
    ids = np.arange(3)
    for t in range(6):  # wraps the capacity-4 ring
        store.append_batch(ids, np.full(3, float(t)),
                           np.full((3, 2, 2), float(t)))
    w = store.window_of(1, seconds=2.5, now=5.0)
    assert w.shape == (3, 2, 2)            # t in {3, 4, 5}
    assert np.array_equal(w[:, 0, 0], np.array([3.0, 4.0, 5.0]))
    # ragged heads via scatter path
    store.append_batch(np.array([2]), np.array([6.0]),
                       np.full((1, 2, 2), 6.0))
    assert store.window_of(2, 1.5, 6.0).shape[0] == 2
    assert store.window_of(0, 1.5, 6.0).shape[0] == 1
