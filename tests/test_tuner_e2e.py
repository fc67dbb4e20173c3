"""End-to-end tuner integration (Fig 5 / Fig 8 shape, small budgets).

These mirror the paper's claims at test scale:
  * collect -> analyse recovers latency metrics + effective levers;
  * a short REINFORCE run beats the default configuration;
  * the tuner keeps working after a workload switch (Fig 8).
"""
import numpy as np
import pytest

from repro.core import AutoTuner
from repro.data.workloads import PoissonWorkload, SwitchingWorkload
from repro.engine import EFFECTIVE, SimCluster


@pytest.fixture(scope="module")
def analysed_tuner():
    env = SimCluster(PoissonWorkload(10_000, 0.5), seed=2)
    tuner = AutoTuner(env, seed=2, window_s=240.0, top_levers=8)
    tuner.collect(1000)
    tuner.analyse()
    return tuner


def test_analysis_reduces_metrics_and_finds_latency(analysed_tuner):
    sel = analysed_tuner.selection
    assert sel.reduction > 0.8  # paper: 92 %
    assert 3 <= sel.k <= 12
    assert len(analysed_tuner.selected_metrics) == len(set(analysed_tuner.selected_metrics))


def test_lasso_recovers_effective_levers(analysed_tuner):
    ranked = analysed_tuner.ranked_levers
    hits = set(ranked) & set(EFFECTIVE)
    assert len(hits) >= 2, ranked
    assert "batch_interval_s" in ranked[:4], ranked


def test_short_rl_run_beats_default(analysed_tuner):
    tuner = analysed_tuner
    tuner.env.reset()
    base = tuner.env.observe(300.0).p99_ms
    cfgr = tuner.build_configurator(steps_per_episode=5, episodes_per_update=4,
                                    window_s=240.0, f_exploit=0.8)
    cfgr.tune(6)
    best = min(r.p99_ms for r in cfgr.history)
    assert best < 0.6 * base, (best, base)  # paper: >70 % after full training
    # execution-phase bookkeeping exists for the Fig 6 breakdown
    ph = cfgr.history[-1].phases
    assert set(ph) == {"loading_s", "stabilisation_s"}
    assert ph["loading_s"] > 0


def test_collect_with_nan_injection_still_analyses():
    env = SimCluster(PoissonWorkload(10_000, 0.5), seed=5)
    tuner = AutoTuner(env, seed=5, window_s=240.0)
    tuner.collect(120, drop_frac=0.05)  # 5 % missing samples -> spline repair
    mets, levs = tuner.analyse()
    assert mets and levs


def test_adaptation_to_workload_switch():
    """Fig 8: after a switch to a heavier distribution the tuner recovers to a
    latency below the immediate post-switch spike."""
    wl = SwitchingWorkload(PoissonWorkload(10_000, 0.5),
                           PoissonWorkload(40_000, 1.0), period_s=1e9)
    env = SimCluster(wl, seed=3)
    tuner = AutoTuner(env, seed=3, window_s=240.0, top_levers=8)
    tuner.collect(500)
    tuner.analyse()
    env.reset()
    cfgr = tuner.build_configurator(steps_per_episode=5, episodes_per_update=4,
                                    window_s=240.0, f_exploit=0.7)
    cfgr.tune(4)
    # switch the workload mid-flight
    wl.period_s = 1.0  # active() now returns b (clock far beyond one period)
    spike = env.observe(240.0).p99_ms
    cfgr.tune(4)
    recovered = np.mean([r.p99_ms for r in cfgr.history[-8:]])
    assert recovered < spike * 1.05, (recovered, spike)


def test_save_and_load_analysis(tmp_path, analysed_tuner):
    p = tmp_path / "analysis.json"
    analysed_tuner.save_analysis(p)
    env = SimCluster(PoissonWorkload(10_000, 0.5), seed=9)
    fresh = AutoTuner(env, seed=9)
    fresh.load_analysis(p)
    assert fresh.ranked_levers == analysed_tuner.ranked_levers
    assert fresh.selected_metrics == analysed_tuner.selected_metrics
