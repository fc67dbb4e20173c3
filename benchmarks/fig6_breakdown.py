"""Fig 6 — execution-time breakdown of one tuning episode.

Paper: episode time is dominated by Configuration Loading and Workload
Stabilisation; Configuration Generation and Network Reward/Adaptation are
negligible.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Row, emit, make_dist1_env


def run(seed: int = 3, updates: int = 4) -> list[Row]:
    from repro.core import AutoTuner

    env = make_dist1_env(seed)
    tuner = AutoTuner(env, seed=seed, window_s=240.0, top_levers=8)
    tuner.collect(400)
    tuner.analyse()
    env.reset()
    cfgr = tuner.build_configurator(steps_per_episode=5, episodes_per_update=2,
                                    window_s=240.0)
    # configuration generation and the network update are host phases:
    # timed as spans around the agent's calls, spread over the steps
    from repro.monitoring import span, spans

    agent = cfgr.agent
    for attr, name in (("act", "fig6.generation"), ("update", "fig6.update")):
        def timed(*a, _fn=getattr(agent, attr), _name=name, **kw):
            with span(_name):
                return _fn(*a, **kw)
        setattr(agent, attr, timed)
    spans.TOTALS.reset()
    cfgr.tune(updates)
    per_step = {k: spans.TOTALS.spans[f"fig6.{k}"].seconds
                / len(cfgr.history) for k in ("generation", "update")}
    phases = {"generation_s": [per_step["generation"]],
              "loading_s": [r.phases["loading_s"] for r in cfgr.history],
              "stabilisation_s": [r.phases["stabilisation_s"]
                                  for r in cfgr.history],
              "update_s": [per_step["update"]]}
    total = sum(np.mean(v) for v in phases.values())
    rows = []
    for k, v in phases.items():
        m = float(np.mean(v))
        rows.append(Row(f"fig6.{k.replace('_s', '')}", m, "s",
                        f"{100 * m / total:.1f}% of episode step"))
    rows.append(Row("fig6.dominated_by_loading_and_stabilisation",
                    int(np.mean(phases["loading_s"]) + np.mean(phases["stabilisation_s"])
                        > 0.9 * total), "bool", "paper's headline finding"))
    return rows


if __name__ == "__main__":
    emit(run())
