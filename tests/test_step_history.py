"""Columnar step history (DESIGN.md §10): the fused loop stores each episode
batch column-wise (``StepBatch``) inside ``Configurator.history`` (a
``StepHistory``) and builds a record's config only when it is read.

The contract pinned here: every record read through the history equals,
field for field and config dict for config dict, what the per-step record
loop builds from the same pulled arrays with the lever table of the same
materialisation — shield off, shield on and the epoch mega-scan's
``records="full"`` path, and still after later §2.4.1 replays have moved
the bins. The starting configs the history keeps are never mutated in
place, nothing builds a config nobody reads, and the history's GC-tracked
growth is O(N) per update, not O(N·S).
"""
import gc

import numpy as np
import pytest

from repro.core.configurator import (Configurator, StepBatch, StepHistory,
                                     StepRecord)
from repro.core.faults import chaos_scenario
from repro.data.workloads import PoissonWorkload
from repro.engine import FleetEnv
from repro.monitoring.spans import TOTALS

METRICS = ["latency_p99_ms", "latency_mean_ms", "queue_depth", "device_util",
           "sched_queue_depth"]
LEVERS = ["max_batch_events", "prefetch_depth", "driver_memory_gb",
          "sink_partitions", "backup_tasks"]
N, S = 16, 5
#: bins that adapt within a few updates, so a later replay moves some
ADAPTIVE = dict(split_after=2, extend_after=2, merge_after=6)


def _cfgr(n=N, *, safe=False, bin_kw=ADAPTIVE, seed=0):
    kw = {}
    faults = None
    if safe:
        kw = dict(reward_mode="slo", slo_ms=12_000.0, safe=True)
        faults = chaos_scenario(n, seed=seed)
    env = FleetEnv([PoissonWorkload(10_000, 0.5) for _ in range(n)],
                   seeds=[seed + i for i in range(n)], backend="jax",
                   faults=faults)
    return Configurator(env, METRICS, LEVERS, seed=seed, steps_per_episode=S,
                        window_s=240.0, device_loop="on", mesh="off",
                        bin_kw=dict(bin_kw), **kw)


def _eager(outs, configs, table):
    """The per-step record loop: one StepRecord and one config copy per
    cluster-step, decoded with ``table`` as it stands now."""
    names = table.names
    lever, bins = outs["lever"].tolist(), outs["bin"].tolist()
    directions = (1 - 2 * (outs["actions"] % 2)).tolist()
    cols = [outs[k].tolist()
            for k in ("rewards", "p99_ms", "clock_s", "load_s", "stab_s")]
    recs, finals = [], []
    for i, cfg in enumerate(configs):
        for t in range(len(lever[i])):
            li = lever[i][t]
            cfg = dict(cfg)
            cfg[names[li]] = table.value_of(li, bins[i][t])
            rw, p, ck, ld, st = (c[i][t] for c in cols)
            recs.append(StepRecord(
                lever=names[li], direction=directions[i][t], config=cfg,
                reward=rw, p99_ms=p, clock_s=ck,
                phases={"loading_s": ld, "stabilisation_s": st}))
        finals.append(dict(cfg))
    return recs, finals


def _capture(cfgr):
    """Wrap ``_materialise``: before each batch, build the eager reference
    from the same pulled arrays and table, and snapshot its start configs."""
    runner = cfgr._device_runner()
    orig = runner._materialise
    seen = []

    def mat(entry, configs, records, batch):
        outs = {k: np.array(v) for k, v in entry["outs"].items()
                if k in ("lever", "bin", "actions", "rewards", "p99_ms",
                         "clock_s", "load_s", "stab_s")}
        recs, finals = _eager(outs, configs, runner._table)
        snap = [dict(c) for c in configs]
        out = orig(entry, configs, records, batch)
        seen.append({"recs": recs, "finals": finals, "snap": snap,
                     "got_finals": out, "outs": outs})
        return out

    runner._materialise = mat
    return seen


def _same(got, want):
    """Bit for bit: equal fields, the same value types, the same key order."""
    assert got == want and want == got
    assert (got.lever, got.direction, got.reward, got.p99_ms, got.clock_s) \
        == (want.lever, want.direction, want.reward, want.p99_ms,
            want.clock_s)
    assert list(got.config.items()) == list(want.config.items())
    assert [type(v) for v in got.config.values()] \
        == [type(v) for v in want.config.values()]
    assert got.phases == want.phases
    for a, b in ((got.reward, want.reward), (got.direction, want.direction)):
        assert type(a) is type(b)


def _check(history, seen):
    want = [r for s in seen for r in s["recs"]]
    assert len(history) == len(want)
    for got, w in zip(history, want):
        _same(got, w)
    for j in (0, len(want) // 2, len(want) - 1):    # indexing path too
        _same(history[j], want[j])


def _batches(history):
    return [src for src, _, _ in history._segs if isinstance(src, StepBatch)]


def test_unshielded_records_equal_the_eager_loop_after_bins_move():
    cfgr = _cfgr()
    seen = _capture(cfgr)
    cfgr.run_update()
    _check(cfgr.history, seen)
    assert seen[-1]["got_finals"] == seen[-1]["finals"]
    assert cfgr.env.configs == seen[-1]["finals"]

    # two more updates: their replays move bins, so decoding the stored
    # steps with the table as it stands now would give other values
    table0 = cfgr._runner._table
    for _ in range(2):
        cfgr.run_update()
    _check(cfgr.history, seen)
    assert cfgr.env.configs == seen[-1]["finals"]
    table = cfgr._runner._table
    assert table is not table0
    moved = 0
    for s in seen[:1]:
        lv, bn = s["outs"]["lever"].ravel(), s["outs"]["bin"].ravel()
        moved += sum(table.value_of(int(li), int(b))
                     != table0.value_of(int(li), int(b))
                     for li, b in zip(lv, bn))
    assert moved > 0, "no bin moved: the later-table failure mode is untested"

    # no starting config the history keeps was mutated in place later
    batches = _batches(cfgr.history)
    assert len(batches) == len(seen) == 3
    for b, s in zip(batches, seen):
        assert [dict(c) for c in b.start] == s["snap"]


def test_shielded_records_equal_the_eager_loop():
    cfgr = _cfgr(8, safe=True, seed=1)
    seen = _capture(cfgr)
    for _ in range(2):
        cfgr.run_update()
    assert cfgr.shield_counters.clamped_actions \
        + cfgr.shield_counters.fallbacks > 0
    _check(cfgr.history, seen)
    for b, s in zip(_batches(cfgr.history), seen):
        assert [dict(c) for c in b.start] == s["snap"]


def test_epoch_full_records_equal_the_eager_loop():
    cfgr = _cfgr()
    seen = _capture(cfgr)
    stats = cfgr.run_epoch(3, records="full")
    assert len(stats) == 3 and len(seen) == 3
    assert len(cfgr.history) == 3 * N * S
    _check(cfgr.history, seen)
    cfgr.run_update()
    _check(cfgr.history, seen)


def _host_records(k, tag):
    return [StepRecord(lever=f"{tag}{i}", direction=1, config={"x": i},
                       reward=float(-i), p99_ms=float(i), clock_s=float(i),
                       phases={"loading_s": 0.0, "stabilisation_s": 0.0})
            for i in range(k)]


def test_sequence_operations_over_mixed_segments():
    cfgr = _cfgr()
    assert cfgr.history == [] and not cfgr.history and len(cfgr.history) == 0
    cfgr.run_update()
    fused = list(cfgr.history)
    host_a, host_b = _host_records(3, "a"), _host_records(4, "b")

    h = StepHistory(host_a)
    h.extend(cfgr.history)
    h.extend(host_b)
    h.extend(cfgr.history[N:2 * N])
    flat = host_a + fused + host_b + fused[N:2 * N]
    assert len(h) == len(flat)
    assert h == flat and list(h) == flat
    for j in (0, 2, 3, len(host_a) + len(fused), -1, -len(flat), -N - 1):
        assert h[j] == flat[j]
    with pytest.raises(IndexError):
        h[len(flat)]
    with pytest.raises(IndexError):
        h[-len(flat) - 1]
    for sl in (slice(None), slice(2, 9), slice(-7, None), slice(1, -1),
               slice(5, 5), slice(len(flat) - 2, len(flat) + 10),
               slice(None, None, 7), slice(None, None, -3)):
        got = h[sl]
        assert list(got) == flat[sl] and len(got) == len(flat[sl])
    view = h[2:-3]
    assert isinstance(view, StepHistory)
    assert view[-1] == flat[2:-3][-1] and view[0] == flat[2]
    assert h[:0] == [] and h[len(flat):] == []

    # + and extend never copy a batch's records
    both = cfgr.history + StepHistory(host_b)
    assert list(both) == fused + host_b
    assert _batches(both)[0] is _batches(cfgr.history)[0]
    h.extend(h)                                   # extending with itself
    assert list(h) == flat + flat


def test_unread_updates_build_no_config():
    cfgr = _cfgr()
    cfgr.run_update()                 # compiles; nothing reads the records
    built0 = TOTALS.counters.get("tune.record_configs_built", 0)
    rows0 = TOTALS.counters.get("tune.records_built", 0)
    for _ in range(3):
        cfgr.run_update()
    assert TOTALS.counters["tune.record_configs_built"] == built0
    assert TOTALS.counters["tune.records_built"] == rows0 + 3 * N * S
    # reading one record's config builds exactly one
    cfg = cfgr.history[-1].config
    assert cfg == cfgr.env.configs[-1]
    assert TOTALS.counters["tune.record_configs_built"] == built0 + 1


def test_history_gc_growth_is_o_of_n_not_n_times_s():
    n = 64
    # frozen bins: no new bin-table rung compiles a program mid-measurement
    cfgr = _cfgr(n, bin_kw=dict(split_after=10**9, extend_after=10**9,
                                merge_after=10**9))
    for _ in range(3):                # compiles, and crosses the exploit flip
        cfgr.run_update()
    gc.collect()
    before = len(gc.get_objects())
    for _ in range(3):
        cfgr.run_update()
    gc.collect()
    grown = len(gc.get_objects()) - before
    assert len(cfgr.history) == 6 * n * S
    assert grown < n * S, grown
