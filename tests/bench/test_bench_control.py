"""`correct` has to come out false for the control and for each fault.

At N=8 on the CPU, against each cell's committed limits. The fleet-median
reward (``reward_gap``, compared in the jax training cell) is a median over
the cell's whole fleet and means nothing over 8 clusters, so at this size
the tests judge the others: the deterministic replay, the encode, decode
and apply checks, the actions' likelihood and the update numbers.

* the control — the plain reference computed in bfloat16 and put in the
  program's place — fails at least one number;
* a whole harness run with the timed path broken underneath reads
  ``correct: false``, once per fault the cells can have: an update that
  returns its state unchanged, an update that sees half the batch (the
  mean taken over the rest), and an answer altered where it is produced
  (one cluster's clock after a step; every applied lever decoded from the
  wrong bin).

The actions altered where they are drawn (``shifted_actions`` in
``bench/calibrate.py``) show only over a whole fleet's draws, so that fault
is read on the chip at each cell's size, not here.
"""
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import calibrate  # noqa: E402
import compare  # noqa: E402
import loops  # noqa: E402
import run  # noqa: E402

SMALL = {"fleet": 8, "warmup_units": 0}
#: numbers that are fleet medians: judged only at the cell's own size
FLEET_MEDIANS = ("reward_gap",)


def holds(checks: dict) -> bool:
    """Every number other than the fleet medians within its limit."""
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for k, v in checks.items() if k not in FLEET_MEDIANS)


def _cfgr(driver):
    return driver.cfgr if hasattr(driver, "cfgr") else driver.ctl.cfgr


def stale_update(driver):
    """The update program runs, but its new state is dropped."""
    ag = _cfgr(driver).agent
    orig = ag._update_jit

    def upd(params, opt_state, *a):
        _, _, loss, first = orig(params, opt_state, *a)
        return params, opt_state, loss, first

    ag._update_jit = upd


def half_batch(driver):
    """The update program sees the first half of the episodes only."""
    ag = _cfgr(driver).agent
    orig = ag._update_jit

    def upd(params, opt_state, states, actions, rewards, mask):
        h = states.shape[0] // 2
        return orig(params, opt_state, states[:h], actions[:h], rewards[:h],
                    mask[:h])

    ag._update_jit = upd


def altered_clock(driver):
    """One cluster's clock after its first step is off by one tick where
    the episode's outputs reach the host."""
    runner = _cfgr(driver)._device_runner()
    orig = runner._materialise

    def mat(entry, configs, records, gen_s):
        outs = dict(entry["outs"])
        clock = np.array(outs["clock_s"])
        clock[0, 0] += configs[0]["batch_interval_s"]
        outs["clock_s"] = clock
        return orig(dict(entry, outs=outs), configs, records, gen_s)

    runner._materialise = mat


def wrong_bin(driver):
    """Every applied lever is decoded from the bin above the one its action
    moved to, where the episode's outputs reach the host."""
    runner = _cfgr(driver)._device_runner()
    orig = runner._materialise

    def mat(entry, configs, records, gen_s):
        table = runner._table
        value_of = type(table).value_of.__get__(table)
        table.value_of = lambda li, b, rng=None: value_of(li, b + 1, rng)
        return orig(entry, configs, records, gen_s)

    runner._materialise = mat


def run_small(cell, seed, monkeypatch, fault=None):
    """A whole harness run at N=8 on the CPU, with ``fault`` planted in
    each driver the harness builds. The warm-up after the checked updates
    only compiles shapes (it is judged by the whole run of
    ``test_bench_cells.py``), so it is skipped here."""
    import jax

    c = run.load_cell(cell)
    c.traffic.update(SMALL)
    monkeypatch.setattr(loops, "warm_up", lambda cfgr, traffic, one_unit: 0)
    if fault is not None:
        build = loops.DRIVERS[c.traffic["kind"]]

        def broken(*a):
            d = build(*a)
            fault(d)
            return d

        monkeypatch.setitem(loops.DRIVERS, c.traffic["kind"], broken)
    args = SimpleNamespace(workload=cell, seed=seed, seconds=0.01, trace=0)
    return run.measure(c, args, jax.devices()[:1], time.perf_counter())


CELLS = [w["name"] for w in
         __import__("json").loads((ROOT / "BENCHMARK.json").read_text())
         ["workloads"]]
FAULTS = {"stale_update": stale_update, "half_batch": half_batch,
          "altered_clock": altered_clock, "wrong_bin": wrong_bin}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_number(cell):
    c = run.load_cell(cell)
    sys.path.insert(0, str(ROOT / "src"))
    with tempfile.TemporaryDirectory() as work:
        d = loops.DRIVERS[c.traffic["kind"]](
            c.deploy, dict(c.traffic, **SMALL), 2**31 + 41, Path(work))
        d.check_units()
        r = calibrate.readings(d)
    _, prog = compare.judge(r["program"], c.limits, c.not_compared)
    _, ctrl = compare.judge(dict(r["program"], **r["control"]), c.limits,
                            c.not_compared)
    # the program itself passes on this seed; the control does not
    assert holds(prog), prog
    assert not holds(ctrl), ctrl


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_reads_not_correct(cell, fault, monkeypatch):
    """The whole run, the harness's look for a chip skipped: sound with the
    timed path intact, not correct with each fault planted under it."""
    out = run_small(cell, 2**31 + 43, monkeypatch, FAULTS.get(fault))
    if fault is None:
        assert holds(out["checks"]), out["checks"]
    else:
        assert not holds(out["checks"]), out["checks"]
        assert out["correct"] is False
