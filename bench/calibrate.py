#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--out F]

For each seed, in one process: builds the cell, drives its checked updates
(or serve cycles) exactly as a benchmark run's set-up does (without the
warm-up that follows them), and prints one
JSON line with

* ``program`` — the numbers ``compare.py`` reads for the program;
* ``control`` — the same numbers for the reference computed one precision
  below the program's and put in its place (the lower precision a later
  change might tempt), against the float64 reference: in bfloat16 its
  replay, its encoder, its update and actions it draws from its own policy
  (the program runs these in float32), in float32 its decode (the program
  decodes in float64);
* ``faults`` — the numbers for faults planted in the reference put in the
  program's place: ``unchanged`` (the update returns its state unchanged),
  ``half_batch`` (the update sees half the episodes), ``clock_tick`` (one
  cluster's clock altered by one tick where it is produced), ``wrong_bin``
  (every applied lever decoded from the bin above the one the action moves
  to) and ``shifted_actions`` (every action altered to the next lever's,
  same direction, where it is drawn).

No measured window runs; the benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import compare  # noqa: E402
from reference import (Precision, action_logp, bin_of,  # noqa: E402
                       decode_lever, policy_update, step_bin)


def reference_as_program(driver, q: Precision, *, half: bool = False,
                         unchanged: bool = False) -> dict:
    """The program's update readings (losses, first moments, final params)
    as the reference at precision ``q`` produces them."""
    spy = driver.spy
    p = {k: np.asarray(v, np.float64) for k, v in driver.init.items()}
    nu = {k: np.zeros_like(v) for k, v in p.items()}
    losses, nu1 = [], None
    for states, actions, rewards in spy.batches:
        if half:
            m = states.shape[0] // 2
            states, actions, rewards = states[:m], actions[:m], rewards[:m]
        p2, nu, _, loss, _ = policy_update(p, nu, states, actions, rewards,
                                           q=q)
        p = p if unchanged else p2
        if unchanged:
            nu = {k: np.zeros_like(v) for k, v in nu.items()}
        nu1 = nu if nu1 is None else nu1
        losses.append(loss)
    return {"losses": losses, "nu1": nu1, "params_end": p}


def sim_as_program(driver, q: Precision, rng) -> dict:
    """The replayed steps' outputs as the reference at ``q`` produces them."""
    return compare.replay(driver.deploy, driver.traffic,
                          driver.checked_records, driver.configs0,
                          n=driver.n, s=driver.s, rng=rng, q=q)


def act_as_program(driver, q: Precision, *, wrong_bin: bool = False) -> dict:
    """The first update's encoded states and applied values as the
    reference at ``q`` produces them (``wrong_bin``: decoded one bin up)."""
    states, actions, _ = driver.spy.batches[0]
    r = compare.act_reference(driver.deploy, driver.spy.enc, actions,
                              driver.configs0, q=q)
    if wrong_bin:
        space = driver.deploy["lever_space"]
        for (i, t), lever in np.ndenumerate(r["levers"]):
            b = bin_of(space[lever], r["values"][i, t], q)
            r["values"][i, t] = float(decode_lever(
                space[lever], step_bin(space[lever], b, 1), q))
    return r


def drawn_batches(driver, q: Precision, rng) -> list:
    """The checked updates' batches with actions drawn from the reference
    policy at ``q``, following its own updates."""
    t = driver.traffic
    p = {k: np.asarray(v, np.float64) for k, v in driver.init.items()}
    nu = {k: np.zeros_like(v) for k, v in p.items()}
    out = []
    for k, (states, _, rewards) in enumerate(driver.spy.batches):
        prob = np.exp(action_logp(p, states, q, exploit=k >= int(
            t["f_warmup_updates"]), f=float(t["f_exploit"])))
        cdf = np.cumsum(prob / prob.sum(-1, keepdims=True), -1)
        u = rng.random(cdf.shape[:-1])[..., None]
        actions = np.minimum((cdf < u).sum(-1), prob.shape[-1] - 1)
        out.append((states, actions, rewards))
        p, nu, *_ = policy_update(p, nu, states, actions, rewards, q=q)
    return out


def readings(driver) -> dict:
    seed = driver.seed
    t = driver.traffic
    out = {"seed": seed, "program": driver.numbers()}
    ref = sim_as_program(driver, Precision("float64"),
                         np.random.default_rng([seed, 2]))
    f64, bf = Precision("float64"), Precision("bfloat16")
    ref_act = act_as_program(driver, f64)

    def sim_numbers(prog):
        return compare.compare_simulation(prog, ref)

    def upd_numbers(r):
        return compare.update_numbers(driver.init, driver.spy.batches,
                                      r["losses"], r["nu1"], r["params_end"])

    def z(batches):
        return {"act_z": compare.act_z(driver.init, batches,
                                       f=float(t["f_exploit"]),
                                       warmup=int(t["f_warmup_updates"]))}

    rng = np.random.default_rng([seed, 3])
    # the decode runs in float64 on the host, so its control is float32;
    # the rest runs in float32 on the device, so theirs is bfloat16
    f32 = Precision("float32")
    out["control"] = dict(
        sim_numbers(sim_as_program(driver, bf, rng)),
        encode_abs_err=compare.compare_act(act_as_program(driver, bf),
                                           ref_act)["encode_abs_err"],
        decode_rel_err=compare.compare_act(act_as_program(driver, f32),
                                           ref_act)["decode_rel_err"],
        **z(drawn_batches(driver, bf, rng)),
        **upd_numbers(reference_as_program(driver, bf)))
    base = sim_as_program(driver, f64, np.random.default_rng([seed, 4]))
    tick = dict(base, clock_s=base["clock_s"].copy())
    tick["clock_s"][0, -1] += driver.configs0[0]["batch_interval_s"]
    n_act = 2 * len(driver.deploy["ranked_levers"])
    shifted = [(st, (a + 2) % n_act, r) for st, a, r in driver.spy.batches]
    out["faults"] = {
        "unchanged": upd_numbers(reference_as_program(driver, f64,
                                                      unchanged=True)),
        "half_batch": upd_numbers(reference_as_program(driver, f64,
                                                       half=True)),
        "clock_tick": sim_numbers(tick),
        "wrong_bin": compare.compare_act(
            act_as_program(driver, f64, wrong_bin=True), ref_act),
        "shifted_actions": z(shifted),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import run

    cell = run.load_cell(args.workload)
    run.device_check(int(cell.cell["chips"]))
    run.enable_cache()
    sys.path.insert(0, str(run.ROOT / "src"))
    import loops

    lines = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix="bench-cal-") as work:
            driver = loops.DRIVERS[cell.traffic["kind"]](
                cell.deploy, cell.traffic, seed, Path(work))
            driver.check_units()
            r = readings(driver)
            driver.close()
        r["seconds"] = time.perf_counter() - t0
        line = json.dumps(r)
        print(line, flush=True)
        lines.append(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
