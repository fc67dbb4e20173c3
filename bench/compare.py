"""The comparison that decides `correct`: what the timed path produced on its
first updates (or serve cycles) against the plain reference.

Numbers read (each against the cell's limit in ``limits/<cell>.json``,
unless that file names it under ``not_compared`` with the reason):

* ``stab_rel_err``  — worst relative gap of a step's stabilisation wait
  (deterministic given the applied config: covers the lever apply, the
  service model and the arrival law at the step's clock);
* ``clock_rel_err`` — worst relative gap of a cluster's clock after a step
  (loading time plus the window's tick geometry and truncation);
* ``p99_gap``       — worst step's |log| ratio of the fleet-median window
  p99 (the tick recurrence and the lane p99);
* ``reward_gap``    — worst step's relative gap of the fleet-median reward
  (window mean, p99 hinge and breach fraction);
* ``loss_gap``      — worst update's gap of the policy loss, against the
  mean size of the terms the loss averages (the loss itself sits near 0);
* ``grad_gap``      — worst leaf's gap of the first gradient's norm, read
  from the optimizer's moments after one update;
* ``change_gap``    — worst leaf's gap of the parameter change's norm after
  the checked updates;
* ``encode_abs_err`` — worst gap of a state feature the policy acted on
  (every feature of the first step, the lever fractions of every step of
  the first update), against the reference encoder;
* ``decode_rel_err`` — worst relative gap of an applied lever value
  against the reference's decode of the bin the action moves to (a step
  that applied another lever or direction than its action names reads
  infinite);
* ``act_z``         — how far the actions of the checked updates lie from
  the policy's own draw: the summed log-likelihood of the actions under the
  reference policy at the reference's weights, less its expectation, in
  standard deviations;
* ``apply_errors``  — steps whose applied config moved a lever other than
  the one the action names (exact: limit 0);
* serve cells also ``promoted_breached`` (promotions of a config that
  breached the SLO in canary) and ``restore_errors`` (a promotion's
  checkpoint that does not restore the promoted incumbent), both exact.

A number that is not finite counts as failing.
"""
from __future__ import annotations

import math

import numpy as np

from reference import (Precision, action_logp, bin_of, decode_lever,
                       encode_states, lever_frac, policy_update, replay_steps,
                       step_bin)


def _finite_or_inf(x: float) -> float:
    x = float(x)
    return x if math.isfinite(x) else math.inf


def step_records(records: list, n: int, s: int, units: int) -> list:
    """Records of ``units`` updates (or cycles), each cluster-major (N, S),
    as a step-major list of per-step record lists over the clusters."""
    steps = []
    for u in range(units):
        chunk = records[u * n * s:(u + 1) * n * s]
        for t in range(s):
            steps.append([chunk[i * s + t] for i in range(n)])
    return steps


def apply_errors(records: list, configs0: list, ranked: list, *, n: int,
                 s: int, units: int) -> int:
    """Steps whose applied config moved a lever other than the one the
    action names, or moved a lever outside the action set."""
    prev = [configs0[i] for i in range(n)]
    errors = 0
    for st in step_records(records, n, s, units):
        for i, r in enumerate(st):
            moved = [k for k, v in r.config.items() if prev[i].get(k) != v]
            if (set(r.config) != set(prev[i])
                    or any(k != r.lever for k in moved)
                    or r.lever not in ranked):
                errors += 1
            prev[i] = r.config
    return errors


def replay(deploy: dict, traffic: dict, records: list, configs0: list, *,
           n: int, s: int, rng, q: Precision) -> dict:
    """The reference's (N, steps) arrays for the first update's (or
    cycle's) steps, replayed from the configs and loading times the program
    applied, starting from the configs deployed before them. Only the first
    is replayed: after it the §2.4.1 adaptation moves the bins, and the
    program then runs other values than the configs it reports (PERF.md,
    Open question 1)."""
    steps = step_records(records, n, s, 1)
    inputs = [{"configs": [r.config for r in st],
               "load_s": np.array([r.phases["loading_s"] for r in st])}
              for st in steps]
    # a serve cycle spins its shadow replicas up with empty queues
    resets = {0} if traffic["kind"] == "serve_plane" else set()
    # the loop observes one window of the deployed configs before its first
    # step: a whole number of that config's ticks
    T_b0 = np.array([c["batch_interval_s"] for c in configs0], float)
    clock0 = np.maximum(np.round(float(deploy["window_s"]) / T_b0), 1.0) * T_b0
    return replay_steps(deploy, inputs,
                        lanes=int(traffic["p99_lanes_per_tick"]), rng=rng,
                        q=q, clock0=clock0, reset_each=resets)


def simulation_numbers(deploy: dict, traffic: dict, records: list,
                       configs0: list, *, n: int, s: int, rng) -> dict:
    """stab/clock/p99/reward numbers over the first update's (or cycle's)
    steps, against the float64 reference."""
    steps = step_records(records, n, s, 1)
    ref = replay(deploy, traffic, records, configs0, n=n, s=s, rng=rng,
                 q=Precision("float64"))
    prog = {
        "stab_s": np.array([[st[i].phases["stabilisation_s"] for st in steps]
                            for i in range(n)]),
        "clock_s": np.array([[st[i].clock_s for st in steps]
                             for i in range(n)]),
        "p99_ms": np.array([[st[i].p99_ms for st in steps]
                            for i in range(n)]),
        "reward": np.array([[st[i].reward for st in steps]
                            for i in range(n)]),
    }
    return compare_simulation(prog, ref)


def compare_simulation(prog: dict, ref: dict) -> dict:
    """The four simulation numbers from (K, steps) program and reference
    arrays of ``stab_s``, ``clock_s``, ``p99_ms`` and ``reward``."""
    def rel(a, b):
        return np.abs(a - b) / np.maximum(np.abs(b), 1e-12)

    with np.errstate(all="ignore"):
        stab = np.max(rel(prog["stab_s"], ref["stab_s"]))
        clock = np.max(rel(prog["clock_s"], ref["clock_s"]))
        p99 = np.max(np.abs(np.log(np.median(prog["p99_ms"], axis=0)
                                   / np.median(ref["p99_ms"], axis=0))))
        mr = np.median(ref["reward"], axis=0)
        rew = np.max(np.abs(np.median(prog["reward"], axis=0) - mr)
                     / np.maximum(np.abs(mr), 1e-2))
    return {"stab_rel_err": _finite_or_inf(stab),
            "clock_rel_err": _finite_or_inf(clock),
            "p99_gap": _finite_or_inf(p99),
            "reward_gap": _finite_or_inf(rew)}


def act_reference(deploy: dict, enc: dict, actions, configs0: list, *,
                  q: Precision) -> dict:
    """What the first update's (or cycle's) steps should have seen and
    applied, from the encoder's inputs before its first step (``enc``:
    the per-node metrics and the running range) and its actions (N, S):
    the first step's states (N, D), every step's lever fractions (N, S, L),
    and each step's lever, direction and decoded value (N, S)."""
    space, ranked = deploy["lever_space"], deploy["ranked_levers"]
    actions = np.asarray(actions)
    n, s = actions.shape
    bins = np.array([[bin_of(space[l], c[l], q) for l in ranked]
                     for c in configs0[:n]])
    fracs = np.zeros((n, s, len(ranked)))
    levers = np.empty((n, s), object)
    values = np.zeros((n, s))
    for t in range(s):
        fracs[:, t] = [[lever_frac(space[l], b[j]) for j, l in enumerate(ranked)]
                       for b in bins]
        for i in range(n):
            j, d = divmod(int(actions[i, t]), 2)
            lever = space[ranked[j]]
            bins[i, j] = step_bin(lever, int(bins[i, j]), 1 - 2 * d)
            levers[i, t] = ranked[j]
            values[i, t] = float(decode_lever(lever, int(bins[i, j]), q))
    states0, _, _ = encode_states(enc["per_node"], enc["lo"], enc["hi"],
                                  fracs[:, 0], q)
    return {"states0": states0, "fracs": fracs, "levers": levers,
            "directions": 1 - 2 * (actions % 2), "values": values}


def act_program(records: list, states, *, n: int, s: int,
                ranked: list) -> dict:
    """The same arrays as the timed path produced them: the states its
    policy acted on and the configs its steps applied."""
    steps = step_records(records, n, s, 1)
    states = np.asarray(states, np.float64)
    return {"states0": states[:, 0],
            "fracs": states[:, :, -len(ranked):],
            "levers": np.array([[st[i].lever for st in steps]
                                for i in range(n)], object),
            "directions": np.array([[st[i].direction for st in steps]
                                    for i in range(n)]),
            "values": np.array([[float(st[i].config[st[i].lever])
                                 for st in steps] for i in range(n)])}


def compare_act(prog: dict, ref: dict) -> dict:
    """``encode_abs_err`` and ``decode_rel_err`` from ``act_program`` and
    ``act_reference`` arrays."""
    with np.errstate(all="ignore"):
        enc = max(np.max(np.abs(prog["states0"] - ref["states0"])),
                  np.max(np.abs(prog["fracs"] - ref["fracs"])))
        dec = np.max(np.abs(prog["values"] - ref["values"])
                     / np.maximum(np.abs(ref["values"]), 1e-12))
    if (np.any(prog["levers"] != ref["levers"])
            or np.any(prog["directions"] != ref["directions"])):
        dec = math.inf
    return {"encode_abs_err": _finite_or_inf(enc),
            "decode_rel_err": _finite_or_inf(dec)}


def act_z(init: dict, batches: list, *, f: float, warmup: int) -> float:
    """|z| of the checked updates' actions under the policy's own draw: the
    reference follows the updates from the same initial weights on the
    program's batches, and at each update scores the actions taken by
    their log-likelihood; the sum, less its expectation (minus the summed
    entropy), over its standard deviation."""
    q = Precision("float64")
    p = {k: np.asarray(v, np.float64) for k, v in init.items()}
    nu = {k: np.zeros_like(v) for k, v in p.items()}
    num = var = 0.0
    for k, (states, actions, rewards) in enumerate(batches):
        logp = action_logp(p, states, q, exploit=k >= warmup, f=f)
        prob = np.exp(logp)
        ent = -(prob * logp).sum(-1)
        chosen = np.take_along_axis(logp, actions[..., None], -1)[..., 0]
        num += float((chosen + ent).sum())
        var += float(((prob * logp ** 2).sum(-1) - ent ** 2).sum())
        p, nu, *_ = policy_update(p, nu, states, actions, rewards, q=q)
    return _finite_or_inf(abs(num) / math.sqrt(max(var, 1e-300)))


def update_numbers(init: dict, batches: list, losses: list, nu1: dict,
                   params_end: dict) -> dict:
    """loss/grad/change numbers: the reference follows the checked updates
    from the same initial weights on the program's own batches."""
    q = Precision("float64")
    p = {k: np.asarray(v, np.float64) for k, v in init.items()}
    nu = {k: np.zeros_like(v) for k, v in p.items()}
    ref_losses, scales, g1 = [], [], None
    for states, actions, rewards in batches:
        p, nu, grads, loss, scale = policy_update(p, nu, states, actions,
                                                  rewards, q=q)
        g1 = grads if g1 is None else g1
        ref_losses.append(loss)
        scales.append(scale)
    loss_gap = max(abs(a - b) / max(c, 1e-12)
                   for a, b, c in zip(losses, ref_losses, scales))
    gn_ref = {k: float(np.linalg.norm(v)) for k, v in g1.items()}
    gn_prog = {k: float(np.sqrt(np.sum(np.asarray(v, np.float64)) / 0.1))
               for k, v in nu1.items()}
    g_med = float(np.median(list(gn_ref.values())))
    moving = [k for k in gn_ref if gn_ref[k] >= 1e-3 * g_med]
    grad_gap = max(abs(gn_prog[k] - gn_ref[k]) / max(gn_ref[k], g_med)
                   for k in moving)
    ch_ref = {k: float(np.linalg.norm(p[k] - np.asarray(init[k], np.float64)))
              for k in moving}
    ch_prog = {k: float(np.linalg.norm(np.asarray(params_end[k], np.float64)
                                       - np.asarray(init[k], np.float64)))
               for k in moving}
    c_med = float(np.median(list(ch_ref.values())))
    change_gap = max(abs(ch_prog[k] - ch_ref[k]) / max(ch_ref[k], c_med)
                     for k in moving)
    return {"loss_gap": _finite_or_inf(loss_gap),
            "grad_gap": _finite_or_inf(grad_gap),
            "change_gap": _finite_or_inf(change_gap)}


def judge(numbers: dict, limits: dict,
          not_compared=()) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) — every number at or under its
    limit; a number without a limit, or a limit without a number, fails.
    Numbers named in ``not_compared`` are left out."""
    numbers = {k: v for k, v in numbers.items() if k not in not_compared}
    checks = {}
    ok = set(numbers) == set(limits)
    for name in sorted(set(numbers) | set(limits)):
        v = numbers.get(name, math.inf)
        lim = limits.get(name, -math.inf)
        checks[name] = {"value": v, "limit": lim}
        ok &= v <= lim
    return bool(ok), checks
