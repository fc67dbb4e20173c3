"""Plain reference of the tuning loop's semantics, for the benchmark's `correct`.

Written from the model's stated equations in straightforward numpy, one
statement at a time, and independent of the program under test: it imports
nothing from ``src/`` and reads nothing the program made except the answers
it checks (the applied configs, loading times, window statistics, rewards and
the policy-update batches the timed path produced). A workload law it does
not build in is the ``reference`` half of the cell's ``laws/<law>.py``, and a
configuration may bring its own copy of this module
(``compare.reference_for``).

Three parts:

* ``encode_states``, ``decode_lever`` and ``action_logp`` — the act half of
  a step: the fleet state encoder (running-range normalised per-node metric
  grids plus each ranked lever's bin fraction), the lever lattice (the
  deployment's lever space cut into its initial bins, an action's move
  along it, and the value a bin decodes to), and the policy's probability
  of each action (the f-gated draw of Algorithm 1);
* ``replay_steps`` — a cluster's shadow steps replayed through the queueing
  model: Kafka buffering during the config load, the stabilisation wait from
  the service-term change, the preroll + observation window of micro-batch
  ticks (retention cap, single server, in-flight cap, straggler and failure
  tails), and the window's statistics (analytic mean, lane-sampled p99,
  breach fraction, SLO reward). The stabilisation wait and the clock are
  deterministic given the applied config and loading time; the window
  statistics draw their own randomness and are compared as fleet medians.
* ``policy_update`` — one REINFORCE update (returns, per-step baseline,
  advantage scale normalisation, policy gradient with entropy bonus, by hand
  backpropagation through the one-hidden-layer tanh MLP) and the rmsprop
  step.

Every arithmetic statement goes through ``Precision`` so the same code runs
in float64 (the reference), float32, or bfloat16 (the control that stands in
for a lower-precision program).
"""
from __future__ import annotations

import numpy as np

import lookup

R2PI = float(np.sqrt(2.0 / np.pi))       # E|N(0,1)|

_REMAT = {"none": 1.0, "block": 1.12, "full": 1.35}
_KV_BLOCK = {64: 0.28, 128: 0.18, 256: 0.22, 512: 0.3}
_TP_COMPUTE = {4: 1.18, 8: 1.06, 16: 1.0, 32: 1.07}
_COMPRESSION = {"int8": 0.55, "topk": 0.4}


class Precision:
    """Rounds every intermediate to one working precision (kept in float64
    containers): ``float64`` is exact, ``float32`` and ``bfloat16`` round."""

    def __init__(self, name: str = "float64"):
        self.name = name
        if name == "float64":
            self.dt = None
        elif name == "float32":
            self.dt = np.float32
        elif name == "bfloat16":
            import ml_dtypes
            self.dt = ml_dtypes.bfloat16
        else:
            raise ValueError(f"unknown precision {name!r}")

    def __call__(self, x) -> np.ndarray:
        x = np.asarray(x, np.float64)
        if self.dt is None:
            return x
        return x.astype(self.dt).astype(np.float64)


# --------------------------------------------------------------------------
# the deployment's service model (per-micro-batch seconds)
# --------------------------------------------------------------------------

def pack_levers(configs) -> dict:
    """The levers the service model reads, as (K,) float64 arrays."""
    def col(f):
        return np.array([float(f(c)) for c in configs], np.float64)

    return {
        "T_b": col(lambda c: c["batch_interval_s"]),
        "max_b": col(lambda c: c["max_batch_events"]),
        "eff_q": col(lambda c: 1.0 if c["attn_block_q"] == 128 else 0.88),
        "eff_k": col(lambda c: 1.0 if c["attn_block_k"] == 128 else 0.9),
        "eff_dtype": col(lambda c: 1.0 if c["compute_dtype"] == "bf16" else 0.5),
        "remat": col(lambda c: _REMAT[c["remat_policy"]]),
        "kvp": col(lambda c: _KV_BLOCK[int(c["kv_block"])]),
        "tp": col(lambda c: int(c["model_axis_size"])),
        "tp_compute": col(lambda c: _TP_COMPUTE[int(c["model_axis_size"])]),
        "compression": col(lambda c: _COMPRESSION.get(c["grad_compression"], 1.0)),
        "mb": col(lambda c: int(c["microbatch_count"])),
        "expert_parallel": col(lambda c: bool(c["expert_parallel"])),
        "driver_mem": col(lambda c: c["driver_memory_gb"]),
        "arena": col(lambda c: c["allocator_arena_mb"]),
        "sink": col(lambda c: int(c["sink_partitions"])),
        "prefetch": col(lambda c: max(int(c["prefetch_depth"]), 0)),
        "backup": col(lambda c: bool(c["backup_tasks"])),
        "straggler_timeout": col(lambda c: c["straggler_timeout_s"]),
        "fail_frac": col(lambda c: c["failure_inject_frac"]),
        "inflight": col(lambda c: c["max_inflight_batches"]),
    }


def service_seconds(cc: dict, batch, size, deploy: dict, q: Precision):
    """Seconds to serve one micro-batch of ``batch`` events of ``size`` MB."""
    cl, mdl = deploy["cluster"], deploy["served_model"]
    chips = (cl["n_nodes"] - 1) * cl["chips_per_worker"]
    tokens = q(q(batch * size) * mdl["tokens_per_mb"])
    eff = q(q(q(cl["base_mfu"] * cc["eff_q"]) * cc["eff_k"]) * cc["eff_dtype"])
    t_comp = q(q(q(tokens * mdl["flops_per_token"]) * cc["remat"])
               / q(chips * mdl["peak_flops_per_chip"] * eff))
    kv_gb = q(q(tokens * mdl["kv_bytes_per_token"]) / 1e9)
    mem_frac = q(np.minimum(q(kv_gb / (chips * cl["hbm_gb_per_chip"]))
                            + cc["kvp"], 1.5))
    pen = q(1.0 + q(np.maximum(mem_frac - 1.0, 0.0) * 2.0))
    coll = q(q(cl["collective_frac"] * t_comp) * q(np.sqrt(q(cc["tp"] / 16.0))))
    coll = q(coll * cc["compression"])
    coll = q(coll / q(1.0 + 0.45 * (cc["mb"] - 1.0)))
    if mdl.get("moe", False):
        moe = cc["expert_parallel"] != 0
        t_comp = q(np.where(moe, t_comp * 0.92, t_comp))
        coll = q(np.where(moe, coll * 1.15, coll))
    t_comp = q(t_comp * cc["tp_compute"])
    ovh = q(cl["dispatch_overhead_s"] * q(1.0 + 0.12 * (cc["mb"] - 1.0)))
    ovh = q(ovh + q(q(cl["driver_gc_coeff"] / np.maximum(cc["driver_mem"], 1.0))
                    * 0.1))
    ovh = q(ovh + q(0.12 * np.maximum(
        q(np.log2(q(512.0 / np.maximum(cc["arena"], 32.0)))), 0.0)))
    ovh = q(ovh + q(0.25 / np.maximum(cc["sink"], 1.0)) + q(0.004 * cc["sink"]))
    ovh = q(ovh * q(0.45 + q(0.55 / (1.0 + cc["prefetch"]))))
    return q(q(ovh + q(t_comp * pen)) + coll)


def arrival_law(law: dict, t, q: Precision, bench):
    """(events/s, MB/event) of a workload law at times ``t``. A law that is
    not built in is the ``reference`` half of ``laws/<law>.py`` under the
    cell's benchmark directory ``bench``."""
    kind = law["law"]
    if kind == "poisson":
        return (np.full(np.shape(t), float(law["rate"])),
                np.full(np.shape(t), float(law["event_size_mb"])))
    if kind == "diurnal":
        phase = q(q(2.0 * np.pi * q(t)) / law["day_s"])
        rate = q(law["rate"] * q(1.0 + q(law["amplitude"] * q(np.sin(phase)))))
        return rate, np.full(np.shape(t), float(law["event_size_mb"]))
    if kind == "switching":
        use_a = np.mod(np.floor(q(t) / law["period_s"]), 2.0) < 0.5
        ra, sa = arrival_law(law["a"], t, q, bench)
        rb, sb = arrival_law(law["b"], t, q, bench)
        return np.where(use_a, ra, rb), np.where(use_a, sa, sb)
    return lookup.load(bench, "laws", kind).reference(law, t, q)


# --------------------------------------------------------------------------
# the act half of a step: encode, lever lattice, policy draw
# --------------------------------------------------------------------------

def lever_edges(lever: dict, q: Precision) -> np.ndarray:
    """A continuous lever's initial bin edges, in its linear (``float``,
    ``int``) or log (``log``) coordinate: ``bins`` equal bins over
    [``lo``, ``hi``]."""
    lo, hi = float(lever["lo"]), float(lever["hi"])
    if lever["kind"] == "log":
        lo, hi = np.log(lo), np.log(hi)
    return q(np.linspace(lo, hi, int(lever["bins"]) + 1))


def n_bins(lever: dict) -> int:
    return 2 if lever["kind"] == "bool" else int(lever["bins"])


def bin_of(lever: dict, value, q: Precision) -> int:
    """The bin a deployed value sits in (values outside the lattice clip to
    its end bins)."""
    if lever["kind"] == "bool":
        return int(bool(value))
    e = lever_edges(lever, q)
    v = float(value)
    v = np.log(v) if lever["kind"] == "log" else v
    v = min(max(v, e[0]), e[-1])
    return int(min(max(np.searchsorted(e, v, "right") - 1, 0),
                   n_bins(lever) - 1))


def step_bin(lever: dict, b: int, direction: int) -> int:
    """An action's move: one bin up or down, clipped to the lattice; a
    boolean lever toggles whatever the direction."""
    if lever["kind"] == "bool":
        return 1 - b
    return int(min(max(b + direction, 0), n_bins(lever) - 1))


def decode_lever(lever: dict, b: int, q: Precision):
    """The value bin ``b`` decodes to: its centre (in log space for a log
    lever), rounded for an integer lever."""
    if lever["kind"] == "bool":
        return bool(b)
    e = lever_edges(lever, q)
    mid = q(0.5 * q(e[b] + e[b + 1]))
    v = float(q(np.exp(mid))) if lever["kind"] == "log" else float(mid)
    return int(round(v)) if lever["kind"] == "int" else v


def lever_frac(lever: dict, b) -> np.ndarray:
    """A lever's state feature: its bin's position along the lattice."""
    return np.asarray(b, np.float64) / max(n_bins(lever) - 1, 1)


def encode_states(per_node, lo, hi, fracs, q: Precision):
    """(N, state_dim) fleet states: each selected metric's per-node window
    values, normalised by the fleet's running range (widened by this
    window) and laid on a square node grid, then the ranked levers' bin
    fractions. ``per_node`` is (N, nodes, M); ``lo``/``hi`` the running
    range before this window; ``fracs`` (N, L). Returns the states and the
    widened range."""
    raw = np.transpose(np.asarray(per_node, np.float64), (0, 2, 1))
    lo = np.minimum(np.asarray(lo, np.float64), raw.min(axis=(0, 2)))
    hi = np.maximum(np.asarray(hi, np.float64), raw.max(axis=(0, 2)))
    span = np.where(hi > lo, q(hi - lo), 1.0)
    lo_eff = np.where(np.isfinite(lo), lo, 0.0)
    normed = q(q(raw - lo_eff[None, :, None]) / span[None, :, None])
    normed = np.clip(np.nan_to_num(normed), 0.0, 1.0)
    N, M, nodes = normed.shape
    rows = int(np.ceil(np.sqrt(nodes)))
    cols = int(np.ceil(nodes / rows))
    grids = np.zeros((N, M, rows * cols))
    grids[:, :, :nodes] = normed
    states = np.concatenate([grids.reshape(N, -1),
                             q(np.asarray(fracs, np.float64))], axis=1)
    return states, lo, hi


def action_logp(params: dict, states, q: Precision, *, exploit: bool,
                f: float) -> np.ndarray:
    """(..., A) log-probability of each action under Algorithm 1's draw:
    the policy's softmax, or, once exploitation is on, with probability
    ``f`` a draw over the top lever's two directions alone (actions 0, 1)
    renormalised, and the full softmax otherwise."""
    _, logp = _forward({k: q(v) for k, v in params.items()}, q(states), q)
    if not exploit:
        return logp
    p = np.exp(logp)
    top = np.zeros_like(p)
    top[..., :2] = p[..., :2] / p[..., :2].sum(-1, keepdims=True)
    return np.log(q(f * top + (1.0 - f) * p))


# --------------------------------------------------------------------------
# the shadow steps, replayed
# --------------------------------------------------------------------------

def replay_steps(deploy: dict, steps: list, *, lanes: int, rng,
                 q: Precision, clock0, last_service=None,
                 reset_each: set = frozenset(), bench):
    """Replay K clusters through ``len(steps)`` shadow steps.

    ``steps[k]`` holds the step's applied configs (``configs``, K dicts)
    and loading seconds (``load_s``, (K,)). ``clock0`` is the clock before
    the first step; ``reset_each`` names the steps before which the queues
    start empty (a serve cycle spins its shadow replicas up fresh);
    ``bench`` is the cell's benchmark directory, where a workload law that
    is not built in is found.
    The window mean is analytic: each window tick's latency mixture mean,
    weighted by its sampled events.
    Returns per-step (K,) arrays: ``stab_s``, ``clock_s``, ``mean_ms``,
    ``p99_ms``, ``breach_frac``, ``reward``."""
    cl = deploy["cluster"]
    law = deploy["workload"]
    win = float(deploy["window_s"])
    T_cap = deploy.get("tick_budget")
    slo = float(deploy["slo_ms"])
    rw = deploy["reward"]
    K = len(steps[0]["configs"])
    clock = q(np.broadcast_to(np.asarray(clock0, np.float64), (K,)).copy())
    backlog = np.zeros(K)
    sfree = np.zeros(K)
    last = (np.full(K, np.nan) if last_service is None
            else np.asarray(last_service, np.float64).copy())
    out = {k: [] for k in ("stab_s", "clock_s", "mean_ms", "p99_ms",
                           "breach_frac", "reward")}
    slo_lo, slo_hi = cl["straggler_slow"]
    for k, st in enumerate(steps):
        if k in reset_each:
            backlog = np.zeros(K)
            sfree = np.zeros(K)
        cc = pack_levers(st["configs"])
        load = np.asarray(st["load_s"], np.float64)
        T_b = cc["T_b"]
        # ---- loading: Kafka buffers arrivals while the config deploys ----
        rate_now, _ = arrival_law(law, clock, q, bench)
        backlog = q(backlog + q(rate_now * load))
        clock = q(clock + load)
        sfree = q(np.maximum(sfree - load, 0.0))
        # ---- stabilisation wait from the service-term change ----
        rate_st, size_st = arrival_law(law, clock, q, bench)
        s_new = service_seconds(cc, q(np.minimum(q(rate_st * T_b),
                                                 cc["max_b"])),
                                size_st, deploy, q)
        prev = np.where(np.isnan(last), s_new, last)
        rel = q(np.abs(q(s_new - prev)) / np.maximum(prev, 1e-6))
        stab = q(np.clip(q(30.0 + q(240.0 * rel)), 30.0, 180.0))
        last = s_new
        # ---- window geometry (preroll + observation, tick budget) ----
        n_win = np.maximum(np.round(q(win / T_b)), 1.0)
        n_skip = np.maximum(np.round(q(stab / T_b)), 0.0)
        if T_cap is not None:
            n_win = np.minimum(n_win, T_cap)
            n_skip = np.minimum(n_skip, T_cap - n_win)
        n_ticks = n_skip + n_win
        T = int(n_ticks.max())
        # ---- the tick recurrence ----
        slow_cap = np.maximum(1.2, q(1.0 + q(cc["straggler_timeout"]
                                             / np.maximum(T_b, 1e-3))))
        inflight = q(np.maximum(cc["inflight"], 1.0) * T_b)
        w_sum = np.zeros(K)
        m_sum = np.zeros(K)
        n_win_ticks = np.zeros(K)
        n_breach = np.zeros(K)
        samples = []
        for t in range(T):
            active = t < n_ticks
            in_win = active & (t >= n_skip)
            times = q(clock + q(t * T_b))
            rate, size = arrival_law(law, times, q, bench)
            z = rng.standard_normal(K)
            arr = q(np.maximum(q(q(rate * T_b) * q(1.0 + cl["noise"] * z)), 0.0))
            age = q(backlog / np.maximum(rate, 1.0))
            blg = q(np.minimum(q(backlog + arr), q(rate * cl["retention_s"])))
            batch = q(np.minimum(blg, cc["max_b"]))
            service = service_seconds(cc, batch, size, deploy, q)
            strag = rng.random(K) < cl["straggler_prob"]
            raw = q(slo_lo + (slo_hi - slo_lo) * rng.random(K))
            slow = np.where(strag, np.where(cc["backup"] != 0, 1.1,
                                            np.minimum(raw, slow_cap)), 1.0)
            fail = rng.random(K) < cc["fail_frac"]
            slow = np.where(fail, q(slow * 2.0), slow)
            service = q(service * slow)
            start = np.maximum(T_b, sfree)
            sfree_new = q(q(np.minimum(q(start + service), q(T_b + inflight)))
                          - T_b)
            processed = np.where(service <= T_b, batch,
                                 q(batch * q(T_b / service)))
            after = q(np.maximum(q(blg - processed), 0.0))
            qd = q(q(start - T_b) + age)
            backlog = np.where(active, after, backlog)
            sfree = np.where(active, sfree_new, sfree)
            # window statistics: the latency of an event in this tick is
            # base + T_b·U(0,1) + 0.1·service·|N(0,1)|
            base_ms = q(q(qd + service) * 1000.0)
            a_ms = q(T_b * 1000.0)
            c_ms = q(100.0 * service)
            tick_mean = q(q(base_ms + q(0.5 * a_ms)) + q(R2PI * c_ms))
            n_s = np.clip(np.floor(batch), 1, 64)
            w = np.where(in_win, n_s, 0.0)
            w_sum += w
            m_sum = q(m_sum + q(w * tick_mean))
            n_win_ticks += in_win
            n_breach += in_win & (tick_mean > slo)
            n_l = np.where(in_win, np.minimum(n_s, lanes), 0).astype(int)
            lane = rng.random((K, lanes))
            zl = np.abs(rng.standard_normal((K, lanes)))
            lat = q(q(base_ms[:, None] + q(a_ms[:, None] * lane))
                    + q(c_ms[:, None] * zl))
            samples.append(np.where(np.arange(lanes)[None, :] < n_l[:, None],
                                    lat, np.nan))
        lat = np.concatenate(samples, axis=1)
        p99 = q(np.nanpercentile(lat, 99.0, axis=1))
        win_mean = q(m_sum / np.maximum(w_sum, 1e-9))
        bf = q(n_breach / np.maximum(n_win_ticks, 1.0))
        reward = q(q(q(-win_mean / 1000.0)
                     - q(rw["hinge_w"] * q(np.maximum(p99 - slo, 0.0) / 1000.0)))
                   - q(rw["breach_w"] * bf))
        clock = q(clock + q(n_ticks * T_b))
        for key, v in (("stab_s", stab), ("clock_s", clock),
                       ("mean_ms", win_mean), ("p99_ms", p99),
                       ("breach_frac", bf), ("reward", reward)):
            out[key].append(v)
    return {k: np.stack(v, axis=1) for k, v in out.items()}   # (K, steps)


# --------------------------------------------------------------------------
# the policy update
# --------------------------------------------------------------------------

def init_policy(state_dim: int, n_actions: int, rng, hidden: int = 20) -> dict:
    """Seeded initial policy weights, float32, in the paper's §3 shape."""
    return {
        "w1": (rng.standard_normal((state_dim, hidden))
               / np.sqrt(state_dim)).astype(np.float32),
        "b1": np.zeros(hidden, np.float32),
        "w2": (rng.standard_normal((hidden, n_actions))
               / np.sqrt(hidden)).astype(np.float32),
        "b2": np.zeros(n_actions, np.float32),
    }


def _forward(p: dict, states, q: Precision):
    h = q(np.tanh(q(q(states @ p["w1"]) + p["b1"])))
    logits = q(q(h @ p["w2"]) + p["b2"])
    zmax = logits.max(axis=-1, keepdims=True)
    lse = q(q(np.log(q(np.exp(q(logits - zmax))).sum(axis=-1, keepdims=True)))
            + zmax)
    logp = q(logits - lse)
    return h, logp


def _loss(p, states, onehot, adv, beta, q):
    h, logp = _forward(p, states, q)
    M = adv.size
    chosen = q((logp * onehot).sum(-1))
    pg = q(-q((chosen * adv).sum()) / M)
    prob = q(np.exp(logp))
    ent = q(-q((prob * logp).sum(-1)))
    return q(pg - q(beta * q(ent.sum() / M))), h, logp, prob, ent


def policy_update(params: dict, nu: dict, states, actions, rewards, *,
                  q: Precision, lr: float = 1e-3, decay: float = 0.9,
                  eps: float = 1e-8, beta: float = 0.01, gamma: float = 1.0):
    """One Algorithm-1 update on an (N, S) episode batch. Returns
    ``(params', nu', grads, loss', scale)``: ``loss'`` is the policy loss at
    the updated parameters, on the same advantages, and ``scale`` the mean
    size of the terms it averages, |log pi(a|s) · advantage|."""
    p = {k: q(v) for k, v in params.items()}
    states = q(states)
    rewards = q(rewards)
    N, S = actions.shape
    returns = np.zeros((N, S))
    acc = np.zeros(N)
    for t in range(S - 1, -1, -1):
        acc = q(rewards[:, t] + q(gamma * acc))
        returns[:, t] = acc
    baseline = q(returns.sum(axis=0) / N)
    adv = q(returns - baseline[None, :])
    M = float(N * S)
    mean_adv = q(adv.sum() / M)
    std = q(np.sqrt(q(q(q(adv - mean_adv) ** 2).sum() / M)))
    ret_mean = q(returns.sum() / M)
    scale = max(float(std), float(q(0.05 * abs(ret_mean))), 1e-8)
    adv = q(adv / scale)
    onehot = np.eye(p["b2"].size)[actions]
    _, h, logp, prob, ent = _loss(p, states, onehot, adv, beta, q)
    scale_terms = float(np.mean(np.abs((logp * onehot).sum(-1) * adv)))
    # d loss / d logits: policy-gradient term plus the entropy bonus
    dz = q(q(q(-adv[..., None] * q(onehot - prob))
             + q(beta * q(prob * q(logp + ent[..., None])))) / M)
    D = states.shape[-1]
    H = h.shape[-1]
    s2 = states.reshape(-1, D)
    h2 = h.reshape(-1, H)
    dz2 = dz.reshape(-1, dz.shape[-1])
    grads = {"w2": q(h2.T @ dz2), "b2": q(dz2.sum(0))}
    dh = q(dz2 @ p["w2"].T)
    dpre = q(dh * q(1.0 - q(h2 * h2)))
    grads["w1"] = q(s2.T @ dpre)
    grads["b1"] = q(dpre.sum(0))
    new_nu, new_p = {}, {}
    for k in p:
        new_nu[k] = q(q(decay * q(nu[k])) + q((1.0 - decay) * q(grads[k] ** 2)))
        new_p[k] = q(p[k] - q(q(lr * grads[k]) / q(np.sqrt(new_nu[k]) + eps)))
    loss_after = _loss(new_p, states, onehot, adv, beta, q)[0]
    return new_p, new_nu, grads, float(loss_after), scale_terms
