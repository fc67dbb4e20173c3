"""REINFORCE policy-gradient configurator (paper §2.4.2, §3, Algorithm 1).

* Policy network: MLP with ONE fully-connected hidden layer of 20 neurons
  (paper §3) over the flattened heat-map state; softmax over actions.
* Actions: (lever, direction) pairs restricted to the Lasso-selected levers —
  2 actions per lever (increase / decrease its discretised value).
* Exploitation factor f: with probability f the action is restricted to the
  TOP-ranKED lever (its two directions re-normalised); with 1-f the policy's
  full distribution is sampled (paper §2.4.2 last para / §4.5).
* Training: adapted REINFORCE with a per-step baseline averaged across the
  N episodes of the batch (Algorithm 1), gamma defaults to 1 so the return
  equals (negative) summed latency; optimiser rmsprop(lr=1e-3) (paper §3).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.monitoring.spans import count, span
from repro.optim import rmsprop

PyTree = Any


def init_policy(state_dim: int, n_actions: int, key: jax.Array,
                hidden: int = 20) -> PyTree:
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (state_dim, hidden)) / np.sqrt(state_dim),
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(k2, (hidden, n_actions)) / np.sqrt(hidden),
        "b2": jnp.zeros((n_actions,)),
    }


@jax.jit
def policy_logits(params: PyTree, state: jnp.ndarray) -> jnp.ndarray:
    h = jnp.tanh(state @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


@jax.jit
def policy_probs(params: PyTree, state: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.softmax(policy_logits(params, state))


#: vmapped action distribution over a fleet of per-cluster states (N, state_dim)
#: -> (N, n_actions); one device dispatch for the whole episode batch.
policy_probs_batch = jax.jit(jax.vmap(policy_probs, in_axes=(None, 0)))


def _sample_actions(params: PyTree, states: jnp.ndarray, key: jax.Array,
                    f: jnp.ndarray, exploit: bool,
                    greedy: bool = False, mask=None) -> jnp.ndarray:
    """Traceable core of ``sample_actions_device`` — also composed un-jitted
    into the fused episode program (repro.core.device_loop), where it is one
    stage of the per-step scan body rather than its own dispatch.
    ``greedy`` short-circuits to the argmax action (explore=False contract of
    the device training loop: deterministic, RNG-free, exactly replayable
    against the host oracle).

    ``mask`` (optional, bool (N, n_actions), True = allowed) is the §16
    safety-shield trust-region action mask: disallowed actions' logits drop
    to -1e9 before sampling (and before the greedy argmax), so probability
    mass reallocates to in-region moves instead of being wasted on moves the
    shield would clamp anyway. ``mask=None`` (the default) traces the exact
    pre-shield program — the shield-off bitwise pins depend on that. An
    all-masked row degenerates to a uniform draw over equal -1e9 logits;
    the shield's hard clamp downstream still confines the result. The
    update program deliberately stays unmasked: the shield is part of the
    environment as far as REINFORCE is concerned (DESIGN.md §16)."""
    logits = jax.vmap(lambda s: policy_logits(params, s))(states)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.float32(-1e9))
    if greedy:
        return jnp.argmax(logits, axis=-1)
    k_full, k_sub, k_gate = jax.random.split(key, 3)
    full_a = jax.random.categorical(k_full, logits, axis=-1)
    if not exploit:
        return full_a
    sub_a = jax.random.categorical(k_sub, logits[:, :2], axis=-1)
    gate = jax.random.uniform(k_gate, (states.shape[0],)) < f
    return jnp.where(gate, sub_a, full_a)


#: Policy forward pass + f-gated categorical sampling fused into ONE device
#: program (DESIGN.md §9): logits for all N cluster states, a Gumbel-max draw
#: over the full action space, a renormalised draw over the top lever's two
#: directions, and the per-row exploitation gate — no host round-trip between
#: acting and env stepping.
sample_actions_device = partial(jax.jit,
                                static_argnames=("exploit", "greedy"))(
                                    _sample_actions)


@jax.jit
def _batch_pg_loss(params: PyTree, states: jnp.ndarray, actions: jnp.ndarray,
                   advantages: jnp.ndarray, mask: jnp.ndarray,
                   entropy_beta: jnp.ndarray) -> jnp.ndarray:
    """-(1/N) sum_t log pi(a_t|s_t) * adv_t over a padded (N, T) batch,
    minus a small entropy bonus (premature-collapse guard)."""
    logits = jax.vmap(jax.vmap(lambda s: policy_logits(params, s)))(states)
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
    pg = -(chosen * advantages * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    ent = -(jnp.exp(logp) * logp).sum(-1)
    ent = (ent * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return pg - entropy_beta * ent


@dataclass
class Trajectory:
    states: list = field(default_factory=list)
    actions: list = field(default_factory=list)
    rewards: list = field(default_factory=list)

    def add(self, s, a, r) -> None:
        self.states.append(np.asarray(s, np.float32))
        self.actions.append(int(a))
        self.rewards.append(float(r))

    def __len__(self) -> int:
        return len(self.actions)


def discounted_returns(rewards: Sequence[float], gamma: float) -> np.ndarray:
    out = np.zeros(len(rewards), np.float32)
    acc = 0.0
    for t in range(len(rewards) - 1, -1, -1):
        acc = rewards[t] + gamma * acc
        out[t] = acc
    return out


def discounted_returns_device(rewards: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """``discounted_returns`` over a padded (N, T) batch as a reverse
    ``lax.scan`` — padded (reward 0) tail steps contribute nothing, so the
    masked result equals per-episode host discounting."""

    def step(acc, r):
        acc = r + gamma * acc
        return acc, acc

    _, out = jax.lax.scan(step, jnp.zeros(rewards.shape[0], rewards.dtype),
                          rewards.T[::-1])
    return out[::-1].T


#: number of times the whole-update program was traced (all agents); the §10
#: no-retrace test pins that steady-state training never grows this.
UPDATE_TRACE_COUNT = [0]


def _update_step(params: PyTree, opt_state: PyTree, states: jnp.ndarray,
                 actions: jnp.ndarray, rewards: jnp.ndarray,
                 mask: jnp.ndarray, *, opt, gamma: float,
                 entropy_beta: float):
    """One whole Algorithm-1 policy update as a single traced program:
    returns-discounting, the across-episode per-step baseline, masked
    advantage scale-normalisation, the policy gradient and the rmsprop step
    — nothing leaves the device (DESIGN.md §10). Jitted per agent (opt/gamma
    close over the trace)."""
    UPDATE_TRACE_COUNT[0] += 1          # side effect at trace time only
    returns = discounted_returns_device(rewards, gamma)
    # baseline b_t = mean over episodes of v_t at the same step
    denom = jnp.maximum(mask.sum(axis=0), 1.0)
    baseline = (returns * mask).sum(axis=0) / denom
    adv = (returns - baseline[None, :]) * mask
    # scale-normalise advantages, but floor the divisor at a fraction of
    # the reward magnitude: when rewards plateau (std -> 0) a bare /std
    # would amplify pure noise into full-strength updates.
    msum = jnp.maximum(mask.sum(), 1.0)
    mean_adv = adv.sum() / msum
    std = jnp.sqrt(jnp.maximum(
        (((adv - mean_adv) ** 2) * mask).sum() / msum, 0.0))
    ret_mean = (returns * mask).sum() / msum
    scale = jnp.maximum(jnp.maximum(std, 0.05 * jnp.abs(ret_mean)),
                        jnp.float32(1e-8))
    adv = adv / scale
    beta = jnp.asarray(entropy_beta, jnp.float32)
    grads = jax.grad(_batch_pg_loss)(params, states, actions, adv, mask, beta)
    params, opt_state = opt.update(grads, opt_state, params)
    loss = _batch_pg_loss(params, states, actions, adv, mask, beta)
    first = (returns[:, 0] * mask[:, 0]).sum() \
        / jnp.maximum(mask[:, 0].sum(), 1.0)
    return params, opt_state, loss, first


class ReinforceAgent:
    """The paper's configurator: acts on a state, learns from episode batches."""

    def __init__(
        self,
        state_dim: int,
        lever_names: Sequence[str],
        *,
        f_exploit: float = 0.8,
        gamma: float = 1.0,
        lr: float = 1e-3,
        hidden: int = 20,
        seed: int = 0,
        entropy_beta: float = 0.01,
        f_warmup_updates: int = 2,
    ):
        self.lever_names = list(lever_names)  # Lasso order: [0] = top lever
        self.n_actions = 2 * len(self.lever_names)
        self.state_dim = state_dim
        self.f = f_exploit
        self.gamma = gamma
        self.entropy_beta = entropy_beta
        self.f_warmup_updates = f_warmup_updates
        self.n_updates = 0
        self._rng = np.random.default_rng(seed)
        self._act_key = jax.random.PRNGKey(seed ^ 0x5EED)
        self._act_draws = 0
        self.params = init_policy(state_dim, self.n_actions,
                                  jax.random.PRNGKey(seed), hidden)
        self.opt = rmsprop(lr=lr)
        self.opt_state = self.opt.init(self.params)
        self._grad = jax.jit(jax.grad(_batch_pg_loss))
        #: the whole-update step with the optimiser and hyper-parameters
        #: bound but NOT jitted: the epoch mega-scan (device_loop.run_epoch)
        #: composes it as one stage of its scan body, so K policy updates
        #: trace into a single device program
        self._update_step = partial(
            _update_step, opt=self.opt, gamma=gamma,
            entropy_beta=entropy_beta)
        #: the whole-update device program; one jit cache per agent (the
        #: optimiser and hyper-parameters close over the trace)
        self._update_jit = jax.jit(self._update_step)

    # -- acting --------------------------------------------------------------
    def action_decode(self, a: int) -> tuple[str, int]:
        """action id -> (lever name, direction ±1)."""
        lever = self.lever_names[a // 2]
        direction = 1 if a % 2 == 0 else -1
        return lever, direction

    def act(self, state: np.ndarray, *, explore: bool = True) -> int:
        """Paper §2.4.2: 'the most relevant levers are preferentially used
        (the top lever is used f% of the time), but the other levers will
        also be used occasionally (1-f)'. Exploitation confines the action to
        the TOP-RANKED lever's two directions, renormalising the policy over
        them — the direction stays stochastic, so every step carries a
        learning signal; with 1-f the full softmax is sampled."""
        probs = np.asarray(policy_probs(self.params, jnp.asarray(state, jnp.float32)))
        probs = probs / probs.sum()
        if self.exploit_ready(explore=explore) and self._rng.uniform() < self.f:
            sub = probs[:2] + 1e-9  # actions 0/1 = top lever's +/- directions
            return int(self._rng.choice(2, p=sub / sub.sum()))
        return int(self._rng.choice(self.n_actions, p=probs))

    def act_batch(self, states: np.ndarray, *, explore: bool = True,
                  greedy: bool = False, mask=None) -> np.ndarray:
        """Sample one action per fleet cluster from (N, state_dim) states.

        The policy forward pass is a single vmapped dispatch
        (``policy_probs_batch``); the f-exploitation gate and the categorical
        draw are vectorised inverse-CDF sampling, so a fleet step costs one
        network evaluation instead of N (Algorithm 1's episode batch runs as
        N parallel episodes — see Configurator.run_fleet_episodes).

        ``mask`` (bool (N, n_actions), True = allowed) is the §16 shield's
        trust-region action mask — the host twin of ``_sample_actions``'s
        masked logits: disallowed actions get zero probability and the rest
        renormalise; an all-masked row degenerates to uniform (the hard
        clamp downstream confines it regardless)."""
        states = np.asarray(states, np.float32)
        probs = np.asarray(policy_probs_batch(self.params, jnp.asarray(states)))
        if mask is not None:
            probs = np.where(mask, probs, 0.0)
            s = probs.sum(axis=1, keepdims=True)
            probs = np.where(s > 0.0, probs / np.maximum(s, 1e-12),
                             1.0 / probs.shape[1])
        probs = probs / probs.sum(axis=1, keepdims=True)
        if greedy:  # deterministic argmax (device-loop replay contract)
            return np.argmax(probs, axis=1).astype(np.int64)
        N = probs.shape[0]
        # inverse-CDF categorical sampling over the full action space
        u = self._rng.uniform(size=N)
        full_a = (np.cumsum(probs, axis=1) < u[:, None]).sum(axis=1)
        full_a = np.minimum(full_a, self.n_actions - 1)
        if not self.exploit_ready(explore=explore):
            return full_a.astype(np.int64)
        # exploitation: restrict to the top lever's two directions per row
        sub = probs[:, :2] + 1e-9
        sub = sub / sub.sum(axis=1, keepdims=True)
        u2 = self._rng.uniform(size=N)
        sub_a = (np.cumsum(sub, axis=1) < u2[:, None]).sum(axis=1)
        sub_a = np.minimum(sub_a, 1)
        gate = self._rng.uniform(size=N) < self.f
        return np.where(gate, sub_a, full_a).astype(np.int64)

    def act_batch_device(self, states, *, explore: bool = True,
                         greedy: bool = False, mask=None) -> jnp.ndarray:
        """``act_batch`` as one fused device program (threefry counter key):
        forward pass, f-exploitation gate and categorical draws never leave
        the device — the acting half of the device-resident episode step
        (Configurator.run_fleet_episodes over a jax/pallas FleetEnv).
        ``mask`` rides into the traced masked sampling (§16 shield)."""
        key = jax.random.fold_in(self._act_key, self._act_draws)
        self._act_draws += 1
        exploit = self.exploit_ready(explore=explore)
        return sample_actions_device(
            self.params, jnp.asarray(states, jnp.float32), key,
            jnp.float32(self.f), exploit, greedy=greedy,
            mask=None if mask is None else jnp.asarray(mask))

    def exploit_ready(self, *, explore: bool = True) -> bool:
        """The f-gate warm-up state the fused episode program bakes in as a
        static: exploitation only after ``f_warmup_updates`` policy updates."""
        return bool(explore and self.n_updates >= self.f_warmup_updates)

    # -- learning (Algorithm 1) -----------------------------------------------
    def update_batch_async(self, states, actions, rewards, mask=None):
        """``update_batch`` with the device dispatch decoupled from the host
        stat pulls: the jitted update program is enqueued immediately
        (params/opt state become its not-yet-ready device outputs — jax
        dispatch is async) and the returned thunk blocks on the reported
        scalars. The §11 double-buffer hook: the fused training loop runs
        its host-side record materialisation and §2.4.1 bin replay between
        dispatch and pull, overlapping the device update."""
        traces0 = UPDATE_TRACE_COUNT[0]
        with span("tune.dispatch.update") as sp:
            states = jnp.asarray(states, jnp.float32)
            actions = jnp.asarray(actions, jnp.int32)
            rewards = jnp.asarray(rewards, jnp.float32)
            if mask is None:
                mask = jnp.ones(actions.shape, jnp.float32)
            else:
                mask = jnp.asarray(mask, jnp.float32)
            self.params, self.opt_state, loss, first = self._update_jit(
                self.params, self.opt_state, states, actions, rewards, mask)
            traced = UPDATE_TRACE_COUNT[0] - traces0
            sp.set_metadata(traced=traced)
        count("tune.programs_traced", traced)
        self.n_updates += 1
        episodes = int(actions.shape[0])

        def stats() -> dict:
            return {"pg_loss": float(loss), "mean_return": float(first),
                    "episodes": episodes,
                    "steps": int(np.asarray(mask).sum())}

        return stats

    def adopt_update(self, params, opt_state, k: int = 1) -> None:
        """Adopt post-update params/optimizer leaves computed OUTSIDE
        ``update_batch`` — the epoch mega-scan runs ``k`` composed
        ``_update_step``s device-side and hands back only the final leaves;
        the exploit-warm-up bookkeeping still advances by ``k``."""
        self.params = params
        self.opt_state = opt_state
        self.n_updates += int(k)

    def update_batch(self, states, actions, rewards, mask=None) -> dict:
        """One REINFORCE batch update from device-resident (N, T) episode
        arrays — returns-discounting, per-step baseline, advantage
        normalisation and the rmsprop gradient step all run as ONE jitted
        program (``_update_step``); only the reported stats scalars are
        pulled to host. ``mask`` marks valid steps of ragged episode batches
        (defaults to all-valid, the fused device loop's shape)."""
        return self.update_batch_async(states, actions, rewards, mask)()

    def update(self, episodes: Sequence[Trajectory]) -> dict:
        """One REINFORCE batch update from N episodes; per-step baseline is
        the across-episode mean return at that step (Algorithm 1). Pads the
        host trajectories and steps through the SAME jitted update program
        the device-resident loop uses (``update_batch``) — one math path,
        two front-ends."""
        eps = [e for e in episodes if len(e)]
        if not eps:
            return {"pg_loss": 0.0, "mean_return": 0.0}
        N = len(eps)
        T = max(len(e) for e in eps)
        states = np.zeros((N, T, self.state_dim), np.float32)
        actions = np.zeros((N, T), np.int32)
        rewards = np.zeros((N, T), np.float32)
        mask = np.zeros((N, T), np.float32)
        for i, e in enumerate(eps):
            L = len(e)
            states[i, :L] = np.stack(e.states)
            actions[i, :L] = e.actions
            rewards[i, :L] = e.rewards
            mask[i, :L] = 1.0
        return self.update_batch(states, actions, rewards, mask)
