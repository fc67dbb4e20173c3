"""Compiled-tier pins (DESIGN.md §14): the fast path must BE the fast path.

``fleet_tick_window`` dispatches over three tiers — Mosaic (TPU),
``interpret`` (debug), ``xla`` (the compiled lowering off-TPU). This suite
pins the compiled tier four ways:

* tier resolution on CPU is ``xla``, never interpret, unless the debug
  override is explicitly set;
* the xla tier is **bitwise** identical to the interpret tier on a shared
  single-block shape — both run the literal ``_tick_step``/``_lane_stats``
  helpers, so agreement is exact, not statistical;
* ``REPRO_REQUIRE_COMPILED`` turns any interpret-tier trace into a hard
  error (the CI compiled-pallas job's no-silent-fallback guard), and the
  full fused training loop runs clean under it;
* the fused loop and the observe path on the compiled tier stay inside the
  chaos-harness statistical tolerances against the numpy oracle.

The pipelined actor/learner rides along: ``tune_pipelined(depth=1)`` must
be bitwise-equal to the sequential ``tune`` schedule (same dispatch order,
same RNG streams, same update inputs), and ``depth>=2`` — one update of
policy staleness — must stay statistically pinned to sequential.
"""
import numpy as np
import pytest

import jax

from chaos_harness import (assert_loop_equivalent,
                           assert_window_stats_equivalent,
                           collect_window_stats)
from repro.core.configurator import Configurator
from repro.data.workloads import PoissonWorkload, SwitchingWorkload
from repro.engine import FleetEnv
from repro.kernels.fleet_tick import (DISPATCH_COUNTS, fleet_tick_window,
                                      pack_tick_consts, pallas_mode)

METRICS = ["latency_p99_ms", "latency_mean_ms", "queue_depth",
           "device_util", "sched_queue_depth"]
LEVERS = ["max_batch_events", "prefetch_depth", "driver_memory_gb",
          "sink_partitions", "backup_tasks"]
FROZEN = dict(split_after=10**9, extend_after=10**9, merge_after=10**9)


@pytest.fixture(autouse=True)
def _compiled_tier(monkeypatch):
    """This suite pins the COMPILED tier: strip the debug/CI overrides so
    ``pallas_mode()`` resolves from the backend alone."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_REQUIRE_COMPILED", raising=False)
    monkeypatch.delenv("REPRO_FLEET_IMPL", raising=False)


def _wl(kind, i):
    if kind == "switching":
        return SwitchingWorkload(PoissonWorkload(6_000, 0.5),
                                 PoissonWorkload(12_000, 0.5),
                                 period_s=700.0 + 60.0 * i)
    return PoissonWorkload(10_000, 0.5)


def _fleet(backend, n, seed=0, kind="poisson"):
    return FleetEnv([_wl(kind, i) for i in range(n)],
                    seeds=[seed + i for i in range(n)], backend=backend)


def _cfgr(env, *, device_loop="on", seed=0, steps=3):
    return Configurator(env, METRICS, LEVERS, seed=seed,
                        steps_per_episode=steps, window_s=240.0,
                        device_loop=device_loop, bin_kw=FROZEN, mesh="off")


def _kernel_inputs(T, N, S, seed=0):
    """Shared random operand set at one (T, N, S) point, with real packed
    consts from a jax fleet of N clusters."""
    import jax.numpy as jnp

    env = _fleet("jax", N, seed=seed)
    cc = {k: jnp.asarray(v, jnp.float32) for k, v in env.packed().items()}
    mc = {k: jnp.asarray(np.asarray(v, np.float32))
          for k, v in env.mc.items()}
    consts = pack_tick_consts(cc, mc, env.spec, env.chips, xp=jnp)
    rng = np.random.default_rng(seed)
    ops = dict(
        state=jnp.zeros((2, N)),
        consts=consts,
        rate=jnp.asarray(rng.uniform(5e3, 2e4, (T, N)), jnp.float32),
        size=jnp.asarray(rng.uniform(0.2, 1.0, (T, N)), jnp.float32),
        z=jnp.asarray(rng.standard_normal((T, N)), jnp.float32),
        u_strag=jnp.asarray(rng.random((T, N)), jnp.float32),
        u_raw=jnp.asarray(rng.random((T, N)), jnp.float32),
        u_fail=jnp.asarray(rng.random((T, N)), jnp.float32),
        active=jnp.ones((T, N), jnp.float32),
        u_wait=jnp.asarray(rng.random((T, S, N)), jnp.float32),
        z2a=jnp.asarray(np.abs(rng.standard_normal((T, S, N))),
                        jnp.float32))
    kw = dict(noise=env.spec.noise, retention_s=env.spec.retention_s,
              straggler_prob=env.spec.straggler_prob,
              slo=env.spec.straggler_slow[0],
              shi=env.spec.straggler_slow[1])
    return ops, kw


# ------------------------------------------------------------ tier dispatch
def test_cpu_tier_resolves_to_xla_unless_forced(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert pallas_mode() == "xla"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert pallas_mode() == "interpret"


def test_xla_tier_bitwise_equals_interpret_tier():
    """The exact-parity point of §14: one (N, T) shape small enough for a
    single grid cell, both tiers on identical operands. The tiers share
    ``_tick_step``/``_lane_stats`` verbatim, so state, ys, the per-tick
    lane statistics AND the streaming top-K head agree to the bit."""
    ops, kw = _kernel_inputs(T=10, N=8, S=16)
    before = dict(DISPATCH_COUNTS)
    a = fleet_tick_window(*ops.values(), **kw, p99_k=4, block_n=8,
                          mode="interpret")
    b = fleet_tick_window(*ops.values(), **kw, p99_k=4, block_n=8,
                          mode="xla")
    for name, x, y in zip(("state", "ys", "stats", "head"), a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape
        assert np.array_equal(x, y, equal_nan=True), (
            name, np.nanmax(np.abs(x - y)))
    # both tiers actually traced (fresh shape) and were counted
    assert DISPATCH_COUNTS["interpret"] == before["interpret"] + 1
    assert DISPATCH_COUNTS["xla"] == before["xla"] + 1


def test_tiled_interpret_tier_bitwise_equals_xla_tier(monkeypatch):
    """A window longer than one VMEM tile runs as several tick tiles on the
    Pallas tiers, the state and head carried across them in the resident
    output blocks — still bitwise-equal to the untiled xla tier. A small
    VMEM budget forces the tiling on a fresh shape."""
    from repro.kernels import fleet_tick

    T, N, S = 48, 16, 8
    monkeypatch.setattr(fleet_tick, "_TILE_VMEM_BYTES",
                        16 * 2 * 4 * 8 * (21 + 2 * S))
    assert fleet_tick.tick_tile(T, S, 8) == 16
    ops, kw = _kernel_inputs(T=T, N=N, S=S)
    a = fleet_tick_window(*ops.values(), **kw, p99_k=4, block_n=8,
                          mode="interpret")
    b = fleet_tick_window(*ops.values(), **kw, p99_k=4, block_n=8,
                          mode="xla")
    for name, x, y in zip(("state", "ys", "stats", "head"), a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y),
                              equal_nan=True), name


@pytest.mark.parametrize("L", [8, 64])
def test_lane_networks_match_numpy(L):
    """The rotation-and-mask networks the tiers share: bitonic sort in both
    directions, the bitonic head merge (top K of head ∪ tick lanes) and the
    pairwise tree sum, against numpy on lanes with -inf padding."""
    import jax.numpy as jnp

    from repro.kernels.fleet_tick import _merge_head, _sort_axis0, _sum0

    # the rotations have no eager rule: the networks only ever run traced
    _sort_axis0 = jax.jit(_sort_axis0, static_argnames="descending")
    _merge_head, _sum0 = jax.jit(_merge_head), jax.jit(_sum0)
    rng = np.random.default_rng(L)
    x = rng.standard_normal((L, 3, 5)).astype(np.float32)
    x[rng.random(x.shape) < 0.2] = -np.inf
    asc = np.sort(x, axis=0)
    assert np.array_equal(np.asarray(_sort_axis0(jnp.asarray(x))), asc)
    assert np.array_equal(
        np.asarray(_sort_axis0(jnp.asarray(x), descending=True)), asc[::-1])
    head = np.sort(rng.standard_normal((L, 3, 5)).astype(np.float32), axis=0)
    merged = _merge_head(jnp.asarray(head), jnp.asarray(asc[::-1]))
    top = np.sort(np.concatenate([head, x]), axis=0)[-L:]
    assert np.array_equal(np.asarray(merged), top)
    y = rng.random((L, 4)).astype(np.float32)
    tree = y
    while tree.shape[0] > 1:
        tree = tree[0::2] + tree[1::2]
    assert np.array_equal(np.asarray(_sum0(jnp.asarray(y))), tree[0])


def test_require_compiled_turns_interpret_trace_into_error(monkeypatch):
    """The CI job's guard: with REPRO_REQUIRE_COMPILED set, an interpret
    trace raises instead of silently running the debug tier; the compiled
    tier still traces fine. Fresh shape — the guard fires at trace time."""
    ops, kw = _kernel_inputs(T=6, N=4, S=8)
    monkeypatch.setenv("REPRO_REQUIRE_COMPILED", "1")
    with pytest.raises(RuntimeError, match="REPRO_REQUIRE_COMPILED"):
        fleet_tick_window(*ops.values(), **kw, p99_k=2, block_n=4,
                          mode="interpret")
    state, ys, stats, head = fleet_tick_window(
        *ops.values(), **kw, p99_k=2, block_n=4, mode="xla")
    assert np.isfinite(np.asarray(state)).all()


# ------------------------------------------- statistical pins, compiled tier
def test_window_stats_compiled_tier_matches_oracle():
    """Engine observe path on backend="pallas" with the xla tier live (no
    interpret override) against the numpy oracle — the same §2.1 window
    recipe and tolerances as the interpret-era pin in test_fleet_jax."""
    interp_before = DISPATCH_COUNTS["interpret"]
    ref = collect_window_stats(_fleet("numpy", 8))
    got = collect_window_stats(_fleet("pallas", 8))
    assert_window_stats_equivalent(got, ref)
    assert DISPATCH_COUNTS["interpret"] == interp_before


def test_fused_loop_compiled_tier_matches_oracle(monkeypatch):
    """The fused training loop over backend="pallas" on the compiled tier,
    run with REPRO_REQUIRE_COMPILED set for its whole duration: any
    silent degrade to interpret anywhere in the loop would raise, and the
    reward/p99 streams must stay inside the harness tolerances vs the
    numpy-oracle per-step loop."""
    env = _fleet("numpy", 24)
    ref = _cfgr(env, device_loop="off")
    for _ in range(2):
        ref.run_update()
    monkeypatch.setenv("REPRO_REQUIRE_COMPILED", "1")
    dev = _cfgr(_fleet("pallas", 24), device_loop="on")
    for _ in range(2):
        dev.run_update()
    assert_loop_equivalent(
        np.array([r.reward for r in ref.history]),
        np.array([r.p99_ms for r in ref.history]),
        np.array([r.reward for r in dev.history]),
        np.array([r.p99_ms for r in dev.history]))


# ------------------------------------------------- pipelined actor/learner
def _twin(n=4, seed=0, steps=3):
    return _cfgr(_fleet("jax", n, seed=seed), seed=seed, steps=steps)


def test_pipeline_depth1_bitwise_equals_sequential():
    """depth=1 IS the sequential schedule: same dispatch order, same device
    RNG counters, same update inputs — params, optimizer state and the
    record stream must match bit for bit."""
    a, b = _twin(), _twin()
    a.tune(3)
    b.tune_pipelined(3, depth=1)
    for x, y in zip(jax.tree_util.tree_leaves(a.agent.params),
                    jax.tree_util.tree_leaves(b.agent.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(a.agent.opt_state),
                    jax.tree_util.tree_leaves(b.agent.opt_state)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert [r.reward for r in a.history] == [r.reward for r in b.history]
    assert [r.p99_ms for r in a.history] == [r.p99_ms for r in b.history]


def test_pipeline_depth2_overlaps_and_stays_pinned():
    """depth=2 runs batch k's update while batch k+1 explores — one update
    of policy staleness on the exploration actions. The record stream must
    keep the full accounting (updates × passes × N × steps records, one
    update_s phase per batch) and stay statistically equivalent to the
    sequential schedule."""
    a, b = _twin(n=16), _twin(n=16)
    updates = 3
    a.tune(updates)
    b.tune_pipelined(updates, depth=2)
    assert len(b.history) == len(a.history) == updates * 16 * 3
    assert b.agent.n_updates == a.agent.n_updates == updates
    for leaf in jax.tree_util.tree_leaves(b.agent.params):
        assert np.isfinite(np.asarray(leaf)).all()
    assert_loop_equivalent(
        np.array([r.reward for r in a.history]),
        np.array([r.p99_ms for r in a.history]),
        np.array([r.reward for r in b.history]),
        np.array([r.p99_ms for r in b.history]))


def test_pipeline_requires_device_loop():
    cfgr = _cfgr(_fleet("numpy", 4), device_loop="auto")
    assert cfgr.device_loop_reason() is not None
    with pytest.raises(RuntimeError):
        cfgr.tune_pipelined(2, depth=2)


# --------------------------------------------------- epoch mega-scan (§15)
def test_megascan_k1_bitwise_equals_sequential():
    """``run_epoch(1)`` IS one sequential outer iteration: same episode
    trace, same RNG fold sequence, same update inputs, and the §2.4.1
    replay runs after every update exactly like the sequential schedule —
    params, optimizer state, the record stream and the final configs must
    match bit for bit across a run that crosses the exploit warm-up
    boundary."""
    a, b = _twin(), _twin()
    a.tune(3)
    for _ in range(3):
        b.run_epoch(1, records="full")
    for x, y in zip(jax.tree_util.tree_leaves(a.agent.params),
                    jax.tree_util.tree_leaves(b.agent.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(a.agent.opt_state),
                    jax.tree_util.tree_leaves(b.agent.opt_state)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert [r.reward for r in a.history] == [r.reward for r in b.history]
    assert [r.p99_ms for r in a.history] == [r.p99_ms for r in b.history]
    assert a.env.configs == b.env.configs
    assert a.env._dev._draws == b.env._dev._draws


def test_megascan_full_records_bitwise_equals_sequential():
    """One K=3 epoch in ``records="full"`` mode vs 3 sequential updates:
    frozen bins make the sequential path's between-update replay a no-op,
    so the epoch's deferred materialisation must reproduce the exact same
    params, record stream and final fleet state (the scan-composed update
    is the SAME ``_update_step`` math the per-update program jits)."""
    a, b = _twin(), _twin()
    a.tune(3)
    stats, _ = b._device_runner().run_epoch(3, records="full")
    assert len(stats) == 3
    for x, y in zip(jax.tree_util.tree_leaves(a.agent.params),
                    jax.tree_util.tree_leaves(b.agent.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert b.agent.n_updates == a.agent.n_updates == 3


def test_megascan_k4_stays_pinned_to_sequential():
    """A K=4 epoch vs 4 sequential updates at N=16: the mega-scan defers
    the §2.4.1 replay and the record pull to the epoch boundary, so the
    streams are statistically — not bitwise — pinned (same contract as
    the depth≥2 pipeline)."""
    a, b = _twin(n=16), _twin(n=16)
    a.tune(4)
    b.tune_megascan(4, k=4, records="full")
    assert len(b.history) == len(a.history) == 4 * 16 * 3
    assert b.agent.n_updates == a.agent.n_updates == 4
    assert_loop_equivalent(
        np.array([r.reward for r in a.history]),
        np.array([r.p99_ms for r in a.history]),
        np.array([r.reward for r in b.history]),
        np.array([r.p99_ms for r in b.history]))


def test_megascan_requires_device_loop():
    cfgr = _cfgr(_fleet("numpy", 4), device_loop="auto")
    with pytest.raises(RuntimeError, match="device loop"):
        cfgr.run_epoch(2)
