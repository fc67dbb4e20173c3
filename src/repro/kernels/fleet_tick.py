"""Fused fleet-tick window kernel: one window of the queueing recurrence plus
its latency-lane statistics on the cluster grid (DESIGN.md §9, §14).

The jax backend of the device fleet engine steps ``service_terms_arrays``
inside a ``lax.scan``; this module is the fused alternative the
``backend="pallas"`` path uses: the *whole window* — T sequential micro-batch
ticks, their queueing state updates AND the per-event latency-lane
statistics — runs as a single fused program, with clusters on the lane axis
(128-wide vectors) and the latency lanes on the sublane axis.

**Tiered dispatch (DESIGN.md §14).** The kernel body has three execution
tiers, selected by ``mode``:

* ``"mosaic"`` — ``pl.pallas_call`` compiled by the Mosaic TPU backend
  (VMEM-resident blocks; the TPU fast path);
* ``"interpret"`` — the same ``pallas_call`` in interpret mode (jnp ops per
  grid cell; the debugging tier — slow, but executes the literal kernel);
* ``"xla"`` — an XLA lowering of the *same tick math* (shared helpers, a
  ``lax.scan`` over ticks): the compiled path off-TPU, where a TPU
  ``pallas_call`` has no lowering (``interpret=False`` raises on the CPU
  backend).

``pallas_mode()`` picks the tier for the current backend;
``DISPATCH_COUNTS`` records which tiers actually traced, and setting
``REPRO_REQUIRE_COMPILED`` makes any interpret-tier trace raise — the CI
compiled-pallas job uses both to prove the fast path never silently degrades
to interpret.

**Fused lane statistics.** Older revisions materialised the full
``(T, S, N)`` latency-lane buffer and re-read it outside the kernel (gather
at emission ticks, bitonic sorts, a window-wide ``top_k``). The kernel now
reduces the lanes *in place*, per tick, and never emits them:

* ``stats[0]`` — per-tick valid-lane sum (window mean = masked cross-tick
  sum ÷ count, done by the caller so both tiers share reduction order);
* ``stats[1..4]`` — per-tick lane quantiles p50/p95/p99 and max from one
  ascending bitonic sort per tick (the per-emission statistics gather these
  rows at the emission ticks — no lane buffer, no post-hoc sorts);
* ``head`` — a streaming top-K of all valid window lanes, maintained as an
  ascending (K, N) carry and merged each tick with a single O(log P)
  bitonic *merge* (the tick's sorted lanes reversed + the head form a
  bitonic sequence). K is sized by ``head_budget`` so K+S is a power of
  two and K covers the caller's p99 interpolation depth; top-K selection
  is arithmetic-free, so the head's values match a full ``top_k`` over the
  materialised lanes bitwise.

The service model is algebraically identical to
``repro.engine.simcluster.service_terms_arrays`` but pre-folded into
per-cluster coefficients (``pack_tick_consts``): service = ovh + tokens·A·pen
+ tokens·C with tokens = batch·size·16. ``_tick_step`` holds the per-tick
math ONCE — the Pallas kernel body and the XLA tier both call it, which is
what makes the tiers agree to the bit on shared shapes
(``tests/test_pallas_compiled.py`` pins this).

**Scan-composability (DESIGN.md §11).** ``window_recurrence`` exposes the
kernel with the same carry contract as the jnp tick scan in
``repro.engine.fleet_jax`` — ``(backlog, sfree_rel) -> (backlog',
sfree_rel')`` plus the per-tick terms and lane statistics the summaries
read — so ``build_step_window(pallas=True)`` composes it straight into the
fused training loop's episode ``lax.scan`` on every tier.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.engine.simcluster import TOKENS_PER_MB, PEAK_FLOPS

DEFAULT_BLOCK_N = 128   # clusters per block (TPU lane width)

#: consts channel layout (rows of the (CONSTS_ROWS, N) array)
_C_TB, _C_MAXB, _C_ACOMP, _C_CCOLL, _C_BMEM, _C_KVP, _C_OVH, _C_SLOWCAP, \
    _C_BACKUP, _C_FAIL, _C_INFLIGHT = range(11)
CONSTS_ROWS = 16  # padded to a sublane multiple

#: VMEM for one grid step's double-buffered blocks on the Pallas tiers:
#: half of v5e's 16 MiB default scoped limit, leaving the rest to the head
#: carry and the sort network's temporaries (``tick_tile``)
_TILE_VMEM_BYTES = 8 << 20

#: mode -> number of times a window program traced through that tier; the
#: compiled-pallas CI smoke asserts the interpret tier stays at zero
DISPATCH_COUNTS: dict = {"mosaic": 0, "interpret": 0, "xla": 0}


def pallas_mode() -> str:
    """The execution tier for the fused window kernel on this backend:
    ``"interpret"`` when forced via ``REPRO_PALLAS_INTERPRET`` (debug),
    ``"mosaic"`` on TPU, else ``"xla"`` — the compiled fast path on
    backends without a Pallas lowering (DESIGN.md §14)."""
    if os.environ.get("REPRO_PALLAS_INTERPRET", ""):
        return "interpret"
    if jax.default_backend() == "tpu":
        return "mosaic"
    return "xla"


def head_budget(S: int, p99_k: int) -> int:
    """Streaming top-K head length for S lanes/tick and a ``p99_k``-deep
    caller interpolation: the smallest K with K ≥ p99_k and K+S a power of
    two (the per-tick head merge is a single bitonic merge of length K+S)."""
    P = 1
    while P < S + p99_k:
        P *= 2
    return P - S


def tick_tile(T: int, S: int, block_n: int) -> int:
    """Ticks per grid step of the Pallas tiers: the largest divisor of T
    that is a multiple of 8 (the sublane tile of the (ticks, clusters)
    blocks) and whose double-buffered blocks fit ``_TILE_VMEM_BYTES`` — the
    whole window when it fits, or when T has no such divisor. Per tick a
    block holds 9 (T, N) operand rows, two S-lane tiles and 12 output rows,
    each ``block_n`` f32 wide."""
    budget = _TILE_VMEM_BYTES
    per_tick = 2 * 4 * block_n * (9 + 2 * S + 12)
    if T * per_tick <= budget or T % 8:
        return T
    fits = [d for d in range(8, T, 8) if T % d == 0 and d * per_tick <= budget]
    return max(fits, default=T)


def pack_tick_consts(cc: dict, mc: dict, spec, chips: int, xp=jnp):
    """Fold the packed lever arrays + model constants into the per-cluster
    coefficient rows the kernel consumes. Same algebra as
    ``service_terms_arrays``, factored by what varies per tick:

        tokens   = batch · size · TOKENS_PER_MB
        service  = ovh + tokens·A·mem_penalty(tokens·B + kvp) + tokens·C
    """
    eff = spec.base_mfu * cc["eff_block_q"] * cc["eff_block_k"] * cc["eff_dtype"]
    a0 = mc["flops_per_tok"] * cc["remat"] / (chips * PEAK_FLOPS * eff)
    moe = (mc["is_moe"] != 0) & (cc["expert_parallel"] != 0)
    a_comp = xp.where(moe, a0 * 0.92, a0) * cc["tp_compute"]
    c_coll = (a0 * spec.collective_frac * (cc["tp"] / 16.0) ** 0.5
              * cc["compression"] / (1.0 + 0.45 * (cc["mb"] - 1.0)))
    c_coll = xp.where(moe, c_coll * 1.15, c_coll)
    b_mem = mc["kv_per_tok"] / 1e9 / (chips * spec.hbm_gb_per_chip)
    ovh = spec.dispatch_overhead_s * (1.0 + 0.12 * (cc["mb"] - 1.0))
    ovh = ovh + spec.driver_gc_coeff / xp.maximum(cc["driver_memory_gb"], 1.0) * 0.1
    ovh = ovh + 0.12 * xp.maximum(
        xp.log2(512.0 / xp.maximum(cc["allocator_arena_mb"], 32.0)), 0.0)
    sink = cc["sink_partitions"]
    ovh = ovh + 0.25 / xp.maximum(sink, 1.0) + 0.004 * sink
    ovh = ovh * (0.45 + 0.55 / (1.0 + cc["prefetch_depth"]))
    T_b = cc["T_b"]
    slow_cap = xp.maximum(1.2, 1.0 + cc["straggler_timeout_s"]
                          / xp.maximum(T_b, 1e-3))
    rows = [T_b, cc["max_batch_events"], a_comp, c_coll, b_mem,
            cc["kv_pressure"], ovh, slow_cap,
            (cc["backup_tasks"] != 0).astype(a0.dtype),
            cc["failure_inject_frac"],
            xp.maximum(cc["max_inflight_batches"], 1.0) * T_b]
    zeros = xp.zeros_like(T_b)
    rows += [zeros] * (CONSTS_ROWS - len(rows))
    return xp.stack(rows).astype(jnp.float32)


# --------------------------------------------------------------------------
# shared per-tick math — the kernel body and the XLA tier both call these,
# so the tiers share expression order (and therefore rounding) exactly
# --------------------------------------------------------------------------

def _tick_step(backlog, sfree, rate, size, z, u_s, u_r, u_f, active, fm, cv,
               *, noise, retention_s, straggler_prob, slo, shi):
    """One micro-batch tick on a (W,) cluster slice: the queueing recurrence
    plus the straggler/failure gates. ``cv`` is the 11-tuple of coefficient
    rows from ``pack_tick_consts``; ``fm`` the chaos service multiplier
    (exactly 1.0 outside fault windows). Returns the active-gated carry and
    the 7 ys channels."""
    (T_b, max_b, a_comp, c_coll, b_mem, kvp, ovh, slow_cap, backup,
     fail_frac, inflight) = cv
    arrivals = rate * T_b * (1.0 + noise * z)
    age = backlog / jnp.maximum(rate, 1.0)
    blg = backlog + jnp.maximum(arrivals, 0.0)
    blg = jnp.minimum(blg, rate * retention_s)          # Kafka retention
    batch = jnp.minimum(blg, max_b)
    tokens = batch * size * TOKENS_PER_MB
    mem_frac = jnp.minimum(tokens * b_mem + kvp, 1.5)
    pen = 1.0 + 2.0 * jnp.maximum(mem_frac - 1.0, 0.0)  # spill cliff
    service = ovh + tokens * a_comp * pen + tokens * c_coll
    smask = u_s < straggler_prob
    raw = slo + (shi - slo) * u_r
    slow = jnp.where(smask, jnp.where(backup != 0, 1.1,
                                      jnp.minimum(raw, slow_cap)), 1.0)
    fmask = u_f < fail_frac
    slow = jnp.where(fmask, slow * 2.0, slow)
    # chaos-table service multiplier (repro.core.faults): exactly 1.0
    # outside fault windows, so fault-free tables are bit-for-bit no-ops
    slow = slow * fm
    service = service * slow
    start_rel = jnp.maximum(T_b, sfree)
    sfree_new = jnp.minimum(start_rel + service, T_b + inflight) - T_b
    processed = jnp.where(service <= T_b, batch, batch * (T_b / service))
    blg_after = jnp.maximum(blg - processed, 0.0)
    qd = (start_rel - T_b) + age
    carry = (jnp.where(active, blg_after, backlog),
             jnp.where(active, sfree_new, sfree))
    ys = (service, qd, batch, jnp.where(active, processed, 0.0),
          smask.astype(jnp.float32), fmask.astype(jnp.float32), blg_after)
    return carry, ys


def _partner(x, j):
    """``x[i ^ j]`` along axis 0 (power-of-two length): the compare-exchange
    partner of every slot, as two sublane rotations and an iota-mask select.
    The mask compares the rotated iota with ``i ^ j``, so the pick does not
    depend on the rotation's direction convention. Rotations and
    ``broadcasted_iota`` lower on every tier (Mosaic has no lowering for the
    strided slices or sub-8 sublane reshapes a slice-based network needs)."""
    L = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    src = pltpu.roll(lane, j, 0)
    return jnp.where(src == (lane ^ j), pltpu.roll(x, j, 0),
                     pltpu.roll(x, L - j, 0))


def _sort_axis0(x, descending=False):
    """Bitonic sort along axis 0 (power-of-two length) — pure min/max,
    rotations and iota masks with no captured index constants, so the SAME
    code traces inside the Pallas kernel body (which forbids constant
    operands) and in the XLA tier, and never touches XLA's general sort
    (~50x slower on CPU). Any sorting network returns the same values, so
    tiers agree to the bit."""
    L = x.shape[0]
    assert L & (L - 1) == 0, f"lane count {L} must be a power of two"
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    k = 2
    while k <= L:
        j = k // 2
        while j >= 1:
            xp = _partner(x, j)
            # slot i keeps the min when its k-block ascends and it is the
            # lower slot of its pair (or descends and it is the upper one)
            asc = ((lane & k) == 0) != descending
            take_min = asc == ((lane & j) == 0)
            x = jnp.where(take_min, jnp.minimum(x, xp), jnp.maximum(x, xp))
            j //= 2
        k *= 2
    return x


def _merge_head(head, srt):
    """Merge a tick's *descending* sorted lanes (S, W) into the ascending
    streaming top-K head (K, W). ``concat(head, srt)`` ascends then
    descends — a bitonic sequence — so one O(log(K+S)) bitonic merge (not a
    full sort) re-sorts it ascending; the largest K survive."""
    S = srt.shape[0]
    x = jnp.concatenate([head, srt], axis=0)
    P = x.shape[0]
    assert P & (P - 1) == 0, f"head+lanes {P} must be a power of two"
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    j = P // 2
    while j >= 1:
        xp = _partner(x, j)
        x = jnp.where((lane & j) == 0, jnp.minimum(x, xp),
                      jnp.maximum(x, xp))
        j //= 2
    return x[S:]


def _sum0(x):
    """Pairwise tree sum along axis 0 (power-of-two length). XLA's reduce
    picks its accumulation order from the operand layout, so the same
    axis-0 ``sum`` rounds differently on (S, N) vs (S, T, N) operands;
    spelling the tree out (slot i adds its partner i ^ s, stride s = 1, 2,
    4, ...; slot 0 ends with the total) keeps the lane sum bitwise-identical
    across tiers whatever the trailing shape."""
    L = x.shape[0]
    assert L & (L - 1) == 0, f"lane count {L} must be a power of two"
    s = 1
    while s < L:
        x = x + _partner(x, s)
        s *= 2
    return _row(x, 0)


def _row(x, i):
    """``x[i]`` for a static non-negative i, as a static slice (a negative
    or traced index lowers to ``dynamic_slice``, which Mosaic refuses)."""
    return jax.lax.index_in_dim(x, i, axis=0, keepdims=False)


def _gather0(x, idx):
    """x[idx[w], w] for (L, W) x and (W,) int idx — one-hot reduction
    against an iota (per-lane dynamic gathers don't vectorise on the
    sublane axis, and index constants can't be captured in-kernel)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(lane == idx[None, :], x, 0.0).sum(axis=0)


def _lane_stats(uw, z2, T_b, qd, service, batch, wm, S):
    """Latency lanes, reduced in place (no lane buffer escapes):

        lat = wait·T_b + queue_delay + service·(1 + 0.1·jitter)   (S, W) s

    Returns (stats5, srt): ``stats5`` = (lane_sum, p50, p95, p99, max)
    over the valid lanes (lane < n_s, window ticks only — rows at
    non-window ticks are unused by every caller), and the descending sorted
    lanes for the caller's streaming top-K head merge. Every op is
    elementwise or an axis-0 reduction, so W may be a single tick's (N,)
    block (the Pallas tiers) or the whole window's (T, N) at once (the XLA
    tier) with bitwise-identical per-column results. Quantiles interpolate
    exactly like the caller-side ``_lerp_quantile``; with invalid lanes
    sorted to the back as -inf, the ascending rank r of a valid lane lives
    at index n_s - 1 - r."""
    lat = uw * T_b[None] + qd[None] + service[None] * (1.0 + 0.1 * z2)
    n_s = jnp.clip(batch.astype(jnp.int32), 1, S)
    lane = jax.lax.broadcasted_iota(jnp.int32, (S,) + batch.shape, 0)
    valid = (lane < n_s[None]) & (wm > 0.0)[None]
    lane_sum = _sum0(jnp.where(valid, lat, 0.0))
    srt = _sort_axis0(jnp.where(valid, lat, -jnp.inf), descending=True)
    top = n_s - 1

    def q_at(q):
        pos = (n_s - 1).astype(jnp.float32) * (q / 100.0)
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.ceil(pos).astype(jnp.int32)
        a = _gather0(srt, top - lo)
        b = _gather0(srt, top - hi)
        return a + (pos - lo.astype(jnp.float32)) * (b - a)

    stats5 = (lane_sum, q_at(50.0), q_at(95.0), q_at(99.0), _row(srt, 0))
    return stats5, srt


# --------------------------------------------------------------------------
# the two lowerings of the same window body
# --------------------------------------------------------------------------

def _tick_window_kernel(state_ref, c_ref, rate_ref, size_ref, z_ref, us_ref,
                        ur_ref, uf_ref, act_ref, wm_ref, uw_ref, z2_ref,
                        fm_ref, state_out_ref, ys_ref, stats_ref, head_ref,
                        *, T: int, S: int, K: int, noise: float,
                        retention_s: float, straggler_prob: float,
                        slo: float, shi: float):
    """One T-tick tile of an exploration window for a (BLOCK_N,) cluster
    block: the queueing recurrence in registers, lanes reduced per tick
    (Pallas tiers). Grid axis 1 walks the window's tiles in order; the state
    and head output blocks stay resident across it and carry the recurrence
    from one tile to the next."""
    cv = tuple(c_ref[i] for i in range(11))
    T_b = cv[0]

    def tick(t, carry):
        backlog, sfree, head = carry
        (backlog, sfree), ys = _tick_step(
            backlog, sfree, rate_ref[t], size_ref[t], z_ref[t], us_ref[t],
            ur_ref[t], uf_ref[t], act_ref[t] != 0, fm_ref[t], cv,
            noise=noise, retention_s=retention_s,
            straggler_prob=straggler_prob, slo=slo, shi=shi)
        stats5, srt = _lane_stats(uw_ref[t], z2_ref[t], T_b, ys[1], ys[0],
                                  ys[2], wm_ref[t], S)
        head = _merge_head(head, srt)
        for r in range(7):
            ys_ref[r, t] = ys[r]
        for r in range(5):
            stats_ref[r, t] = stats5[r]
        return backlog, sfree, head

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_out_ref[...] = state_ref[...]
        head_ref[...] = jnp.full(head_ref.shape, -jnp.inf, jnp.float32)

    backlog, sfree, head = jax.lax.fori_loop(
        0, T, tick, (state_out_ref[0], state_out_ref[1], head_ref[...]))
    state_out_ref[0] = backlog
    state_out_ref[1] = sfree
    head_ref[...] = head


def _window_xla(state, consts, rate, size, z, u_strag, u_raw, u_fail, active,
                wmask, u_wait, z2a, fmult, *, T, S, K, noise, retention_s,
                straggler_prob, slo, shi, unroll=1):
    """The compiled XLA tier: the SAME shared tick/lane math as the kernel
    body, split by data dependence. The queueing recurrence is genuinely
    sequential, so it runs as a thin ``lax.scan`` over ticks (~40 ops on
    (N,) vectors per tick, ``unroll`` stays at 1: unrolling duplicates the
    body faster than XLA:CPU can fuse it). The lane statistics do NOT feed
    the recurrence, so one vectorised ``_lane_stats`` call processes the
    whole (S, T, N) lane block at once — the bitonic network's 2·log²S
    compare-exchange stages each touch T·N columns instead of dispatching
    T tiny (S, N) ops (measured ~3× faster at T=32, N=128; see
    benchmarks/roofline.py ``--kernel fleet_tick``). Every lane op is
    elementwise or an axis-0 reduction, so the per-column results — and the
    per-tick head merge fold after it — stay bitwise-equal to the interpret
    tier on a single-block shape."""
    cv = tuple(consts[i] for i in range(11))
    T_b = cv[0]
    kw = dict(noise=noise, retention_s=retention_s,
              straggler_prob=straggler_prob, slo=slo, shi=shi)

    def body(carry, xs):
        backlog, sfree = carry
        rate_t, size_t, z_t, us_t, ur_t, uf_t, act_t, fm_t = xs
        (backlog, sfree), ys = _tick_step(
            backlog, sfree, rate_t, size_t, z_t, us_t, ur_t, uf_t,
            act_t != 0, fm_t, cv, **kw)
        return (backlog, sfree), ys

    (backlog, sfree), ys = jax.lax.scan(
        body, (state[0], state[1]),
        (rate, size, z, u_strag, u_raw, u_fail, active, fmult),
        unroll=min(unroll, T))
    service, qd, batch = ys[0], ys[1], ys[2]
    stats5, srt = _lane_stats(
        jnp.moveaxis(u_wait, 0, 1), jnp.moveaxis(z2a, 0, 1),
        T_b, qd, service, batch, wmask, S)          # W = (T, N)
    head0 = jnp.full((K, state.shape[1]), -jnp.inf, jnp.float32)
    head, _ = jax.lax.scan(
        lambda h, srt_t: (_merge_head(h, srt_t), None),
        head0, jnp.moveaxis(srt, 0, 1))             # fold ticks in order
    return (jnp.stack([backlog, sfree]),
            jnp.stack(ys, axis=0),                  # (7, T, N)
            jnp.stack(stats5, axis=0),              # (5, T, N)
            head)                                   # (K, N)


@functools.partial(
    jax.jit,
    static_argnames=("noise", "retention_s", "straggler_prob", "slo", "shi",
                     "p99_k", "block_n", "mode"))
def fleet_tick_window(state, consts, rate, size, z, u_strag, u_raw, u_fail,
                      active, u_wait, z2a, fmult=None, wmask=None, *, noise,
                      retention_s, straggler_prob, slo, shi, p99_k=2,
                      block_n=DEFAULT_BLOCK_N, mode=None):
    """Run one window's fused tick recurrence + lane statistics.

    state (2, N) [backlog, server_free_rel]; consts (CONSTS_ROWS, N) from
    ``pack_tick_consts``; rate/size/z/u_*/active (T, N); u_wait/z2a
    (T, S, N); ``fmult`` an optional (T, N) chaos-table service multiplier
    (defaults to all-ones — a bit-for-bit no-op); ``wmask`` the (T, N)
    window mask gating which ticks' lanes feed the statistics (defaults to
    ``active`` — the whole simulated span). ``p99_k`` is the caller's p99
    interpolation depth; the streaming head is sized ≥ p99_k by
    ``head_budget``. ``mode`` selects the tier (default ``pallas_mode()``).

    Returns (state' (2, N), ys (7, T, N), stats (5, T, N), head (K, N)):
    ys rows = service, queue_delay, batch, processed, straggler, failure,
    backlog_after; stats rows = lane_sum, p50, p95, p99, max (seconds, valid
    at window ticks); head = ascending top-K window lane latencies.
    """
    T, S, N = u_wait.shape
    if mode is None:
        mode = pallas_mode()
    if mode == "interpret" and os.environ.get("REPRO_REQUIRE_COMPILED", ""):
        raise RuntimeError(
            "REPRO_REQUIRE_COMPILED is set but the fleet_tick window would "
            "run the interpret tier (unset REPRO_PALLAS_INTERPRET, or run on "
            "a backend with a compiled tier)")
    DISPATCH_COUNTS[mode] = DISPATCH_COUNTS.get(mode, 0) + 1
    if fmult is None:
        fmult = jnp.ones_like(rate)
    fmult = jnp.broadcast_to(fmult, (T, N))
    if wmask is None:
        wmask = active
    K = head_budget(S, p99_k)
    kw = dict(T=T, S=S, K=K, noise=noise, retention_s=retention_s,
              straggler_prob=straggler_prob, slo=slo, shi=shi)
    if mode == "xla":
        return _window_xla(state, consts, rate, size, z, u_strag, u_raw,
                           u_fail, active, wmask, u_wait, z2a, fmult, **kw)
    bn = min(block_n, N)
    tT = tick_tile(T, S, bn)
    grid = (pl.cdiv(N, bn), T // tT)
    vm = pltpu.VMEM
    blk = lambda i, t: (0, i)           # per-cluster-block, resident over t
    tn = lambda i, t: (t, i)            # (ticks, cluster-block)
    lane = lambda i, t: (t, 0, i)       # (ticks, lanes, cluster-block)
    row = lambda i, t: (0, t, i)        # (rows, ticks, cluster-block)
    kernel = functools.partial(_tick_window_kernel, **dict(kw, T=tT))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((2, bn), blk, memory_space=vm),
            pl.BlockSpec((CONSTS_ROWS, bn), blk, memory_space=vm),
        ] + [pl.BlockSpec((tT, bn), tn, memory_space=vm)] * 8 + [
            pl.BlockSpec((tT, S, bn), lane, memory_space=vm),
            pl.BlockSpec((tT, S, bn), lane, memory_space=vm),
            pl.BlockSpec((tT, bn), tn, memory_space=vm),
        ],
        out_specs=[
            pl.BlockSpec((2, bn), blk, memory_space=vm),
            pl.BlockSpec((7, tT, bn), row, memory_space=vm),
            pl.BlockSpec((5, tT, bn), row, memory_space=vm),
            pl.BlockSpec((K, bn), blk, memory_space=vm),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        out_shape=[
            jax.ShapeDtypeStruct((2, N), jnp.float32),
            jax.ShapeDtypeStruct((7, T, N), jnp.float32),
            jax.ShapeDtypeStruct((5, T, N), jnp.float32),
            jax.ShapeDtypeStruct((K, N), jnp.float32),
        ],
        interpret=mode == "interpret",
    )(state, consts, rate, size, z, u_strag, u_raw, u_fail, active, wmask,
      u_wait, z2a, fmult)


def window_recurrence(backlog, sfree_rel, consts, rate, size, z, u_strag,
                      u_raw, u_fail, active, u_wait, z2a, fmult=None,
                      wmask=None, *, noise, retention_s, straggler_prob,
                      slo, shi, p99_k=2, mode=None):
    """The fused window kernel with the jnp tick scan's carry contract:

        (backlog, sfree_rel) -> (backlog', sfree_rel'),
        (service, queue_delay, batch, processed, backlog_after),
        stats (5, T, N) seconds, head (K, N) seconds

    — the drop-in fused twin of the ``_tick_body`` scan that
    ``repro.engine.fleet_jax.build_step_window`` carries through the fused
    training loop's episode ``lax.scan`` (DESIGN.md §11), on whichever tier
    ``mode``/``pallas_mode()`` selects."""
    state_out, ys, stats, head = fleet_tick_window(
        jnp.stack([backlog, sfree_rel]), consts, rate, size, z, u_strag,
        u_raw, u_fail, active, u_wait, z2a, fmult, wmask, noise=noise,
        retention_s=retention_s, straggler_prob=straggler_prob, slo=slo,
        shi=shi, p99_k=p99_k, mode=mode)
    terms = (ys[0], ys[1], ys[2], ys[3], ys[6])
    return (state_out[0], state_out[1]), terms, stats, head
