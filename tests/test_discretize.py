"""§2.4.1 dynamic discretisation: split / extend / merge / jitter / bounds."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep; skip, never fail collection
from hypothesis import given, settings, strategies as st

from repro.core.discretize import DynamicBins, LeverDiscretiser, LeverSpec


def _bins(**kw):
    spec = LeverSpec("x", kind="float", lo=0.0, hi=10.0, default=5.0,
                     hard_lo=-20.0, hard_hi=40.0)
    return DynamicBins(spec, seed=0, **kw)


def test_initial_bins_ten_and_delta():
    b = _bins()
    assert b.n_bins == 10
    np.testing.assert_allclose(b.delta, 1.0)


def test_same_bin_streak_halves_bin_size():
    b = _bins(split_after=5)
    for _ in range(5):
        b.record(4)
    assert b.n_bins == 20  # paper: '20 bins after this initial halving'


def test_splits_stop_at_the_bin_cap():
    """A fleet-sized replay of one bin (thousands of same-bin assignments)
    halves the bins only up to MAX_SPLIT_BINS, not once per streak."""
    from repro.core.discretize import MAX_SPLIT_BINS

    b = _bins(split_after=5, merge_after=10**9)
    b.record_many(np.full(70, 4))
    assert MAX_SPLIT_BINS // 2 < b.n_bins <= MAX_SPLIT_BINS


def test_top_bin_streak_extends_range():
    b = _bins(extend_after=3)
    hi0 = b._edges[-1]
    for _ in range(3):
        b.record(b.n_bins - 1)
    assert b._edges[-1] > hi0


def test_extension_respects_hard_bounds():
    spec = LeverSpec("x", kind="float", lo=0.0, hi=10.0, hard_hi=12.0)
    b = DynamicBins(spec, extend_after=2, split_after=10**9)
    for _ in range(50):
        b.record(b.n_bins - 1)
    assert b._edges[-1] <= 12.0 + 1e-9


def test_log_lever_extension_bounded():
    spec = LeverSpec("t", kind="log", lo=0.25, hi=20.0, hard_lo=0.05, hard_hi=30.0)
    b = DynamicBins(spec, extend_after=2, split_after=10**9)
    for _ in range(100):
        b.record(b.n_bins - 1)
    assert b.value(b.n_bins - 1, jitter=False) <= 30.0 + 1e-6
    for _ in range(100):
        b.record(0)
    assert b.value(0, jitter=False) >= 0.05 - 1e-9


def test_merge_removes_idle_adjacent_bins():
    b = _bins(merge_after=5, split_after=10**9, extend_after=10**9)
    n0 = b.n_bins
    for _ in range(30):
        b.record(0)  # bins 5..9 stay idle -> eligible to merge
    assert b.n_bins < n0


def test_ridge_jitter_stays_within_bin():
    b = _bins(ridge_frac=0.4)
    for k in range(b.n_bins):
        for _ in range(20):
            v = b.value(k)
            assert b._edges[k] - 1e-9 <= v <= b._edges[k + 1] + 1e-9


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(-1, 1), min_size=1, max_size=120),
       st.integers(0, 100))
def test_property_random_walk_never_escapes_hard_bounds(moves, seed):
    spec = LeverSpec("x", kind="log", lo=0.5, hi=8.0, default=2.0,
                     hard_lo=0.1, hard_hi=32.0)
    disc = LeverDiscretiser([spec], seed=seed)
    cfg = disc.default_config()
    for d in moves:
        if d == 0:
            continue
        cfg = disc.apply(cfg, "x", d)
        assert 0.1 - 1e-9 <= cfg["x"] <= 32.0 + 1e-9


def test_discretiser_choice_and_bool_cycle():
    specs = [LeverSpec("c", kind="choice", choices=("a", "b", "z")),
             LeverSpec("flag", kind="bool", default=False)]
    disc = LeverDiscretiser(specs)
    cfg = disc.default_config()
    assert cfg == {"c": "a", "flag": False}
    cfg = disc.apply(cfg, "c", +1)
    assert cfg["c"] == "b"
    cfg = disc.apply(cfg, "c", -1)
    assert cfg["c"] == "a"
    cfg = disc.apply(cfg, "flag", +1)
    assert cfg["flag"] is True


def test_int_lever_values_are_ints():
    disc = LeverDiscretiser([LeverSpec("n", kind="int", lo=1, hi=64, default=8)])
    cfg = disc.apply(disc.default_config(), "n", +1)
    assert isinstance(cfg["n"], int)
    assert 1 <= cfg["n"] <= 64 + 32  # may extend, but stays integral

