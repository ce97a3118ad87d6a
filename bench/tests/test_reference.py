"""The plain reference against brute force, and the comparison's numbers."""
import numpy as np
import pytest

from bench import reference
from bench.reference import InsertBatch, LiveTruth, Truth


def _brute_1d(kind, keys, meas, lq, uq):
    out = []
    for a, b in zip(lq, uq):
        if kind in ("count", "sum"):
            sel = (keys > a) & (keys <= b)
            out.append(meas[sel].sum())
        else:
            sel = (keys >= a) & (keys <= b)
            red = np.max if kind == "max" else np.min
            empty = -np.inf if kind == "max" else np.inf
            out.append(red(meas[sel]) if sel.any() else empty)
    return np.array(out)


@pytest.mark.parametrize("kind", ["count", "sum", "max", "min"])
def test_truth_1d_matches_brute_force(kind):
    rng = np.random.default_rng(1)
    keys = np.round(rng.uniform(0, 50, 400), 1)        # with duplicates
    meas = rng.uniform(-5, 5, 400)
    lq = np.sort(rng.choice(keys, 200))
    uq = lq + rng.uniform(0, 10, 200)
    data = keys if kind == "count" else (keys, meas)
    m = np.ones_like(keys) if kind == "count" else meas
    np.testing.assert_allclose(Truth(kind, data)(lq, uq),
                               _brute_1d(kind, keys, m, lq, uq))


@pytest.mark.parametrize("kind", ["count2d", "sum2d", "max2d", "min2d"])
def test_truth_2d_matches_brute_force(kind):
    rng = np.random.default_rng(2)
    xs, ys, ws = (rng.uniform(0, 10, 300) for _ in range(3))
    data = (xs, ys) if kind == "count2d" else (xs, ys, ws)
    w = np.ones_like(xs) if kind == "count2d" else ws
    if kind in ("count2d", "sum2d"):
        q = [rng.uniform(0, 10, 50) for _ in range(4)]
        lx, ly = np.minimum(q[0], q[1]), np.minimum(q[2], q[3])
        ux, uy = np.maximum(q[0], q[1]), np.maximum(q[2], q[3])
        want = [w[(xs > a) & (xs <= b) & (ys > c) & (ys <= d)].sum()
                for a, b, c, d in zip(lx, ux, ly, uy)]
        got = Truth(kind, data)(lx, ux, ly, uy)
    else:
        u, v = rng.uniform(0, 10, 50), rng.uniform(0, 10, 50)
        red = np.max if kind == "max2d" else np.min
        want = [red(w[(xs <= a) & (ys <= b)]) if ((xs <= a) & (ys <= b)).any()
                else (-np.inf if kind == "max2d" else np.inf)
                for a, b in zip(u, v)]
        got = Truth(kind, data)(u, v)
    np.testing.assert_allclose(got, want)


def test_live_truth_brackets_the_visible_inserts():
    keys = np.arange(100, dtype=float)
    vals = np.ones(100)
    b1 = InsertBatch([10.5, 20.5], [5.0, 7.0], issued=1.0, acked=2.0)
    b2 = InsertBatch([30.5], [11.0], issued=3.0, acked=4.0)
    t = LiveTruth("sum", (keys, vals), [b1, b2])
    lq, uq = np.array([0.0, 0.0, 0.0]), np.array([99.0, 99.0, 15.0])
    lo, hi = t.bounds(lq, uq, submitted=[2.5, 0.5, 5.0],
                      resolved=[3.5, 0.9, 6.0])
    # read 0: must see b1, may see b2; read 1: sees neither;
    # read 2: covers only b1's first record
    np.testing.assert_allclose(lo, [99 + 12, 99, 15 + 5])
    np.testing.assert_allclose(hi, [99 + 12 + 11, 99, 15 + 5])


def test_live_truth_counts_records_for_count_tables():
    t = LiveTruth("count", np.arange(10.0),
                  [InsertBatch([2.5, 3.5], [9.0, 9.0], 0.0, 0.0)])
    lo, hi = t.bounds(np.array([0.0]), np.array([9.0]), [1.0], [1.0])
    assert lo[0] == hi[0] == 9 + 2


LIMITS = {"err_over_bound": 1.0, "rel_err": 0.01,
          "refined_err_over_bound": 0.0}


def test_compare_numbers():
    truth = np.array([1000.0, 50.0, 0.0, 20000.0])
    ans = np.array([1080.0, 50.0, 0.0, 20000.0])
    refined = np.array([False, True, True, False])
    n = reference.compare(ans, truth, truth, refined, 100.0, LIMITS)
    assert n["err_over_bound"]["value"] == pytest.approx(0.8, rel=1e-6)
    assert n["rel_err"]["value"] == pytest.approx(0.08, rel=1e-5)
    assert n["refined_err_over_bound"]["value"] == 0.0
    assert not reference.passed(n)           # rel_err 0.08 > 0.01
    bad = reference.compare([1000.0, 51.0, 0.0, 20000.0], truth, truth,
                            refined, 100.0, LIMITS)
    assert bad["refined_err_over_bound"]["value"] == pytest.approx(0.01)
    assert not reference.passed(bad)
    empty = reference.compare([0.5], [0.0], [0.0], [True], 100.0, LIMITS)
    assert empty["rel_err"]["value"] == np.inf      # empty range: exact
    nan = reference.compare([np.nan], [1.0], [1.0], [False], 100.0, LIMITS)
    assert nan["err_over_bound"]["value"] == np.inf


def test_control_answers_follow_the_acked_snapshot():
    keys = np.arange(10.0)
    b = InsertBatch([4.5], [3.0], issued=1.0, acked=2.0)
    t = LiveTruth("sum", (keys, np.ones(10)), [b])
    out = reference.control_answers(t, np.array([0.0, 0.0]),
                                    np.array([9.0, 9.0]),
                                    np.array([1.5, 2.5]))
    np.testing.assert_allclose(out, [9.0, 12.0])
