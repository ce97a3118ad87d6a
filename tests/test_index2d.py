"""Two-key extension (§6): dominance counting, merge-sort tree, quadtree."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (MergeSortTree, build_index_2d, dominance_rank,
                        query_count_2d)
from repro.data import make_queries_2d, osm_points


def test_dominance_rank_brute(rng):
    n = 800
    px, py = rng.uniform(0, 10, n), rng.uniform(0, 10, n)
    got = dominance_rank(px, py)
    want = np.array([((px <= a) & (py <= b)).sum() for a, b in zip(px, py)])
    assert (got == want).all()


def test_merge_sort_tree_rect(rng):
    n = 2000
    px, py = rng.normal(0, 3, n), rng.normal(0, 3, n)
    t = MergeSortTree.build(px, py)
    x0 = rng.uniform(-5, 5, 100); x1 = x0 + rng.uniform(0, 4, 100)
    y0 = rng.uniform(-5, 5, 100); y1 = y0 + rng.uniform(0, 4, 100)
    got = np.asarray(t.query(jnp.asarray(x0), jnp.asarray(x1),
                             jnp.asarray(y0), jnp.asarray(y1)))
    want = np.array([((px >= a) & (px <= b) & (py >= c) & (py <= d)).sum()
                     for a, b, c, d in zip(x0, x1, y0, y1)])
    assert (got == want).all()


@pytest.mark.parametrize("deg", [2, 3])
def test_quadtree_count_guarantee(deg):
    """Lemma 6.3: delta = eps_abs/4 ==> |A - R| <= eps_abs (empirically, at
    rectangle corners drawn near data — the paper's workload)."""
    px, py = osm_points(20_000, seed=5)
    eps_abs = 200.0
    idx = build_index_2d(px, py, deg=deg, delta=eps_abs / 4)
    x0, x1, y0, y1 = make_queries_2d(px, py, 300, seed=9)
    res = query_count_2d(idx, x0, x1, y0, y1)
    t = idx.exact
    truth = np.asarray(
        t.cf(jnp.asarray(x1), jnp.asarray(y1)) - t.cf(jnp.asarray(x0), jnp.asarray(y1))
        - t.cf(jnp.asarray(x1), jnp.asarray(y0)) + t.cf(jnp.asarray(x0), jnp.asarray(y0)))
    err = np.abs(np.asarray(res.answer) - truth)
    assert err.max() <= eps_abs + 1e-6


def test_quadtree_rel_guarantee():
    px, py = osm_points(20_000, seed=6)
    idx = build_index_2d(px, py, deg=3, delta=25.0)
    x0, x1, y0, y1 = make_queries_2d(px, py, 300, seed=11, frac=0.2)
    eps_rel = 0.05
    res = query_count_2d(idx, x0, x1, y0, y1, eps_rel=eps_rel)
    t = idx.exact
    truth = np.asarray(
        t.cf(jnp.asarray(x1), jnp.asarray(y1)) - t.cf(jnp.asarray(x0), jnp.asarray(y1))
        - t.cf(jnp.asarray(x1), jnp.asarray(y0)) + t.cf(jnp.asarray(x0), jnp.asarray(y0)))
    pos = truth > 0
    rel = np.abs(np.asarray(res.answer)[pos] - truth[pos]) / truth[pos]
    assert rel.max() <= eps_rel + 1e-9


def test_quadtree_lookup_total():
    """Every point in the root bounding box lands in exactly one leaf."""
    px, py = osm_points(5_000, seed=7)
    idx = build_index_2d(px, py, deg=2, delta=100.0)
    rng = np.random.default_rng(0)
    qx = rng.uniform(px.min(), px.max(), 2000)
    qy = rng.uniform(py.min(), py.max(), 2000)
    leaf = np.asarray(idx.locate(jnp.asarray(qx), jnp.asarray(qy)))
    assert (leaf >= 0).all() and (leaf < idx.n_leaves).all()
    b = np.asarray(idx.bounds)[np.asarray(idx.leaf_nodes)[leaf]]
    assert ((qx >= b[:, 0]) & (qx <= b[:, 1]) & (qy >= b[:, 2]) & (qy <= b[:, 3])).all()


# ---------------------------------------------------------------------------
# measure-carrying extension (DESIGN.md §12): weighted trees, SUM/MAX/MIN
# quadtrees, selective refit
# ---------------------------------------------------------------------------

from repro.core import (query_dommax_2d, query_sum_2d,  # noqa: E402
                        selective_refit_2d)


@pytest.fixture(scope="module")
def wdata():
    rng = np.random.default_rng(0x2D)
    n = 4000
    px, py = rng.uniform(0, 100, n), rng.uniform(0, 100, n)
    w = 50 + 10 * np.sin(px / 10) + 10 * np.cos(py / 15) + rng.uniform(0, 5, n)
    return px, py, w


def test_weighted_mst_exact(wdata):
    """cf_sum / dommax (device and host paths) against brute force."""
    px, py, w = wdata
    t = MergeSortTree.build(px, py, ws=w)
    rng = np.random.default_rng(1)
    qu, qv = rng.uniform(0, 100, 150), rng.uniform(0, 100, 150)
    dom = (px[None, :] <= qu[:, None]) & (py[None, :] <= qv[:, None])
    want_sum = (dom * w[None, :]).sum(axis=1)
    np.testing.assert_allclose(
        np.asarray(t.cf_sum(jnp.asarray(qu), jnp.asarray(qv))), want_sum,
        rtol=1e-12)
    np.testing.assert_allclose(t.cf_sum_np(qu, qv), want_sum, rtol=1e-12)
    want_max = np.where(dom.any(axis=1),
                        np.where(dom, w[None, :], -np.inf).max(axis=1),
                        -np.inf)
    np.testing.assert_array_equal(
        np.asarray(t.dommax(jnp.asarray(qu), jnp.asarray(qv))), want_max)
    np.testing.assert_array_equal(t.dommax_np(qu, qv), want_max)


def test_host_oracles_match_device_twins(wdata):
    """The construction-time host oracles (one searchsorted per level over
    rank keys) equal the device twins' unrolled binary searches exactly,
    ties included: queries sit on data coordinates and on duplicates."""
    px, py, w = wdata
    px = np.concatenate([px, px[:50]])          # duplicate points
    py = np.concatenate([py, py[:50]])
    w = np.concatenate([w, w[:50] + 1.0])
    t = MergeSortTree.build(px, py, ws=w)
    rng = np.random.default_rng(2)
    qu = np.concatenate([rng.uniform(-5, 105, 100), px[:100],
                         [px.min(), px.max()]])
    qv = np.concatenate([rng.uniform(-5, 105, 100), py[100:200],
                         [py.max(), py.min()]])
    ju, jv = jnp.asarray(qu), jnp.asarray(qv)
    np.testing.assert_array_equal(t.cf_np(qu, qv), np.asarray(t.cf(ju, jv)))
    np.testing.assert_array_equal(t.cf_sum_np(qu, qv),
                                  np.asarray(t.cf_sum(ju, jv)))
    np.testing.assert_array_equal(t.dommax_np(qu, qv),
                                  np.asarray(t.dommax(ju, jv)))


def test_unweighted_mst_unchanged(wdata):
    """Weight-free build keeps the old layout (no weighted arrays)."""
    px, py, _ = wdata
    t = MergeSortTree.build(px, py)
    assert t.wcum_levels is None and t.wpmax_levels is None


def test_sum2d_certified_bound(wdata):
    """|A - R| <= 4*delta for rectangle SUM (the Lemma 6.3 shape over the
    weighted CF)."""
    px, py, w = wdata
    delta = 400.0
    idx = build_index_2d(px, py, measures=w, agg="sum2d", deg=2,
                         delta=delta, max_depth=8)
    rng = np.random.default_rng(2)
    lx = rng.uniform(0, 80, 120); ux = lx + rng.uniform(5, 20, 120)
    ly = rng.uniform(0, 80, 120); uy = ly + rng.uniform(5, 20, 120)
    res = query_sum_2d(idx, lx, ux, ly, uy)
    truth = np.array([
        w[(px > a) & (px <= b) & (py > c) & (py <= d)].sum()
        for a, b, c, d in zip(lx, ux, ly, uy)])
    assert np.abs(np.asarray(res.answer) - truth).max() \
        <= 4 * idx.certified_delta + 1e-6
    # Q_rel refinement keeps the relative bound
    resr = query_sum_2d(idx, lx, ux, ly, uy, eps_rel=0.05)
    pos = truth > 0
    rel = np.abs(np.asarray(resr.answer)[pos] - truth[pos]) / truth[pos]
    assert rel.max() <= 0.05 + 1e-9


@pytest.mark.parametrize("agg", ["max2d", "min2d"])
def test_dommax2d_certified_bound(wdata, agg):
    """|A - R| <= delta for dominance MAX/MIN at corners dominating data."""
    px, py, w = wdata
    idx = build_index_2d(px, py, measures=w, agg=agg, deg=2, delta=5.0,
                         max_depth=8)
    rng = np.random.default_rng(3)
    u = px[rng.integers(0, len(px), 120)] + 1e-9
    v = py[rng.integers(0, len(px), 120)] + 1e-9
    res = query_dommax_2d(idx, u, v)
    dom = (px[None, :] <= u[:, None]) & (py[None, :] <= v[:, None])
    red = np.max if agg == "max2d" else np.min
    truth = np.array([red(w[d]) for d in dom])
    assert np.abs(np.asarray(res.answer) - truth).max() \
        <= idx.certified_delta + 1e-6
    resr = query_dommax_2d(idx, u, v, eps_rel=0.05)
    rel = np.abs(np.asarray(resr.answer) - truth) / np.abs(truth)
    assert rel.max() <= 0.05 + 1e-9


def test_leaf_agg_partition(wdata):
    """Per-leaf exact aggregates cover the dataset exactly once."""
    px, py, w = wdata
    idx = build_index_2d(px, py, measures=w, agg="sum2d", deg=2,
                         delta=800.0, max_depth=7)
    assert np.isclose(float(np.asarray(idx.leaf_agg).sum()), w.sum())
    idxm = build_index_2d(px, py, measures=w, agg="max2d", deg=2,
                          delta=8.0, max_depth=7)
    la = np.asarray(idxm.leaf_agg)
    assert np.isclose(la[np.isfinite(la)].max(), w.max())


def test_selective_refit_touches_only_dirty_leaves(wdata):
    """The acceptance invariant: leaves outside every changed point's
    dominance boundary keep their coefficient rows bit for bit; wholly
    dominated leaves change only in the constant term (by the exact
    inserted measure); bounds stay certified."""
    px, py, w = wdata
    delta = 800.0
    idx = build_index_2d(px, py, measures=w, agg="sum2d", deg=2,
                         delta=delta, max_depth=7)
    # one inserted point, well inside the domain
    ins = (np.array([70.0]), np.array([65.0]), np.array([55.0]))
    npx = np.concatenate([px, ins[0]])
    npy = np.concatenate([py, ins[1]])
    npw = np.concatenate([w, ins[2]])
    new_idx, stats = selective_refit_2d(idx, npx, npy, npw,
                                        ins[0], ins[1], ins[2])
    assert not stats["rebuild"] and stats["split"] == 0
    assert stats["refit"] < stats["n_leaves"] // 4   # selectivity

    lb = np.asarray(idx.bounds)[np.asarray(idx.leaf_nodes)]
    old_c = np.asarray(idx.coeffs)
    new_lb = np.asarray(new_idx.bounds)[np.asarray(new_idx.leaf_nodes)]
    new_c = np.asarray(new_idx.coeffs)
    # no splits: leaves correspond 1:1 by bounds
    assert len(lb) == len(new_lb)
    x0, y0, wv = float(ins[0][0]), float(ins[1][0]), float(ins[2][0])
    n_same = n_shift = n_refit = 0
    for i, b in enumerate(lb):
        j = int(np.where((new_lb == b).all(axis=1))[0][0])
        untouched = b[1] < x0 or b[3] < y0
        dominated = b[0] >= x0 and b[2] >= y0
        if untouched:
            np.testing.assert_array_equal(old_c[i], new_c[j])
            n_same += 1
        elif dominated:
            assert new_c[j][0] == old_c[i][0] + wv   # exact constant bump
            np.testing.assert_array_equal(old_c[i][1:], new_c[j][1:])
            n_shift += 1
        else:
            n_refit += 1
    assert n_refit == stats["refit"] and n_shift == stats["shifted"]
    assert n_same > 0 and n_shift > 0 and n_refit > 0

    # certified bound holds over the merged dataset
    rng = np.random.default_rng(4)
    lx = rng.uniform(0, 80, 80); ux = lx + rng.uniform(5, 20, 80)
    ly = rng.uniform(0, 80, 80); uy = ly + rng.uniform(5, 20, 80)
    res = query_sum_2d(new_idx, lx, ux, ly, uy)
    truth = np.array([
        npw[(npx > a) & (npx <= b) & (npy > c) & (npy <= d)].sum()
        for a, b, c, d in zip(lx, ux, ly, uy)])
    assert np.abs(np.asarray(res.answer) - truth).max() \
        <= 4 * new_idx.certified_delta + 1e-6


def test_selective_refit_out_of_root_falls_back(wdata):
    """Points outside the frozen root rectangle force a full rebuild."""
    px, py, w = wdata
    idx = build_index_2d(px, py, measures=w, agg="sum2d", deg=2,
                         delta=800.0, max_depth=6)
    npx = np.concatenate([px, [150.0]])
    npy = np.concatenate([py, [50.0]])
    npw = np.concatenate([w, [10.0]])
    new_idx, stats = selective_refit_2d(
        idx, npx, npy, npw, np.array([150.0]), np.array([50.0]),
        np.array([10.0]))
    assert stats["rebuild"]
    assert float(new_idx.root_bounds[1]) >= 150.0


def test_selective_refit_splits_when_certificate_fails(wdata):
    """A dense insert burst inside one leaf deepens the tree in place."""
    px, py, w = wdata
    idx = build_index_2d(px, py, measures=w, agg="count2d", deg=2,
                         delta=40.0, max_depth=9)
    # 300 duplicated-ish points in a tiny box: the covering leaf's count CF
    # jumps too sharply for its old fit
    rng = np.random.default_rng(5)
    bx = rng.uniform(42.0, 42.5, 300)
    by = rng.uniform(42.0, 42.5, 300)
    bw = np.ones(300)
    npx = np.concatenate([px, bx])
    npy = np.concatenate([py, by])
    npw = np.concatenate([np.ones_like(px), bw])
    new_idx, stats = selective_refit_2d(idx, npx, npy, npw, bx, by, bw)
    assert not stats["rebuild"]
    assert stats["split"] >= 1
    assert new_idx.n_leaves > idx.n_leaves
