"""Engine integration: every backend must answer every query type within
the paper's certified error bounds, and the backends must agree with each
other (and with the core reference path) on identical query batches."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro.core import (ExactMax, ExactSum, build_index_1d,  # noqa: E402
                        build_index_2d, query_count_2d, query_max, query_sum)
from repro.engine import (BACKENDS, Engine, build_plan,  # noqa: E402
                          build_plan_2d, refine_slots)
import repro.engine.engine as engine_mod  # noqa: E402
from repro.engine.engine import _exec_sum, raw_sum, truth_sum  # noqa: E402

N = 3000
NQ = 400
DELTA = 25.0


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    keys = np.sort(rng.uniform(0, 800, N))
    meas = rng.uniform(0, 10, N)
    return keys, meas


@pytest.fixture(scope="module")
def queries(data):
    keys, _ = data
    rng = np.random.default_rng(11)
    a = keys[rng.integers(0, N, NQ)]
    b = keys[rng.integers(0, N, NQ)]
    return np.minimum(a, b), np.maximum(a, b)


@pytest.fixture(scope="module")
def plans(data):
    keys, meas = data
    out = {}
    for agg, m, deg in (("sum", meas, 2), ("count", None, 2),
                        ("max", meas * 100, 3), ("min", meas * 100, 3)):
        idx = build_index_1d(keys, m, agg, deg=deg, delta=DELTA)
        out[agg] = (idx, build_plan(idx))
    return out


@pytest.fixture(scope="module")
def plan2d():
    rng = np.random.default_rng(13)
    px = rng.uniform(0, 120, 5000)
    py = rng.uniform(0, 120, 5000)
    idx = build_index_2d(px, py, deg=2, delta=DELTA, max_depth=6)
    qa = rng.uniform(0, 120, 256)
    qb = qa + rng.uniform(0.5, 40, 256)
    qc = rng.uniform(0, 120, 256)
    qd = qc + rng.uniform(0.5, 40, 256)
    return px, py, idx, build_plan_2d(idx), (qa, qb, qc, qd)


def _truth_1d(agg, keys, meas, lq, uq):
    if agg in ("sum", "count"):
        m = np.ones_like(keys) if agg == "count" else meas
        ex = ExactSum.build(keys, m)
        return np.asarray(ex.cf_at(jnp.asarray(uq)) - ex.cf_at(jnp.asarray(lq)))
    sgn = -1.0 if agg == "min" else 1.0
    ex = ExactMax.build(keys, sgn * meas)
    return sgn * np.asarray(ex.query(jnp.asarray(lq), jnp.asarray(uq)))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("agg", ["sum", "count", "max", "min"])
def test_certified_bounds_1d(plans, data, queries, agg, backend):
    """Lemma 5.1/5.3: every backend's raw answer obeys the Q_abs bound."""
    keys, meas = data
    lq, uq = queries
    _, plan = plans[agg]
    res = Engine(backend=backend).query(plan, lq, uq)
    truth = _truth_1d(agg, keys, meas * 100 if agg in ("max", "min") else meas,
                      lq, uq)
    bound = 2 * DELTA if agg in ("sum", "count") else DELTA
    assert np.max(np.abs(np.asarray(res.answer) - truth)) <= bound + 1e-6


@pytest.mark.parametrize("agg", ["sum", "count", "max", "min"])
def test_cross_backend_equivalence_1d(plans, queries, agg):
    """All three backends produce identical f64 answers (and match core)."""
    idx, plan = plans[agg]
    lq, uq = queries
    outs = {b: np.asarray(Engine(backend=b).query(plan, lq, uq).answer)
            for b in BACKENDS}
    for b in ("pallas", "ref"):
        np.testing.assert_allclose(outs[b], outs["xla"], rtol=1e-9, atol=1e-9)
    qfn = query_sum if agg in ("sum", "count") else query_max
    core = np.asarray(qfn(idx, lq, uq).answer)
    np.testing.assert_allclose(outs["xla"], core, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("agg", ["sum", "max"])
def test_fused_qrel_refinement(plans, data, queries, agg, backend):
    """Lemma 5.2/5.4 + in-path refinement: final answers satisfy eps_rel."""
    keys, meas = data
    lq, uq = queries
    _, plan = plans[agg]
    eps_rel = 0.05
    res = Engine(backend=backend).query(plan, lq, uq, eps_rel=eps_rel)
    truth = _truth_1d(agg, keys, meas * 100 if agg == "max" else meas, lq, uq)
    ans = np.asarray(res.answer)
    pos = np.abs(truth) > 0
    rel = np.abs(ans[pos] - truth[pos]) / np.abs(truth[pos])
    assert rel.max() <= eps_rel + 1e-9
    # the index must stay useful: refinement cannot fire on every query
    assert np.asarray(res.refined).mean() < 1.0


@pytest.mark.parametrize("backend", BACKENDS)
def test_certified_bounds_2d(plan2d, backend):
    """Lemma 6.3: 2-key COUNT within 4*delta on every backend."""
    px, py, idx, plan, (qa, qb, qc, qd) = plan2d
    res = Engine(backend=backend).query(plan, qa, qb, qc, qd)
    truth = np.asarray(idx.exact.cf(qb, qd) - idx.exact.cf(qa, qd)
                       - idx.exact.cf(qb, qc) + idx.exact.cf(qa, qc))
    assert np.max(np.abs(np.asarray(res.answer) - truth)) <= 4 * DELTA + 1e-6


def test_cross_backend_equivalence_2d(plan2d):
    px, py, idx, plan, (qa, qb, qc, qd) = plan2d
    outs = {b: np.asarray(Engine(backend=b).count2d(plan, qa, qb, qc, qd).answer)
            for b in BACKENDS}
    for b in ("pallas", "ref"):
        np.testing.assert_allclose(outs[b], outs["xla"], rtol=1e-9, atol=1e-9)
    core = np.asarray(query_count_2d(idx, qa, qb, qc, qd).answer)
    np.testing.assert_allclose(outs["xla"], core, rtol=1e-9, atol=1e-9)


def test_qrel_2d_fused(plan2d):
    px, py, idx, plan, (qa, qb, qc, qd) = plan2d
    eps_rel = 0.05
    res = Engine(backend="ref").count2d(plan, qa, qb, qc, qd, eps_rel=eps_rel)
    truth = np.asarray(idx.exact.cf(qb, qd) - idx.exact.cf(qa, qd)
                       - idx.exact.cf(qb, qc) + idx.exact.cf(qa, qc))
    ans = np.asarray(res.answer)
    pos = truth > 0
    rel = np.abs(ans[pos] - truth[pos]) / truth[pos]
    assert rel.max() <= eps_rel + 1e-9


@pytest.mark.parametrize("nq", [3, 64, 130, 700])
def test_batch_bucketing_consistency(plans, data, nq):
    """Padding to power-of-two buckets must not change any answer."""
    keys, meas = data
    rng = np.random.default_rng(nq)
    a = keys[rng.integers(0, N, nq)]
    b = keys[rng.integers(0, N, nq)]
    lq, uq = np.minimum(a, b), np.maximum(a, b)
    _, plan = plans["sum"]
    eng = Engine(backend="pallas")
    got = np.asarray(eng.sum(plan, lq, uq).answer)
    assert got.shape == (nq,)
    ref = np.asarray(Engine(backend="xla").sum(plan, lq, uq).answer)
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-9)


def test_pallas_deg4_max_falls_back(data, queries):
    """deg-4 MAX has no in-kernel closed form; the engine must still answer
    within the certified bound on the pallas backend (XLA fallback)."""
    keys, meas = data
    idx = build_index_1d(keys, meas * 100, "max", deg=4, delta=DELTA)
    plan = build_plan(idx)
    lq, uq = queries
    res = Engine(backend="pallas").extremum(plan, lq, uq)
    truth = _truth_1d("max", keys, meas * 100, lq, uq)
    assert np.max(np.abs(np.asarray(res.answer) - truth)) <= DELTA + 1e-6


def test_refinement_requires_exact_arrays(data):
    keys, meas = data
    idx = build_index_1d(keys, meas, "sum", deg=2, delta=DELTA,
                         keep_exact=False)
    plan = build_plan(idx)
    with pytest.raises(ValueError, match="refinement"):
        Engine().sum(plan, keys[:4], keys[-4:], eps_rel=0.01)


def test_serve_step_routes_through_engine(plans, queries):
    from repro.serve.step import make_aggregate_step
    _, plan = plans["count"]
    lq, uq = queries
    step = make_aggregate_step(Engine(backend="ref"), plan, eps_rel=0.05)
    res = step(lq, uq)
    assert res.answer.shape == (NQ,)
    assert np.asarray(res.refined).mean() < 1.0


# ---------------------------------------------------------------------------
# compacted Q_rel refinement in the static SUM/COUNT executor
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fine_count(data):
    """A COUNT plan fine enough that wide ranges pass the Lemma 5.2 test
    (2 * delta * (1 + 1/0.01) = 404 keys) and narrow ones are flagged."""
    keys, _ = data
    return keys, build_plan(build_index_1d(keys, None, "count", deg=2,
                                           delta=2.0))


@jax.jit
def _whole_batch_sum(plan, lq, uq):
    """The executor's refinement before compaction: the exact search on
    every row of the bucket, kept where the Lemma 5.2 test fails."""
    dt = plan.dtype
    lqc = jnp.maximum(lq.astype(dt), plan.domain_lo)
    uqc = jnp.maximum(uq.astype(dt), plan.domain_lo)
    approx = raw_sum(plan, lqc, uqc, backend="xla", interpret=None, bq=256)
    two_d = 2.0 * plan.delta
    ok = ((approx - two_d > 0) &
          (two_d / jnp.maximum(approx - two_d, 1e-300) <= 0.01))
    return jnp.where(ok, approx, truth_sum(plan, lq, uq)), approx, ~ok


def _flagged_bucket(keys, domain_lo, size, m, rng):
    """``size`` shuffled rows, ``m`` of them flagged with ``lq != uq``
    (narrow ranges and reversed ones, ``lq > uq``); the rest are wide
    ranges that pass, padding rows (``lq == uq == domain_lo``) and empty
    ranges ``lq == uq`` inside the domain, both flagged but searched by
    neither path."""
    i = rng.integers(0, N - 1100, size)
    narrow = (keys[i], keys[i + 3])
    kinds = np.where(np.arange(size) < m, np.arange(size) % 2,
                     2 + np.arange(size) % 3)
    lq = np.select([kinds == 0, kinds == 1, kinds == 2, kinds == 3],
                   [narrow[0], narrow[1], keys[i], domain_lo], keys[i])
    uq = np.select([kinds == 0, kinds == 1, kinds == 2, kinds == 3],
                   [narrow[1], narrow[0], keys[i + 1000], domain_lo],
                   keys[i])
    perm = rng.permutation(size)
    return lq[perm], uq[perm]


@pytest.mark.parametrize("size", [64, 1024])
@pytest.mark.parametrize("flagged", ["none", "one", "slots", "slots+1",
                                     "all"])
def test_compacted_refinement_bit_identical(fine_count, size, flagged,
                                           monkeypatch):
    """Refining only the flagged rows, in ``refine_slots`` slots or on the
    whole bucket when they overflow, gives the whole-batch formula's
    answers, approximations and flags bit for bit; the search runs on the
    slots unless the flagged non-empty ranges outnumber them."""
    keys, plan = fine_count
    k = refine_slots(size)
    m = {"none": 0, "one": 1, "slots": k, "slots+1": k + 1,
         "all": size}[flagged]
    lq, uq = _flagged_bucket(keys, float(plan.domain_lo), size, m,
                             np.random.default_rng(size + m))
    got = _exec_sum(plan, jnp.asarray(lq), jnp.asarray(uq), backend="xla",
                    eps_rel=0.01, interpret=None, bq=256)
    want = _whole_batch_sum(plan, jnp.asarray(lq), jnp.asarray(uq))
    refined = np.asarray(got[2])
    assert np.count_nonzero(refined & (lq != uq)) == m
    if m < size:
        assert (~refined).any() and (refined & (lq == uq)).any()
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype
        assert np.array_equal(g.view(np.uint8), w.view(np.uint8))
    searched = []

    def spy(plan, lq, uq):   # records the rows of the search that ran
        jax.debug.callback(lambda: searched.append(lq.shape[0]))
        return truth_sum(plan, lq, uq)
    monkeypatch.setattr(engine_mod, "truth_sum", spy)
    jax.block_until_ready(jax.jit(lambda *a: engine_mod._refine_sum(*a))(
        plan, jnp.asarray(lq), jnp.asarray(uq), want[1], ~want[2]))
    jax.effects_barrier()
    assert searched == [k if m <= k else size]


@pytest.mark.parametrize("size", [64, 128, 256, 512, 1024])
def test_refine_slots_from_the_bucket_alone(size):
    """A sixteenth of the bucket, at least 8: fewer slots than rows at
    every served bucket, so every one compacts."""
    k = refine_slots(size)
    assert k == max(8, size // 16)
    assert 8 <= k < size and size % k == 0


# ---------------------------------------------------------------------------
# 2-D measure aggregates (DESIGN.md §12): SUM over rectangles, dominance
# MAX/MIN — every backend agrees and stays within the certified bound
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def plans2d_measure():
    rng = np.random.default_rng(17)
    px = rng.uniform(0, 120, 4000)
    py = rng.uniform(0, 120, 4000)
    w = 50 + 10 * np.sin(px / 10) + 10 * np.cos(py / 15)
    out = {}
    for agg, delta in (("sum2d", 400.0), ("max2d", 4.0), ("min2d", 4.0)):
        idx = build_index_2d(px, py, measures=w, agg=agg, deg=2,
                             delta=delta, max_depth=7)
        out[agg] = (idx, build_plan_2d(idx))
    rect = (rng.uniform(0, 95, 256), None, rng.uniform(0, 95, 256), None)
    rect = (rect[0], rect[0] + rng.uniform(2, 25, 256),
            rect[2], rect[2] + rng.uniform(2, 25, 256))
    ci = rng.integers(0, 4000, 256)   # anchored at data points, so every
    corners = (px[ci], py[ci])        # corner dominates at least one record
    return px, py, w, out, rect, corners


@pytest.mark.parametrize("backend", BACKENDS)
def test_certified_bounds_sum2d(plans2d_measure, backend):
    px, py, w, plans, rect, _ = plans2d_measure
    idx, plan = plans["sum2d"]
    res = Engine(backend=backend).sum2d(plan, *rect)
    la, ua, lb, ub = rect
    truth = np.array([
        w[(px > a) & (px <= b) & (py > c) & (py <= d)].sum()
        for a, b, c, d in zip(la, ua, lb, ub)])
    assert np.abs(np.asarray(res.answer) - truth).max() \
        <= 4 * idx.certified_delta + 1e-6


@pytest.mark.parametrize("agg", ["max2d", "min2d"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_certified_bounds_dommax2d(plans2d_measure, agg, backend):
    px, py, w, plans, _, corners = plans2d_measure
    idx, plan = plans[agg]
    u, v = corners
    res = Engine(backend=backend).extremum2d(plan, u, v)
    dom = (px[None, :] <= u[:, None]) & (py[None, :] <= v[:, None])
    red = np.max if agg == "max2d" else np.min
    truth = np.array([red(w[d]) for d in dom])
    assert np.abs(np.asarray(res.answer) - truth).max() \
        <= idx.certified_delta + 1e-6


@pytest.mark.parametrize("agg", ["sum2d", "max2d", "min2d"])
def test_cross_backend_equivalence_2d_measures(plans2d_measure, agg):
    """All four backends agree bitwise on the 2-D measure aggregates (the
    locate->gather, one-hot scan, jnp oracle and descent paths share one
    leaf rule and one Horner sequence)."""
    px, py, w, plans, rect, corners = plans2d_measure
    _, plan = plans[agg]
    ranges = rect if agg == "sum2d" else corners
    outs = {b: np.asarray(Engine(backend=b).query(plan, *ranges).answer)
            for b in BACKENDS}
    for b in BACKENDS[1:]:
        np.testing.assert_array_equal(outs[b], outs["xla"], err_msg=b)


@pytest.mark.parametrize("agg", ["sum2d", "max2d"])
def test_qrel_2d_measures_fused(plans2d_measure, agg):
    px, py, w, plans, rect, corners = plans2d_measure
    idx, plan = plans[agg]
    eps_rel = 0.05
    if agg == "sum2d":
        la, ua, lb, ub = rect
        res = Engine(backend="ref").sum2d(plan, *rect, eps_rel=eps_rel)
        truth = np.array([
            w[(px > a) & (px <= b) & (py > c) & (py <= d)].sum()
            for a, b, c, d in zip(la, ua, lb, ub)])
    else:
        u, v = corners
        res = Engine(backend="pallas").extremum2d(plan, u, v,
                                                  eps_rel=eps_rel)
        dom = (px[None, :] <= u[:, None]) & (py[None, :] <= v[:, None])
        truth = np.array([w[d].max() for d in dom])
    ans = np.asarray(res.answer)
    pos = np.abs(truth) > 0
    rel = np.abs(ans[pos] - truth[pos]) / np.abs(truth[pos])
    assert rel.max() <= eps_rel + 1e-9


def test_execute_dispatch_2d_aggs(plans2d_measure):
    """`execute` routes IndexPlan2D by its agg; mismatched executors
    refuse the plan."""
    from repro.engine import execute, execute_count2d, execute_sum2d
    _, _, _, plans, rect, corners = plans2d_measure
    _, plan_s = plans["sum2d"]
    _, plan_m = plans["max2d"]
    r1 = execute(plan_s, rect, backend="ref")
    r2 = execute_sum2d(plan_s, *rect, backend="ref")
    np.testing.assert_array_equal(np.asarray(r1.answer),
                                  np.asarray(r2.answer))
    r3 = execute(plan_m, corners, backend="ref")
    assert r3.answer.shape == corners[0].shape
    with pytest.raises(AssertionError):
        execute_count2d(plan_s, *rect)
