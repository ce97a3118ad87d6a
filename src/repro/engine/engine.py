"""Backend-dispatched query engine: one fused execution path per
(aggregate, backend, batch-bucket).

``Engine`` executes SUM/COUNT/MAX/MIN (1 key) and COUNT (2 keys) against
``IndexPlan``/``IndexPlan2D`` through a pluggable backend:

* ``'xla'``         — searchsorted locate + gather + Horner, sparse-table
                      interior MAX (the reference semantics of
                      ``core.queries``);
* ``'pallas'``      — the locate->gather TPU kernels (DESIGN.md §10):
                      branch-free binary search resolves each endpoint in
                      O(log H), then exactly one coefficient row is
                      gathered and evaluated (interpret mode on CPU);
* ``'pallas_scan'`` — the original one-hot membership kernels, O(Q*H) per
                      batch — kept for A/B benchmarking (the H-sweep in
                      benchmarks/bench_kernels.py shows the crossover);
* ``'ref'``         — pure-jnp oracles mirroring the kernel contracts.

Every path is a single jitted function that computes the raw approximation,
applies the Lemma 5.2/5.4 (or 6.4) Q_rel acceptance test, and merges the
vectorized exact refinement with ``jnp.where`` — the refinement arrays live
inside the plan, so there is no host round trip and no per-query Python
dispatch.  Batches are padded to power-of-two buckets before entering the
jitted path: compilation count is bounded by the number of distinct
(aggregate, backend, bucket) triples, and plans with identical layouts share
compilations (plan metadata is static, arrays are traced).

Q_abs guarantees need no test: build the index with delta = eps_abs/2 (SUM,
Lemma 5.1), eps_abs (MAX, Lemma 5.3) or eps_abs/4 (2-D COUNT, Lemma 6.3)
and the raw answer already satisfies the bound.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from ..core.exact import sparse_table_range_max
from ..core.index2d import mst_cf, mst_cf_sum, mst_dommax, quadtree_eval_cf
from ..core.poly import eval_segments, horner
from ..core.quantile import (boundary_array, certified_quantile_shifted,
                             rank_slack)
from ..core.queries import QueryResult, max_eval_segments
from ..kernels import ref as _ref
from ..kernels.leaf_eval2d import (corner_count2d_gather_pallas,
                                   corner_count2d_pallas,
                                   corner_eval2d_gather_pallas,
                                   corner_eval2d_pallas)
from ..kernels.poly_eval import DEFAULT_BQ, resolve_interpret
from ..kernels.quantile_invert import quantile_invert_pallas
from ..kernels.range_max import range_max_gather_pallas, range_max_pallas
from ..kernels.range_sum import range_sum_gather_pallas, range_sum_pallas
from .plan import IndexPlan, IndexPlan2D, big_sentinel, pad_to_multiple

__all__ = ["Engine", "BACKENDS", "QuantileResult", "raw_sum",
           "raw_extremum", "raw_count2d", "raw_eval2d", "truth_sum",
           "truth_extremum", "truth_count2d", "truth_sum2d",
           "truth_dommax2d", "check_pow2", "execute_sum",
           "execute_extremum", "execute_quantile", "execute_count2d",
           "execute_sum2d", "execute_extremum2d", "execute", "pad_fills"]


class QuantileResult(NamedTuple):
    """Certified quantile triple: ``lo <= answer <= hi`` everywhere, and
    [lo, hi] brackets the exact quantile key (DESIGN.md §16)."""
    answer: jnp.ndarray
    lo: jnp.ndarray
    hi: jnp.ndarray

BACKENDS = ("xla", "pallas", "pallas_scan", "ref")


def check_pow2(name: str, v: int) -> None:
    """Bucket/tile/capacity sizes must be powers of two (so smaller ones
    always divide larger ones)."""
    if v < 1 or v & (v - 1):
        raise ValueError(f"{name} must be a power of two, got {v}")


def _bucket_size(n: int, min_bucket: int) -> int:
    b = max(min_bucket, 1)
    while b < n:
        b <<= 1
    return b


def _pad_bucket(q: jnp.ndarray, size: int, fill) -> jnp.ndarray:
    p = size - q.shape[0]
    if p == 0:
        return q
    return jnp.concatenate([q, jnp.full((p,), fill, q.dtype)])


def pad_fills(plan: Union[IndexPlan, IndexPlan2D]):
    """Per-range-coordinate padding fills for bucketed batches — the same
    values the ``execute_*`` entry points pad with, exposed so external
    batchers (the serving engine's admission path) produce bit-identical
    padded batches."""
    if hasattr(plan, "levels"):   # LSM ladder: every level shares the fills
        plan = plan.levels[0].plan
    if isinstance(plan, IndexPlan2D):
        x0, _, y0, _ = plan.root
        if plan.agg in ("max2d", "min2d"):
            return (x0, y0)
        return (x0, x0, y0, y0)
    return (plan.domain_lo, plan.domain_lo)


def _cf_at(keys, cf, q):
    """Inclusive prefix CF at q: sum of measures with key <= q."""
    idx = jnp.searchsorted(keys, q, side="right")
    padded = jnp.concatenate([jnp.zeros((1,), cf.dtype), cf])
    return padded[idx]


# ---------------------------------------------------------------------------
# shared raw-approximation / static-truth primitives (traced inside jit by
# both the static executors below and the dynamic ones in dynamic.py)
# ---------------------------------------------------------------------------

def raw_sum(plan: IndexPlan, lqc, uqc, *, backend: str,
            interpret: Optional[bool], bq: int):
    """Backend-dispatched raw SUM/COUNT approximation (clamped queries)."""
    if backend == "pallas":
        return range_sum_gather_pallas(lqc, uqc, plan.seg_lo, plan.seg_hi,
                                       plan.coeffs, bq=bq,
                                       interpret=interpret)
    if backend == "pallas_scan":
        return range_sum_pallas(lqc, uqc, plan.seg_lo, plan.seg_next,
                                plan.seg_hi, plan.coeffs,
                                bq=bq, bh=plan.bh, interpret=interpret)
    if backend == "ref":
        return _ref.range_sum_ref(lqc, uqc, plan.seg_lo, plan.seg_next,
                                  plan.seg_hi, plan.coeffs)
    return (eval_segments(uqc, plan.seg_lo, plan.seg_hi, plan.coeffs)
            - eval_segments(lqc, plan.seg_lo, plan.seg_hi, plan.coeffs))


def raw_extremum(plan: IndexPlan, lqc, uqc, *, backend: str,
                 interpret: Optional[bool], bq: int):
    """Backend-dispatched raw MAX approximation, in MAX space (MIN plans run
    on negated measures end to end)."""
    if backend == "pallas":
        return range_max_gather_pallas(lqc, uqc, plan.seg_lo, plan.seg_hi,
                                       plan.coeffs, plan.st, bq=bq,
                                       interpret=interpret)
    if backend == "pallas_scan":
        return range_max_pallas(lqc, uqc, plan.seg_lo, plan.seg_next,
                                plan.seg_hi, plan.coeffs, plan.seg_agg,
                                bq=bq, bh=plan.bh, interpret=interpret)
    if backend == "ref":
        return _ref.range_max_ref(lqc, uqc, plan.seg_lo, plan.seg_next,
                                  plan.seg_hi, plan.coeffs, plan.seg_agg)
    return max_eval_segments(plan.seg_lo, plan.seg_hi, plan.coeffs,
                             plan.st, lqc, uqc)


def raw_count2d(plan: IndexPlan2D, lxc, uxc, lyc, uyc, *, backend: str,
                interpret: Optional[bool], bq: int):
    """Backend-dispatched raw 2-key COUNT approximation (clamped corners)."""
    if backend == "pallas" and plan.leaf_z is not None:
        return corner_count2d_gather_pallas(
            lxc, uxc, lyc, uyc, plan.xcuts, plan.ycuts, plan.leaf_z,
            plan.leaf_bounds, plan.leaf_coeffs, deg=plan.deg,
            depth=plan.max_depth, bq=bq, interpret=interpret)
    if backend in ("pallas", "pallas_scan"):
        # scan fallback: plans whose depth exceeds the Morton int32 range
        return corner_count2d_pallas(
            lxc, uxc, lyc, uyc, plan.leaf_mx0, plan.leaf_mx1, plan.leaf_my0,
            plan.leaf_my1, plan.leaf_bounds, plan.leaf_coeffs,
            deg=plan.deg, bq=bq, bh=plan.bh, interpret=interpret)
    if backend == "ref":
        return _ref.corner_count2d_ref(
            lxc, uxc, lyc, uyc, plan.leaf_mx0, plan.leaf_mx1, plan.leaf_my0,
            plan.leaf_my1, plan.leaf_bounds, plan.leaf_coeffs, plan.deg)
    ev = lambda u, v: quadtree_eval_cf(
        plan.children, plan.leaf_of, plan.bounds, plan.qt_coeffs,
        plan.leaf_nodes, plan.max_depth, plan.deg, u, v)
    return ev(uxc, uyc) - ev(lxc, uyc) - ev(uxc, lyc) + ev(lxc, lyc)


def raw_eval2d(plan: IndexPlan2D, uc, vc, *, backend: str,
               interpret: Optional[bool], bq: int):
    """Backend-dispatched single-corner evaluation P_{leaf(u,v)}(u, v) —
    the dominance MAX/MIN query path (clamped corners).  Dominance queries
    touch exactly one leaf, so there is no inclusion-exclusion step."""
    if backend == "pallas" and plan.leaf_z is not None:
        return corner_eval2d_gather_pallas(
            uc, vc, plan.xcuts, plan.ycuts, plan.leaf_z, plan.leaf_bounds,
            plan.leaf_coeffs, deg=plan.deg, depth=plan.max_depth, bq=bq,
            interpret=interpret)
    if backend in ("pallas", "pallas_scan"):
        # scan fallback: plans whose depth exceeds the Morton int32 range
        return corner_eval2d_pallas(
            uc, vc, plan.leaf_mx0, plan.leaf_mx1, plan.leaf_my0,
            plan.leaf_my1, plan.leaf_bounds, plan.leaf_coeffs,
            deg=plan.deg, bq=bq, bh=plan.bh, interpret=interpret)
    if backend == "ref":
        return _ref.leaf_eval2d_ref(
            uc, vc, plan.leaf_mx0, plan.leaf_mx1, plan.leaf_my0,
            plan.leaf_my1, plan.leaf_bounds, plan.leaf_coeffs, plan.deg)
    return quadtree_eval_cf(plan.children, plan.leaf_of, plan.bounds,
                            plan.qt_coeffs, plan.leaf_nodes, plan.max_depth,
                            plan.deg, uc, vc)


def truth_sum(plan: IndexPlan, lq, uq):
    """Exact static SUM/COUNT over (lq, uq] from the plan's refinement CF."""
    return _cf_at(plan.ref_keys, plan.ref_cf, uq) - _cf_at(
        plan.ref_keys, plan.ref_cf, lq)


def truth_extremum(plan: IndexPlan, lq, uq):
    """Exact static MAX over [lq, uq] (MAX space) from the refinement table."""
    i = jnp.searchsorted(plan.ref_keys, lq, side="left")
    j = jnp.searchsorted(plan.ref_keys, uq, side="right")
    return sparse_table_range_max(plan.ref_st, i, j)


def truth_count2d(plan: IndexPlan2D, lx, ux, ly, uy):
    """Exact static 2-key COUNT over (lx, ux] x (ly, uy] (merge-sort tree)."""
    cf = lambda u, v: mst_cf(plan.ref_xs, plan.ref_ys_levels, u, v)
    return (cf(ux, uy) - cf(lx, uy) - cf(ux, ly) + cf(lx, ly)).astype(
        plan.dtype)


def truth_sum2d(plan: IndexPlan2D, lx, ux, ly, uy):
    """Exact static 2-key SUM over (lx, ux] x (ly, uy] (weighted tree)."""
    cf = lambda u, v: mst_cf_sum(plan.ref_xs, plan.ref_ys_levels,
                                 plan.ref_wcum, u, v)
    return (cf(ux, uy) - cf(lx, uy) - cf(ux, ly) + cf(lx, ly)).astype(
        plan.dtype)


def truth_dommax2d(plan: IndexPlan2D, u, v):
    """Exact static dominance MAX over {x <= u, y <= v}, in MAX space
    (-inf when the dominated set is empty)."""
    return mst_dommax(plan.ref_xs, plan.ref_ys_levels, plan.ref_wpmax,
                      u, v).astype(plan.dtype)


# ---------------------------------------------------------------------------
# fused jitted executors (one compilation per static signature)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_sum(plan: IndexPlan, lq, uq, *, backend: str,
              eps_rel: Optional[float], interpret: Optional[bool], bq: int):
    dt = plan.dtype
    lqc = jnp.maximum(lq.astype(dt), plan.domain_lo)
    uqc = jnp.maximum(uq.astype(dt), plan.domain_lo)
    with jax.named_scope("approx"):
        approx = raw_sum(plan, lqc, uqc, backend=backend,
                         interpret=interpret, bq=bq)
    if eps_rel is None:
        return approx, approx, jnp.zeros(approx.shape, bool)
    # Lemma 5.2 test: 2d / (A - 2d) <= eps_rel  (requires A > 2d)
    two_d = 2.0 * plan.delta
    ok = ((approx - two_d > 0) &
          (two_d / jnp.maximum(approx - two_d, 1e-300) <= eps_rel))
    with jax.named_scope("refine"):
        truth = truth_sum(plan, lq, uq)
    return jnp.where(ok, approx, truth), approx, ~ok


@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_extremum(plan: IndexPlan, lq, uq, *, backend: str,
                   eps_rel: Optional[float],
                   interpret: Optional[bool], bq: int):
    dt = plan.dtype
    lqc = jnp.maximum(lq.astype(dt), plan.domain_lo)
    uqc = jnp.maximum(uq.astype(dt), plan.domain_lo)
    with jax.named_scope("approx"):
        approx = raw_extremum(plan, lqc, uqc, backend=backend,
                              interpret=interpret, bq=bq)
    neg = plan.agg == "min"
    if eps_rel is None:
        out = -approx if neg else approx
        return out, out, jnp.zeros(out.shape, bool)
    # Lemma 5.4 test: A >= delta * (1 + 1/eps_rel), in MAX space (MIN runs
    # on negated measures end to end, exactly like core.queries.query_max)
    ok = approx >= plan.delta * (1.0 + 1.0 / eps_rel)
    with jax.named_scope("refine"):
        truth = truth_extremum(plan, lq, uq)
    ans = jnp.where(ok, approx, truth)
    if neg:
        ans, approx = -ans, -approx
    return ans, approx, ~ok


@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_rect2d(plan: IndexPlan2D, lx, ux, ly, uy, *, backend: str,
                 eps_rel: Optional[float], interpret: Optional[bool],
                 bq: int):
    """Shared 4-corner rectangle executor for 2-key COUNT and SUM (the raw
    path is identical — only the exact-refinement truth differs, selected
    at trace time from the plan's static ``agg``)."""
    dt = plan.dtype
    x0, x1, y0, y1 = plan.root
    lxc, uxc = (jnp.clip(q.astype(dt), x0, x1) for q in (lx, ux))
    lyc, uyc = (jnp.clip(q.astype(dt), y0, y1) for q in (ly, uy))
    with jax.named_scope("approx"):
        approx = raw_count2d(plan, lxc, uxc, lyc, uyc, backend=backend,
                             interpret=interpret, bq=bq)
    if eps_rel is None:
        return approx, approx, jnp.zeros(approx.shape, bool)
    # Lemma 6.4 test: A >= 4*delta*(1 + 1/eps_rel)
    ok = approx >= 4.0 * plan.delta * (1.0 + 1.0 / eps_rel)
    with jax.named_scope("refine"):
        truth = (truth_sum2d(plan, lx, ux, ly, uy) if plan.agg == "sum2d"
                 else truth_count2d(plan, lx, ux, ly, uy))
    return jnp.where(ok, approx, truth), approx, ~ok


@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_extremum2d(plan: IndexPlan2D, u, v, *, backend: str,
                     eps_rel: Optional[float],
                     interpret: Optional[bool], bq: int):
    """Dominance MAX/MIN: one fitted-surface evaluation per corner, in MAX
    space throughout (min2d plans are built on negated measures)."""
    dt = plan.dtype
    x0, x1, y0, y1 = plan.root
    uc = jnp.clip(u.astype(dt), x0, x1)
    vc = jnp.clip(v.astype(dt), y0, y1)
    with jax.named_scope("approx"):
        approx = raw_eval2d(plan, uc, vc, backend=backend,
                            interpret=interpret, bq=bq)
    neg = plan.agg == "min2d"
    if eps_rel is None:
        out = -approx if neg else approx
        return out, out, jnp.zeros(out.shape, bool)
    # Lemma 5.4 shape: A >= delta * (1 + 1/eps_rel), in MAX space
    ok = approx >= plan.delta * (1.0 + 1.0 / eps_rel)
    with jax.named_scope("refine"):
        truth = truth_dommax2d(plan, u.astype(dt), v.astype(dt))
    ans = jnp.where(ok, approx, truth)
    if neg:
        ans, approx = -ans, -approx
    return ans, approx, ~ok


# ---------------------------------------------------------------------------
# the dispatch path: one module-level entry per aggregate family.
# Everything public (the Engine shims below, the PolyFit session facade in
# repro.api, the serving layer) routes through these four functions, so
# bucketing, validation and executor selection live exactly once.
# ---------------------------------------------------------------------------

def _prepare(*qs, min_bucket: int, bq: int):
    """Cast to a common device batch + bucket geometry."""
    check_pow2("bq", bq)                # the bucket math below relies on
    check_pow2("min_bucket", min_bucket)  # pow2 sizes (bq divides size)
    qs = [jnp.asarray(q) for q in qs]
    n = qs[0].shape[0]
    size = _bucket_size(n, min_bucket)
    return qs, n, size, min(bq, size)   # both powers of two -> bq | size


def _require_exact(cond: bool):
    if not cond:
        raise ValueError("Q_rel refinement requires a plan built with "
                         "with_exact=True")


def _check_backend(backend: str):
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend}")


def execute_sum(plan: IndexPlan, lq, uq, *, backend: str = "xla",
                eps_rel: Optional[float] = None,
                interpret: Optional[bool] = None,
                bq: int = DEFAULT_BQ, min_bucket: int = 64) -> QueryResult:
    """1-D SUM/COUNT over (lq, uq] through the fused jitted executor."""
    assert plan.agg in ("sum", "count"), plan.agg
    _check_backend(backend)
    if eps_rel is not None:
        _require_exact(plan.ref_cf is not None)
    (lq, uq), n, size, bq = _prepare(lq, uq, min_bucket=min_bucket, bq=bq)
    fill = plan.domain_lo.astype(lq.dtype)
    ans, approx, refined = _exec_sum(
        plan, _pad_bucket(lq, size, fill), _pad_bucket(uq, size, fill),
        backend=backend, eps_rel=eps_rel, interpret=interpret, bq=bq)
    return QueryResult(ans[:n], approx[:n], refined[:n])


@partial(jax.jit, static_argnames=("backend", "interpret", "bq"))
def _exec_quantile(plan: IndexPlan, q, *, backend: str,
                   interpret: Optional[bool], bq: int):
    dt = plan.dtype
    qc = jnp.clip(q.astype(dt), 0.0, 1.0)
    err = (plan.seg_err if plan.seg_err is not None
           else jnp.full_like(plan.seg_lo, plan.delta))
    if plan.agg == "count":
        M = jnp.asarray(float(plan.n), dt)
        slack = rank_slack("count", M)
    elif plan.ref_cf is not None:
        M = plan.ref_cf[-1]          # exact total mass
        slack = rank_slack("sum", M)
    else:
        # fitted total mass is off by at most the top segment's error:
        # widen the rank slack by delta to stay sound
        M = horner(plan.coeffs[plan.h - 1], jnp.asarray(1.0, dt))
        slack = rank_slack("sum", M) + plan.delta
    t = qc * M
    B = boundary_array(plan.coeffs)
    if plan.ref_keys is not None:
        keys = pad_to_multiple(plan.ref_keys, 128, big_sentinel(dt))
        nk = plan.n
    else:
        keys, nk = None, 0
    if backend in ("pallas", "pallas_scan"):
        return quantile_invert_pallas(
            t, t - slack, t + slack, B, plan.seg_lo, plan.seg_hi,
            plan.coeffs, err, keys, h=plan.h, n=nk,
            delta=float(plan.delta), bq=bq, interpret=interpret,
            scan=(backend == "pallas_scan"))
    return certified_quantile_shifted(
        t, t - slack, t + slack, seg_lo=plan.seg_lo, seg_hi=plan.seg_hi,
        coeffs=plan.coeffs, seg_err=err, h=plan.h,
        delta=float(plan.delta), B=B, ref_keys=keys, n=nk,
        scan=(backend == "ref"))


def execute_quantile(plan: IndexPlan, q, *, backend: str = "xla",
                     interpret: Optional[bool] = None, bq: int = DEFAULT_BQ,
                     min_bucket: int = 64) -> QuantileResult:
    """Certified 1-D QUANTILE by CF inversion (DESIGN.md §16).

    ``q`` holds quantile fractions in [0, 1]; works on SUM/COUNT plans
    (COUNT inverts ranks, SUM inverts cumulative measure — the weighted
    quantile).  Q_abs-style certificates only: the returned [lo, hi]
    always brackets the exact quantile key, with no Q_rel refinement
    path (the certificate *is* the guarantee).
    """
    assert plan.agg in ("sum", "count"), plan.agg
    _check_backend(backend)
    if plan.deg < 1:
        raise ValueError("quantile inversion needs a plan with deg >= 1")
    if backend in ("pallas", "pallas_scan") and plan.ref_keys is None:
        backend = "xla"   # the kernel's key-grid snap needs ref_keys
    (q,), n, size, bq = _prepare(q, min_bucket=min_bucket, bq=bq)
    ans, lo, hi = _exec_quantile(plan, _pad_bucket(q, size, 0.5),
                                 backend=backend, interpret=interpret,
                                 bq=bq)
    return QuantileResult(ans[:n], lo[:n], hi[:n])


def execute_extremum(plan: IndexPlan, lq, uq, *, backend: str = "xla",
                     eps_rel: Optional[float] = None,
                     interpret: Optional[bool] = None,
                     bq: int = DEFAULT_BQ, min_bucket: int = 64) -> QueryResult:
    """1-D MAX/MIN over [lq, uq] (MIN plans run on negated measures)."""
    assert plan.agg in ("max", "min"), plan.agg
    _check_backend(backend)
    if eps_rel is not None:
        _require_exact(plan.ref_st is not None)
    if backend in ("pallas", "pallas_scan", "ref") and plan.deg > 3:
        # in-kernel closed-form extrema stop at deg 3 (the paper's
        # recommended MAX range); higher degrees take the XLA path
        backend = "xla"
    (lq, uq), n, size, bq = _prepare(lq, uq, min_bucket=min_bucket, bq=bq)
    fill = plan.domain_lo.astype(lq.dtype)
    ans, approx, refined = _exec_extremum(
        plan, _pad_bucket(lq, size, fill), _pad_bucket(uq, size, fill),
        backend=backend, eps_rel=eps_rel, interpret=interpret, bq=bq)
    return QueryResult(ans[:n], approx[:n], refined[:n])


def _execute_rect2d(plan: IndexPlan2D, lx, ux, ly, uy, *, backend, eps_rel,
                    interpret, bq, min_bucket) -> QueryResult:
    _check_backend(backend)
    if eps_rel is not None:
        _require_exact(plan.ref_xs is not None)
    (lx, ux, ly, uy), n, size, bq = _prepare(lx, ux, ly, uy,
                                             min_bucket=min_bucket, bq=bq)
    x0, _, y0, _ = plan.root
    args = (_pad_bucket(lx, size, x0), _pad_bucket(ux, size, x0),
            _pad_bucket(ly, size, y0), _pad_bucket(uy, size, y0))
    ans, approx, refined = _exec_rect2d(
        plan, *args, backend=backend, eps_rel=eps_rel, interpret=interpret,
        bq=bq)
    return QueryResult(ans[:n], approx[:n], refined[:n])


def execute_count2d(plan: IndexPlan2D, lx, ux, ly, uy, *,
                    backend: str = "xla", eps_rel: Optional[float] = None,
                    interpret: Optional[bool] = None, bq: int = DEFAULT_BQ,
                    min_bucket: int = 64) -> QueryResult:
    """2-key COUNT over (lx, ux] x (ly, uy] via 4-corner inclusion-exclusion."""
    assert plan.agg == "count2d", plan.agg
    return _execute_rect2d(plan, lx, ux, ly, uy, backend=backend,
                           eps_rel=eps_rel, interpret=interpret, bq=bq,
                           min_bucket=min_bucket)


def execute_sum2d(plan: IndexPlan2D, lx, ux, ly, uy, *,
                  backend: str = "xla", eps_rel: Optional[float] = None,
                  interpret: Optional[bool] = None, bq: int = DEFAULT_BQ,
                  min_bucket: int = 64) -> QueryResult:
    """2-key SUM over (lx, ux] x (ly, uy]: the same 4-corner path over a
    CF_sum-fitted plan, |A - R| <= 4*delta (DESIGN.md §12)."""
    assert plan.agg == "sum2d", plan.agg
    return _execute_rect2d(plan, lx, ux, ly, uy, backend=backend,
                           eps_rel=eps_rel, interpret=interpret, bq=bq,
                           min_bucket=min_bucket)


def execute_extremum2d(plan: IndexPlan2D, u, v, *, backend: str = "xla",
                       eps_rel: Optional[float] = None,
                       interpret: Optional[bool] = None, bq: int = DEFAULT_BQ,
                       min_bucket: int = 64) -> QueryResult:
    """Dominance MAX/MIN at (u, v): the extremal measure over
    {x <= u, y <= v}, |A - R| <= delta (min2d plans run on negated
    measures end to end)."""
    assert plan.agg in ("max2d", "min2d"), plan.agg
    _check_backend(backend)
    if eps_rel is not None:
        _require_exact(plan.ref_wpmax is not None)
    (u, v), n, size, bq = _prepare(u, v, min_bucket=min_bucket, bq=bq)
    x0, _, y0, _ = plan.root
    ans, approx, refined = _exec_extremum2d(
        plan, _pad_bucket(u, size, x0), _pad_bucket(v, size, y0),
        backend=backend, eps_rel=eps_rel, interpret=interpret, bq=bq)
    return QueryResult(ans[:n], approx[:n], refined[:n])


def execute(plan: Union[IndexPlan, IndexPlan2D], ranges, *,
            backend: str = "xla", eps_rel: Optional[float] = None,
            interpret: Optional[bool] = None, bq: int = DEFAULT_BQ,
            min_bucket: int = 64) -> QueryResult:
    """Dispatch on the plan: (lq, uq) for 1-D, (lx, ux, ly, uy) for 2-D
    rectangles, (u, v) for 2-D dominance MAX/MIN."""
    kw = dict(backend=backend, eps_rel=eps_rel, interpret=interpret, bq=bq,
              min_bucket=min_bucket)
    if hasattr(plan, "levels"):   # LsmPlan / LsmPlan2D level ladder
        from .lsm import execute_lsm
        return execute_lsm(plan, None, ranges, **kw)
    if isinstance(plan, IndexPlan2D):
        if plan.agg == "count2d":
            return execute_count2d(plan, *ranges, **kw)
        if plan.agg == "sum2d":
            return execute_sum2d(plan, *ranges, **kw)
        return execute_extremum2d(plan, *ranges, **kw)
    if plan.agg in ("sum", "count"):
        return execute_sum(plan, *ranges, **kw)
    return execute_extremum(plan, *ranges, **kw)


# ---------------------------------------------------------------------------
# the engine — a thin configuration shim over the dispatch path (kept for
# downstream callers; new code should go through repro.api.PolyFit)
# ---------------------------------------------------------------------------

class Engine:
    """Backend-dispatched range-aggregate query engine.

    One instance serves any number of plans; jit compiles (and caches) one
    executable per (aggregate, backend, batch-bucket, plan-layout).
    ``interpret`` controls Pallas interpret mode (default: from the
    platform, ``kernels.poly_eval.resolve_interpret``).

    Every method is a shim binding this instance's (backend, interpret, bq,
    min_bucket) onto the module-level ``execute_*`` dispatch functions — the
    same path the ``repro.api`` session facade uses, so old and new callers
    hit bit-identical executors.
    """

    def __init__(self, backend: str = "xla", interpret: Optional[bool] = None,
                 bq: int = DEFAULT_BQ, min_bucket: int = 64):
        _check_backend(backend)
        check_pow2("bq", bq)
        check_pow2("min_bucket", min_bucket)
        self.backend = backend
        self.interpret = resolve_interpret(interpret)
        self.bq = bq
        self.min_bucket = min_bucket

    def _kw(self, eps_rel):
        return dict(backend=self.backend, eps_rel=eps_rel,
                    interpret=self.interpret, bq=self.bq,
                    min_bucket=self.min_bucket)

    def sum(self, plan: IndexPlan, lq, uq,
            eps_rel: Optional[float] = None) -> QueryResult:
        return execute_sum(plan, lq, uq, **self._kw(eps_rel))

    count = sum   # COUNT is SUM over unit measures

    def quantile(self, plan: IndexPlan, q) -> QuantileResult:
        kw = self._kw(None)
        kw.pop("eps_rel")   # quantile certificates are Q_abs-only
        return execute_quantile(plan, q, **kw)

    def extremum(self, plan: IndexPlan, lq, uq,
                 eps_rel: Optional[float] = None) -> QueryResult:
        return execute_extremum(plan, lq, uq, **self._kw(eps_rel))

    def count2d(self, plan: IndexPlan2D, lx, ux, ly, uy,
                eps_rel: Optional[float] = None) -> QueryResult:
        return execute_count2d(plan, lx, ux, ly, uy, **self._kw(eps_rel))

    def sum2d(self, plan: IndexPlan2D, lx, ux, ly, uy,
              eps_rel: Optional[float] = None) -> QueryResult:
        return execute_sum2d(plan, lx, ux, ly, uy, **self._kw(eps_rel))

    def extremum2d(self, plan: IndexPlan2D, u, v,
                   eps_rel: Optional[float] = None) -> QueryResult:
        return execute_extremum2d(plan, u, v, **self._kw(eps_rel))

    def query(self, plan: Union[IndexPlan, IndexPlan2D], *ranges,
              eps_rel: Optional[float] = None) -> QueryResult:
        return execute(plan, ranges, **self._kw(eps_rel))
