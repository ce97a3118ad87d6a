"""Dynamic PolyFit: delta-buffered inserts/deletes with selective refit
(DESIGN.md §9).

A static ``IndexPlan`` freezes the fitted key array; absorbing one new point
used to mean rebuilding the whole plan.  ``DynamicEngine`` makes plans
updatable while keeping every certified bound:

* **Delta buffers** — fixed-capacity, device-resident, sentinel-padded
  arrays (a sorted insert log and delete tombstones), registered as pytree
  leaves so the fused query paths stay jittable with one compilation per
  (aggregate, backend, batch-bucket, capacity).
* **Fused exact correction** — every query executes the static plan's
  backend-dispatched approximation *and* an exact delta scan
  (``kernels/delta_scan.py``; one-hot membership matmul, like the segment
  kernels) in a single jitted executor.  The only approximation error left
  is the static plan's own E(I) <= delta, so Lemmas 5.1-5.4/6.3-6.4 hold
  verbatim over the updated dataset (the buffered contribution is exact).
* **Selective refit** — when the buffer fills, or a segment's accumulated
  |measure| drift exceeds its error headroom (delta - E(I)), a merge pass
  re-fits *only* the segments whose spans contain changed keys
  (``core.segmentation.greedy_segmentation`` on the affected windows);
  clean SUM/COUNT segments absorb the CF shift of upstream edits as a
  constant-coefficient bump (adding c to F adds c to the fitted P exactly,
  leaving E(I) unchanged), and clean MAX/MIN segments are untouched.  The
  merged index is assembled (``core.index.assemble_index_1d``) and the new
  plan is installed atomically — plans are immutable pytrees, so queries
  already in flight keep the old plan and are never blocked; with
  ``background=True`` the merge itself runs on a worker thread and only the
  final pointer swap takes the lock.

MAX/MIN deletes cannot be folded into a monotone max correction (the
deleted point may *be* the maximum), so they shadow their victim instead:
the buffer carries the victim keys plus a victim-masked exact sparse
table (``vic_keys``/``live_st``), queries whose range covers a victim
refine to the exact live answer, and the actual removal waits for the
next capacity-triggered merge — no delete ever forces an eager refit
(``engine.lsm`` applies the same scheme per level).  SUM/COUNT deletes
ride the tombstone buffer like inserts.

``DynamicEngine2D`` applies the same buffering + fused-correction scheme
to 2-key COUNT/SUM/dominance-MAX/MIN plans; its merge runs
``core.index2d.selective_refit_2d`` over the touched leaves only.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.exact import build_sparse_table, sparse_table_range_max
from ..core.fitting import PolyModel, fit_minimax_lp
from ..core.index import PolyFitIndex1D, _continuum_post, assemble_index_1d
from ..core.index2d import (MergeSortTree, PolyFitIndex2D, mst_dommax,
                            selective_refit_2d)
from ..core.queries import QueryResult
from ..core.segmentation import FastAcceptFitter, greedy_segmentation
from ..kernels import ref as _ref
from ..kernels.delta_scan import (delta_count2d_gather_pallas,
                                  delta_count2d_pallas,
                                  delta_dommax2d_gather_pallas,
                                  delta_dommax2d_pallas,
                                  delta_max_gather_pallas, delta_max_pallas,
                                  delta_sum2d_gather_pallas,
                                  delta_sum2d_pallas,
                                  delta_sum_gather_pallas, delta_sum_pallas)
from ..core.poly import horner
from ..core.quantile import boundary_array, invert_cf, rank_slack
from ..kernels.poly_eval import DEFAULT_BQ, resolve_interpret
from .engine import (QuantileResult, _bucket_size, _pad_bucket, check_pow2,
                     raw_count2d, raw_eval2d, raw_extremum, raw_sum,
                     truth_count2d, truth_dommax2d, truth_extremum,
                     truth_sum, truth_sum2d)
from .plan import (IndexPlan, IndexPlan2D, big_sentinel, build_plan,
                   build_plan_2d, pad_to_multiple)

__all__ = ["DeltaBuffer", "DeltaBuffer2D", "DynamicEngine",
           "DynamicEngine2D", "fused_executor", "fused_quantile_executor"]


# ---------------------------------------------------------------------------
# device-resident delta buffers (pytree-registered, fixed capacity)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeltaBuffer:
    """Sorted insert log + delete tombstones for a 1-D plan.

    Empty slots hold a huge-but-finite sentinel key (``big_sentinel``) so
    they fail every membership test inside the delta-scan kernels; the
    kernels never need the fill level.  Values live in *internal* space
    (negated for MIN plans, mirroring the static index).

    Appends also maintain the locate->gather correction structures
    (DESIGN.md §10): exclusive prefix sums over both logs (a buffered
    SUM/COUNT correction is then two binary searches + a subtraction) and,
    for MAX/MIN plans, a sparse table over the insert log (the located span
    answers in O(1)).  Sentinel slots carry value 0, so the prefix sums are
    flat across the tail and the structures are fill-level oblivious too.

    Extremal deletes shadow their victim instead of merging eagerly:
    ``vic_keys`` holds the (sentinel-padded, sorted) keys of deleted base
    rows and ``live_st`` a victim-masked exact sparse table over the base
    measures.  A query whose range covers a victim cannot trust the fitted
    approximation (the victim may *be* the maximum) and refines against
    ``live_st`` instead — exact, and no merge on the write path.  Both are
    ``None`` until the first extremal delete, keeping the no-victim trace
    bit-identical to the victim-free executor.
    """

    ins_keys: jnp.ndarray   # (cap,) sorted, sentinel-padded
    ins_vals: jnp.ndarray   # (cap,) measures; 0 on padding
    ins_cf: jnp.ndarray     # (cap+1,) exclusive prefix sum of ins_vals
    del_keys: jnp.ndarray   # (cap,) sorted, sentinel-padded
    del_vals: jnp.ndarray   # (cap,) tombstoned measures; 0 on padding
    del_cf: jnp.ndarray     # (cap+1,) exclusive prefix sum of del_vals
    ins_st: Optional[jnp.ndarray]   # (L, cap) sparse table (max/min only)
    cap: int
    vic_keys: Optional[jnp.ndarray] = None   # (vcap,) deleted base keys
    live_st: Optional[jnp.ndarray] = None    # (L, n) victim-masked exact ST

    @staticmethod
    def empty(cap: int, dtype=jnp.float64,
              with_st: bool = False) -> "DeltaBuffer":
        big = big_sentinel(dtype)
        s = jnp.full((cap,), big, dtype)
        z = jnp.zeros((cap,), dtype)
        cf = jnp.zeros((cap + 1,), dtype)
        st = (jnp.full((max(1, cap.bit_length()), cap), -jnp.inf, dtype)
              if with_st else None)
        return DeltaBuffer(s, z, cf, s, z, cf, st, cap)


jax.tree_util.register_dataclass(
    DeltaBuffer,
    data_fields=["ins_keys", "ins_vals", "ins_cf", "del_keys", "del_vals",
                 "del_cf", "ins_st", "vic_keys", "live_st"],
    meta_fields=["cap"],
)


@dataclasses.dataclass(frozen=True)
class DeltaBuffer2D:
    """Insert/delete point logs for a 2-key plan (x-sorted).

    ``*_ylv`` are merge-sort-tree level arrays (level l = y values sorted
    within blocks of 2^l of the x-order), rebuilt on append, so the
    locate->gather correction answers each corner's dominance count in
    O(log^2 cap) instead of scanning the log.

    Measure-carrying plans (sum2d/max2d/min2d) additionally log each
    point's measure (``*_w``, internal space — negated for min2d, 0 on
    sentinel padding) and, for the locate->gather backend, the weighted
    merge-sort-tree companions: per-block inclusive prefix sums
    (``*_wcum``) for the SUM correction and prefix maxima (``ins_wpmax``)
    for the dominance-MAX correction.  Extremal deletes never populate the
    delete log: they shadow base victims via ``vic_x``/``vic_y`` and the
    victim-masked exact tree ``live_wpmax`` (see ``DeltaBuffer``), so the
    delete log needs no max structure.
    """

    ins_x: jnp.ndarray
    ins_y: jnp.ndarray
    ins_ylv: jnp.ndarray    # (L, cap) per-level block-sorted y arrays
    del_x: jnp.ndarray
    del_y: jnp.ndarray
    del_ylv: jnp.ndarray    # (L, cap)
    cap: int
    # -- measure-carrying extension (sum2d/max2d/min2d plans) ------------
    ins_w: Optional[jnp.ndarray] = None      # (cap,) measures; 0 on padding
    del_w: Optional[jnp.ndarray] = None
    ins_wcum: Optional[jnp.ndarray] = None   # (L, cap) block prefix sums
    del_wcum: Optional[jnp.ndarray] = None
    ins_wpmax: Optional[jnp.ndarray] = None  # (L, cap) block prefix maxima
    vic_x: Optional[jnp.ndarray] = None      # (vcap,) deleted base points
    vic_y: Optional[jnp.ndarray] = None
    live_wpmax: Optional[jnp.ndarray] = None  # (L, n) victim-masked tree

    @staticmethod
    def empty(cap: int, dtype=jnp.float64,
              weighted: bool = False) -> "DeltaBuffer2D":
        big = big_sentinel(dtype)
        s = jnp.full((cap,), big, dtype)
        lv = jnp.full((max(1, cap.bit_length()), cap), big, dtype)
        if not weighted:
            return DeltaBuffer2D(s, s, lv, s, s, lv, cap)
        z = jnp.zeros((cap,), dtype)
        zlv = jnp.zeros((max(1, cap.bit_length()), cap), dtype)
        return DeltaBuffer2D(s, s, lv, s, s, lv, cap,
                             ins_w=z, del_w=z, ins_wcum=zlv, del_wcum=zlv,
                             ins_wpmax=zlv)


jax.tree_util.register_dataclass(
    DeltaBuffer2D,
    data_fields=["ins_x", "ins_y", "ins_ylv", "del_x", "del_y", "del_ylv",
                 "ins_w", "del_w", "ins_wcum", "del_wcum", "ins_wpmax",
                 "vic_x", "vic_y", "live_wpmax"],
    meta_fields=["cap"],
)


def _merge_sorted(cap: int, keys, vals, new_k, new_v):
    """Merge a (sentinel-padded) batch into the sorted log, keeping shape.

    Valid entries sort before the sentinels, so slicing back to ``cap``
    drops padding only (caller guarantees fill + batch <= cap).
    """
    k = jnp.concatenate([keys, new_k])
    v = jnp.concatenate([vals, new_v])
    order = jnp.argsort(k)   # stable: existing entries first on ties
    return k[order][:cap], v[order][:cap]


def _scan_sum(x, axis: int = 0):
    """Inclusive prefix sum as a log-depth scan.  XLA lowers ``cumsum`` on
    TPU through ``reduce_window``, which in f64 takes minutes to compile
    for a v5e (about 160 s for 1024 values); the scan compiles in under a
    second, and every platform adds in the same order."""
    return jax.lax.associative_scan(jnp.add, x, axis=axis)


def _prefix_sum_jnp(vals):
    """Exclusive prefix-sum array ((cap+1,)) over the sorted log's values."""
    return jnp.concatenate([jnp.zeros((1,), vals.dtype), _scan_sum(vals)])


def _sparse_table_jnp(vals, *, cap: int):
    """(L, cap) sparse table over the sorted log (``build_sparse_table``
    semantics: st[j, i] = max(vals[i : i+2^j]), -inf past the end)."""
    rows = [vals]
    for j in range(1, max(1, cap.bit_length())):
        half = 1 << (j - 1)
        prev = rows[-1]
        shifted = jnp.concatenate(
            [prev[half:], jnp.full((half,), -jnp.inf, prev.dtype)])
        rows.append(jnp.maximum(prev, shifted))
    return jnp.stack(rows)


def _mst_levels_jnp(ys, *, cap: int):
    """(L, cap) merge-sort-tree levels of the x-sorted log's y values
    (level l = per-block sort with block size 2^l; level 0 = x order)."""
    rows = [ys]
    for l in range(1, max(1, cap.bit_length())):
        b = 1 << l
        rows.append(jnp.sort(ys.reshape(cap // b, b), axis=1).reshape(-1))
    return jnp.stack(rows)


def _mst_levels_w_jnp(ys, ws, *, cap: int):
    """Weighted merge-sort-tree levels of the x-sorted log: per-level
    block-sorted y arrays plus per-block inclusive weight prefix sums and
    prefix maxima (the structures ``mst_weighted_prefix`` consumes).
    Returns (ylv, wcum, wpmax), each (L, cap)."""
    ylv, wcum, wpmax = [ys], [ws], [ws]
    y, w = ys, ws
    for l in range(1, max(1, cap.bit_length())):
        b = 1 << l
        y2 = y.reshape(cap // b, b)
        perm = jnp.argsort(y2, axis=1)   # jax sorts are stable
        y2 = jnp.take_along_axis(y2, perm, axis=1)
        w2 = jnp.take_along_axis(w.reshape(cap // b, b), perm, axis=1)
        y, w = y2.reshape(-1), w2.reshape(-1)
        ylv.append(y)
        wcum.append(_scan_sum(w2, axis=1).reshape(-1))
        wpmax.append(jax.lax.associative_scan(jnp.maximum, w2,
                                              axis=1).reshape(-1))
    return jnp.stack(ylv), jnp.stack(wcum), jnp.stack(wpmax)


# The fused append executors: ONE jitted device dispatch per insert/delete
# chunk, rebuilding the sorted log and every derived correction structure
# (prefix sums, sparse table, merge-sort-tree levels) inside a single
# compilation.  The previous shape — one jitted call per structure, per
# batch — dispatched (and, on first use per backend, *compiled*) each helper
# separately; the measured ~480x `updates2d.insert.pallas` gap in
# BENCH_updates.json was exactly those un-warmed per-structure compilations
# landing on the timed path.  One fused executable per (cap, structure
# flags) also means chunked inserts amortize: appending a 1024-record chunk
# costs one dispatch, not eight 128-record ones.

@partial(jax.jit, static_argnames=("cap", "with_st"))
def _append_1d(keys, vals, new_k, new_v, *, cap: int, with_st: bool):
    """Fused 1-D append: merged sorted log + exclusive prefix sums and,
    for the locate->gather MAX/MIN correction, the insert-log sparse
    table.  Returns (keys, vals, cf, st-or-None)."""
    k, v = _merge_sorted(cap, keys, vals, new_k, new_v)
    cf = _prefix_sum_jnp(v)
    st = _sparse_table_jnp(v, cap=cap) if with_st else None
    return k, v, cf, st


@partial(jax.jit, static_argnames=("cap", "levels", "weighted"))
def _append_2d(bx, by, bw, nx, ny, nw, *, cap: int, levels: bool,
               weighted: bool):
    """Fused 2-D append: x-sorted point log plus (when the locate->gather
    correction reads them) the merge-sort-tree level arrays — weighted
    variants also rebuild the per-block prefix sums/maxima.  Returns
    (x, y, w, ylv, wcum, wpmax) with None for structures not requested
    (``bw``/``nw`` are ignored when ``weighted`` is False)."""
    x = jnp.concatenate([bx, nx])
    y = jnp.concatenate([by, ny])
    order = jnp.argsort(x)   # stable: existing entries first on ties
    x, y = x[order][:cap], y[order][:cap]
    w = ylv = wcum = wpmax = None
    if weighted:
        w = jnp.concatenate([bw, nw])[order][:cap]
        if levels:
            ylv, wcum, wpmax = _mst_levels_w_jnp(y, w, cap=cap)
    elif levels:
        ylv = _mst_levels_jnp(y, cap=cap)
    return x, y, w, ylv, wcum, wpmax


def _pad_batch(arr: np.ndarray, fill, dtype) -> jnp.ndarray:
    """Pad a host batch to the next power of two (bounds compilations)."""
    m = len(arr)
    size = max(1, 1 << (m - 1).bit_length()) if m else 1
    out = np.full((size,), fill, np.float64)
    out[:m] = arr
    return jnp.asarray(out, dtype)


# ---------------------------------------------------------------------------
# fused delta corrections (traced inside the dynamic executors)
# ---------------------------------------------------------------------------

def _delta_sum(lq, uq, keys, vals, cf, *, backend, interpret, bq):
    if backend == "pallas":
        # locate->gather: two binary searches into the append-maintained
        # prefix-sum array (O(log D) instead of the O(D) one-hot sweep)
        return delta_sum_gather_pallas(lq, uq, keys, cf, bq=bq,
                                       interpret=interpret)
    if backend == "pallas_scan":
        return delta_sum_pallas(lq, uq, keys, vals, bq=bq, interpret=interpret)
    if backend == "ref":
        return _ref.delta_sum_ref(lq, uq, keys, vals)
    # xla: the log is sorted and cf precomputed -> two searchsorted lookups
    return (cf[jnp.searchsorted(keys, uq, side="right")]
            - cf[jnp.searchsorted(keys, lq, side="right")])


def _delta_max(lq, uq, keys, vals, st, *, backend, interpret, bq):
    if backend == "pallas":
        # locate the covered span of the sorted log, O(1) sparse-table RMQ
        return delta_max_gather_pallas(lq, uq, keys, st, bq=bq,
                                       interpret=interpret)
    if backend == "pallas_scan":
        return delta_max_pallas(lq, uq, keys, vals, bq=bq, interpret=interpret)
    # xla + ref: dense masked max over the (small) buffer
    return _ref.delta_max_ref(lq, uq, keys, vals)


def _delta_count2d(lx, ux, ly, uy, kx, ky, ylv, *, backend, interpret, bq,
                   dtype):
    if backend == "pallas":
        # locate->gather: merge-sort-tree dominance counts, O(log^2 D)
        return delta_count2d_gather_pallas(lx, ux, ly, uy, kx, ylv, bq=bq,
                                           interpret=interpret, dtype=dtype)
    if backend == "pallas_scan":
        return delta_count2d_pallas(lx, ux, ly, uy, kx, ky, bq=bq,
                                    interpret=interpret, dtype=dtype)
    return _ref.delta_count2d_ref(lx, ux, ly, uy, kx, ky, dtype=dtype)


def _delta_sum2d(lx, ux, ly, uy, kx, ky, wv, ylv, wcum, *, backend,
                 interpret, bq):
    if backend == "pallas":
        # locate->gather: weighted merge-sort-tree sums, O(log^2 D)
        return delta_sum2d_gather_pallas(lx, ux, ly, uy, kx, ylv, wcum,
                                         bq=bq, interpret=interpret)
    if backend == "pallas_scan":
        return delta_sum2d_pallas(lx, ux, ly, uy, kx, ky, wv, bq=bq,
                                  interpret=interpret)
    return _ref.delta_sum2d_ref(lx, ux, ly, uy, kx, ky, wv)


def _delta_dommax2d(u, v, kx, ky, wv, ylv, wpmax, *, backend, interpret, bq):
    if backend == "pallas":
        # locate->gather: weighted merge-sort-tree maxima, O(log^2 D)
        return delta_dommax2d_gather_pallas(u, v, kx, ylv, wpmax, bq=bq,
                                            interpret=interpret)
    if backend == "pallas_scan":
        return delta_dommax2d_pallas(u, v, kx, ky, wv, bq=bq,
                                     interpret=interpret)
    return _ref.delta_dommax2d_ref(u, v, kx, ky, wv)


# ---------------------------------------------------------------------------
# fused dynamic executors: static approximation + exact delta correction +
# Q_rel acceptance + vectorized refinement, one jitted path per signature
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_dyn_sum(plan: IndexPlan, buf: DeltaBuffer, lq, uq, *, backend: str,
                  eps_rel: Optional[float],
                  interpret: Optional[bool], bq: int):
    dt = plan.dtype
    lqr, uqr = lq.astype(dt), uq.astype(dt)
    lqc = jnp.maximum(lqr, plan.domain_lo)
    uqc = jnp.maximum(uqr, plan.domain_lo)
    with jax.named_scope("approx"):
        static = raw_sum(plan, lqc, uqc, backend=backend,
                         interpret=interpret, bq=bq)
    # exact correction over (lq, uq] — unclamped: buffered keys may lie
    # outside the static domain
    corr = (_delta_sum(lqr, uqr, buf.ins_keys, buf.ins_vals, buf.ins_cf,
                       backend=backend, interpret=interpret, bq=bq)
            - _delta_sum(lqr, uqr, buf.del_keys, buf.del_vals, buf.del_cf,
                         backend=backend, interpret=interpret, bq=bq))
    approx = static + corr
    if eps_rel is None:
        return approx, approx, jnp.zeros(approx.shape, bool)
    # Lemma 5.2 holds over the updated dataset: |approx - truth| <= 2*delta
    # because the delta contribution is exact
    two_d = 2.0 * plan.delta
    ok = ((approx - two_d > 0) &
          (two_d / jnp.maximum(approx - two_d, 1e-300) <= eps_rel))
    with jax.named_scope("refine"):
        truth = truth_sum(plan, lqr, uqr) + corr
    return jnp.where(ok, approx, truth), approx, ~ok


@partial(jax.jit, static_argnames=("backend", "interpret", "bq"))
def _exec_dyn_quantile(plan: IndexPlan, buf: DeltaBuffer, q, *, backend: str,
                       interpret: Optional[bool], bq: int):
    """Certified quantile over the *updated* CF G = F + (ins - del).

    G is the CF of the live multiset (deletes remove existing rows), hence
    monotone; only F is fitted.  The loop inverts F against the
    delta-corrected rank target and re-certifies with the exact buffer
    correction evaluated at the candidate key: at convergence the
    key-certified facts about F plus the exact B(x) give
    G(x_hi) >= rank + slack and G(x_lo) <= rank - slack (DESIGN.md §16).
    Inversion is O(Q log H) scalar work with no kernel variant, so
    ``backend`` is ignored and every backend shares this path
    bit-identically.
    """
    del backend, interpret, bq
    dt = plan.dtype
    qc = jnp.clip(q.astype(dt), 0.0, 1.0)
    err = (plan.seg_err if plan.seg_err is not None
           else jnp.full_like(plan.seg_lo, plan.delta))
    Bnd = boundary_array(plan.coeffs)
    kw = dict(B=Bnd, seg_lo=plan.seg_lo, seg_hi=plan.seg_hi,
              coeffs=plan.coeffs, h=plan.h)
    if plan.ref_keys is not None:
        keys = pad_to_multiple(plan.ref_keys, 128, big_sentinel(dt))
        nk = plan.n
    else:
        keys, nk = None, 0

    # total live mass and rank slack over the updated multiset
    dM = buf.ins_cf[-1] - buf.del_cf[-1]
    if plan.agg == "count":
        M = jnp.asarray(float(plan.n), dt) + dM
        slack = rank_slack("count", M)
    else:
        if plan.ref_cf is not None:
            M0, extra = plan.ref_cf[-1], 0.0
        else:
            M0 = horner(plan.coeffs[plan.h - 1], jnp.asarray(1.0, dt))
            extra = plan.delta
        M = M0 + dM
        slack = rank_slack("sum", M) + extra
    r = qc * M
    tiny = 1e-9 * (jnp.abs(r) + 1.0)

    def corr(x):
        # exact buffered mass at or below x (exclusive prefix sums; the
        # sentinel-padded tails contribute zero)
        return (buf.ins_cf[jnp.searchsorted(buf.ins_keys, x, side="right")]
                - buf.del_cf[jnp.searchsorted(buf.del_keys, x, side="right")])

    live = buf.ins_keys < big_sentinel(dt) / 2
    dom_hi = plan.seg_hi[plan.h - 1]
    dom_lo = plan.seg_lo[0]
    # unconditional fallbacks: >=/<= every live key of the updated set
    fb_top = jnp.maximum(dom_hi,
                         jnp.max(jnp.where(live, buf.ins_keys, -jnp.inf)))
    fb_lo = jnp.minimum(dom_lo,
                        jnp.min(jnp.where(live, buf.ins_keys, jnp.inf)))

    # raw fitted estimate: fixed-point on the delta-corrected rank
    zeros = jnp.zeros_like(err)
    xm, okm = invert_cf(r, "hi", seg_err=zeros, delta=0.0, slack=0.0,
                        raw=True, **kw)
    xm = jnp.where(okm, xm, dom_hi)
    for _ in range(2):
        xm2, okm = invert_cf(r - corr(xm), "hi", seg_err=zeros, delta=0.0,
                             slack=0.0, raw=True, **kw)
        xm = jnp.where(okm, xm2, dom_hi)

    # upper: find x_hi with F(x_hi) >= tF and tF + B(x_hi) >= r + slack
    r_hi = r + slack
    tF = r_hi - corr(xm)
    x_hi, ok_hi = xm, jnp.zeros(r.shape, bool)
    for _ in range(4):
        x_hi, ok_v = invert_cf(tF, "hi", seg_err=err,
                               delta=float(plan.delta), slack=0.0,
                               ref_keys=keys, n=nk, **kw)
        need = r_hi - corr(x_hi)
        ok_hi = (need <= tF + tiny) & ok_v
        tF = jnp.maximum(tF, need)
    x_hi = jnp.where(ok_hi, x_hi, fb_top)

    # lower: every base key <= x_lo has F <= tL (the invert_cf 'lo'
    # contract, flagged by ok_v), so F(x_lo) <= max(tL, 0) and
    # G(x_lo) <= max(tL, 0) + B(x_lo) <= r - slack at convergence; G
    # monotone => x_lo precedes every rank-r crossing
    r_lo = r - slack
    tL = r_lo - corr(xm)
    x_lo, ok_lo = xm, jnp.zeros(r.shape, bool)
    for _ in range(4):
        x_lo, ok_v = invert_cf(tL, "lo", seg_err=err,
                               delta=float(plan.delta), slack=0.0, **kw)
        need = r_lo - corr(x_lo)
        ok_lo = (need >= jnp.maximum(tL, 0.0) - tiny) & ok_v
        tL = jnp.minimum(tL, need)
    x_lo = jnp.where(ok_lo, x_lo, fb_lo)

    ans = jnp.clip(xm, x_lo, x_hi)
    return ans, x_lo, x_hi


@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_dyn_extremum(plan: IndexPlan, buf: DeltaBuffer, lq, uq, *,
                       backend: str, eps_rel: Optional[float],
                       interpret: Optional[bool], bq: int):
    """MAX space throughout; the delete log is empty by construction
    (extremal deletes shadow a victim — ``buf.vic_keys``/``buf.live_st`` —
    instead of populating the device delete log; see DeltaBuffer)."""
    dt = plan.dtype
    lqr, uqr = lq.astype(dt), uq.astype(dt)
    lqc = jnp.maximum(lqr, plan.domain_lo)
    uqc = jnp.maximum(uqr, plan.domain_lo)
    with jax.named_scope("approx"):
        static = raw_extremum(plan, lqc, uqc, backend=backend,
                              interpret=interpret, bq=bq)
    ins = _delta_max(lqr, uqr, buf.ins_keys, buf.ins_vals, buf.ins_st,
                     backend=backend, interpret=interpret, bq=bq)
    approx = jnp.maximum(static, ins)
    neg = plan.agg == "min"
    if buf.vic_keys is not None:
        # victim-shadowed path: a range covering a deleted base row cannot
        # trust the fitted approximation (the victim may be the maximum) —
        # refine against the victim-masked exact sparse table instead
        with jax.named_scope("refine"):
            i0 = jnp.searchsorted(plan.ref_keys, lqr, side="left")
            i1 = jnp.searchsorted(plan.ref_keys, uqr, side="right")
            base_exact = sparse_table_range_max(buf.live_st, i0, i1)
        exact = jnp.maximum(base_exact, ins)
        vk = buf.vic_keys
        threat = jnp.any((lqr[:, None] <= vk[None, :]) &
                         (vk[None, :] <= uqr[:, None]), axis=1)
        if eps_rel is None:
            ans = jnp.where(threat, exact, approx)
            if neg:
                ans = -ans
            return ans, ans, threat
        ok = (~threat) & (approx >= plan.delta * (1.0 + 1.0 / eps_rel))
        ans = jnp.where(ok, approx, exact)
        if neg:
            ans, approx = -ans, -approx
        return ans, approx, ~ok
    if eps_rel is None:
        out = -approx if neg else approx
        return out, out, jnp.zeros(out.shape, bool)
    # Lemma 5.4: max(static +- delta, exact) stays within delta of the truth
    ok = approx >= plan.delta * (1.0 + 1.0 / eps_rel)
    with jax.named_scope("refine"):
        truth = jnp.maximum(truth_extremum(plan, lqr, uqr), ins)
    ans = jnp.where(ok, approx, truth)
    if neg:
        ans, approx = -ans, -approx
    return ans, approx, ~ok


@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_dyn_count2d(plan: IndexPlan2D, buf: DeltaBuffer2D, lx, ux, ly, uy,
                      *, backend: str, eps_rel: Optional[float],
                      interpret: Optional[bool], bq: int):
    dt = plan.dtype
    x0, x1, y0, y1 = plan.root
    lxr, uxr, lyr, uyr = (q.astype(dt) for q in (lx, ux, ly, uy))
    lxc, uxc = (jnp.clip(q, x0, x1) for q in (lxr, uxr))
    lyc, uyc = (jnp.clip(q, y0, y1) for q in (lyr, uyr))
    with jax.named_scope("approx"):
        static = raw_count2d(plan, lxc, uxc, lyc, uyc, backend=backend,
                             interpret=interpret, bq=bq)
    corr = (_delta_count2d(lxr, uxr, lyr, uyr, buf.ins_x, buf.ins_y,
                           buf.ins_ylv, backend=backend, interpret=interpret,
                           bq=bq, dtype=dt)
            - _delta_count2d(lxr, uxr, lyr, uyr, buf.del_x, buf.del_y,
                             buf.del_ylv, backend=backend,
                             interpret=interpret, bq=bq, dtype=dt))
    approx = static + corr
    if eps_rel is None:
        return approx, approx, jnp.zeros(approx.shape, bool)
    ok = approx >= 4.0 * plan.delta * (1.0 + 1.0 / eps_rel)   # Lemma 6.4
    with jax.named_scope("refine"):
        truth = truth_count2d(plan, lxr, uxr, lyr, uyr) + corr
    return jnp.where(ok, approx, truth), approx, ~ok


@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_dyn_sum2d(plan: IndexPlan2D, buf: DeltaBuffer2D, lx, ux, ly, uy,
                    *, backend: str, eps_rel: Optional[float],
                    interpret: Optional[bool], bq: int):
    dt = plan.dtype
    x0, x1, y0, y1 = plan.root
    lxr, uxr, lyr, uyr = (q.astype(dt) for q in (lx, ux, ly, uy))
    lxc, uxc = (jnp.clip(q, x0, x1) for q in (lxr, uxr))
    lyc, uyc = (jnp.clip(q, y0, y1) for q in (lyr, uyr))
    with jax.named_scope("approx"):
        static = raw_count2d(plan, lxc, uxc, lyc, uyc, backend=backend,
                             interpret=interpret, bq=bq)
    # exact weighted correction — unclamped: buffered points may lie
    # outside the static root rectangle
    corr = (_delta_sum2d(lxr, uxr, lyr, uyr, buf.ins_x, buf.ins_y,
                         buf.ins_w, buf.ins_ylv, buf.ins_wcum,
                         backend=backend, interpret=interpret, bq=bq)
            - _delta_sum2d(lxr, uxr, lyr, uyr, buf.del_x, buf.del_y,
                           buf.del_w, buf.del_ylv, buf.del_wcum,
                           backend=backend, interpret=interpret, bq=bq))
    approx = static + corr
    if eps_rel is None:
        return approx, approx, jnp.zeros(approx.shape, bool)
    ok = approx >= 4.0 * plan.delta * (1.0 + 1.0 / eps_rel)   # Lemma 6.4
    with jax.named_scope("refine"):
        truth = truth_sum2d(plan, lxr, uxr, lyr, uyr) + corr
    return jnp.where(ok, approx, truth), approx, ~ok


@partial(jax.jit, static_argnames=("backend", "eps_rel", "interpret", "bq"))
def _exec_dyn_dommax2d(plan: IndexPlan2D, buf: DeltaBuffer2D, u, v, *,
                       backend: str, eps_rel: Optional[float],
                       interpret: Optional[bool], bq: int):
    """MAX space throughout; the delete log is empty by construction
    (extremal deletes shadow a victim — ``buf.vic_x``/``buf.vic_y``/
    ``buf.live_wpmax`` — instead of populating the device delete log)."""
    dt = plan.dtype
    x0, x1, y0, y1 = plan.root
    ur, vr = u.astype(dt), v.astype(dt)
    uc = jnp.clip(ur, x0, x1)
    vc = jnp.clip(vr, y0, y1)
    with jax.named_scope("approx"):
        static = raw_eval2d(plan, uc, vc, backend=backend,
                            interpret=interpret, bq=bq)
    ins = _delta_dommax2d(ur, vr, buf.ins_x, buf.ins_y, buf.ins_w,
                          buf.ins_ylv, buf.ins_wpmax, backend=backend,
                          interpret=interpret, bq=bq)
    approx = jnp.maximum(static, ins)
    neg = plan.agg == "min2d"
    if buf.vic_x is not None:
        # victim-shadowed path: refine dominance corners that cover a
        # deleted base point against the victim-masked merge-sort tree
        with jax.named_scope("refine"):
            base_exact = mst_dommax(plan.ref_xs, plan.ref_ys_levels,
                                    buf.live_wpmax, ur, vr)
        exact = jnp.maximum(base_exact.astype(dt), ins)
        threat = jnp.any((buf.vic_x[None, :] <= ur[:, None]) &
                         (buf.vic_y[None, :] <= vr[:, None]), axis=1)
        if eps_rel is None:
            ans = jnp.where(threat, exact, approx)
            if neg:
                ans = -ans
            return ans, ans, threat
        ok = (~threat) & (approx >= plan.delta * (1.0 + 1.0 / eps_rel))
        ans = jnp.where(ok, approx, exact)
        if neg:
            ans, approx = -ans, -approx
        return ans, approx, ~ok
    if eps_rel is None:
        out = -approx if neg else approx
        return out, out, jnp.zeros(out.shape, bool)
    ok = approx >= plan.delta * (1.0 + 1.0 / eps_rel)
    with jax.named_scope("refine"):
        truth = jnp.maximum(truth_dommax2d(plan, ur, vr), ins)
    ans = jnp.where(ok, approx, truth)
    if neg:
        ans, approx = -ans, -approx
    return ans, approx, ~ok


# ---------------------------------------------------------------------------
# serving-executor factory: the AOT-lowerable unit behind serve/engine.py
# ---------------------------------------------------------------------------

def fused_executor(agg: str, dynamic: bool, *, backend: str,
                   eps_rel: Optional[float],
                   interpret: Optional[bool], bq: int,
                   deg: int):
    """A plain callable ``fn(plan, buf, *padded_ranges)`` with every static
    argument closed over — the unit the serving engine AOT-lowers
    (``jax.jit(fn).lower(...).compile()``) and caches per (table, bucket).

    ``buf`` is the table's ``DeltaBuffer``/``DeltaBuffer2D`` for dynamic
    tables and an empty tuple for static ones (the argument slot is kept so
    one executable-cache shape serves both).  The function returns the raw
    executor triple ``(ans, approx, refined)`` over the padded bucket; the
    caller slices real rows back out.  Dispatch mirrors ``execute_*``
    exactly — including the deg>3 extremum backend downgrade — so answers
    are bit-identical to the session path.
    """
    from .engine import (_exec_extremum, _exec_extremum2d, _exec_rect2d,
                         _exec_sum)
    if agg in ("max", "min") and deg > 3 and backend in (
            "pallas", "pallas_scan", "ref"):
        backend = "xla"   # no in-kernel closed form past deg 3
    statics = dict(backend=backend, eps_rel=eps_rel, interpret=interpret,
                   bq=bq)
    if dynamic:
        ex = {"sum": _exec_dyn_sum, "count": _exec_dyn_sum,
              "max": _exec_dyn_extremum, "min": _exec_dyn_extremum,
              "count2d": _exec_dyn_count2d, "sum2d": _exec_dyn_sum2d,
              "max2d": _exec_dyn_dommax2d,
              "min2d": _exec_dyn_dommax2d}[agg]

        def fn(plan, buf, *qs):
            return ex(plan, buf, *qs, **statics)
    else:
        ex = {"sum": _exec_sum, "count": _exec_sum,
              "max": _exec_extremum, "min": _exec_extremum,
              "count2d": _exec_rect2d, "sum2d": _exec_rect2d,
              "max2d": _exec_extremum2d, "min2d": _exec_extremum2d}[agg]

        def fn(plan, buf, *qs):
            del buf
            return ex(plan, *qs, **statics)
    return fn


def fused_quantile_executor(dynamic: bool, *, backend: str,
                            interpret: Optional[bool], bq: int, deg: int):
    """The QUANTILE counterpart of ``fused_executor``: a plain callable
    ``fn(plan, buf, q)`` returning the certified (answer, lo, hi) triple
    over the padded fraction bucket.  Q_abs-only — there is no Q_rel
    refinement path (the certificate *is* the guarantee)."""
    del deg   # quantile inversion has no degree-gated backend downgrade
    from .engine import _exec_quantile
    if dynamic:
        def fn(plan, buf, q):
            return _exec_dyn_quantile(plan, buf, q, backend=backend,
                                      interpret=interpret, bq=bq)
    else:
        def fn(plan, buf, q):
            del buf
            return _exec_quantile(plan, q, backend=backend,
                                  interpret=interpret, bq=bq)
    return fn


# ---------------------------------------------------------------------------
# merge pass: apply the buffered ops, refit only the dirty segments
# ---------------------------------------------------------------------------

def _merge_1d(index: PolyFitIndex1D, keys: np.ndarray, meas: np.ndarray,
              ins_k: np.ndarray, ins_v: np.ndarray,
              del_k: np.ndarray, del_v: np.ndarray
              ) -> Tuple[PolyFitIndex1D, np.ndarray, np.ndarray]:
    """Merge buffered ops into (keys, meas) and selectively refit.

    Returns (new_index, new_keys, new_meas) with measures in internal
    space.  Only segments whose ``locate`` span contains a changed key are
    re-segmented (greedy GS on the affected windows); clean SUM/COUNT
    segments get their constant coefficient shifted by the exact upstream
    CF delta, which preserves their certified E(I).
    """
    agg, deg, delta = index.agg, index.deg, index.delta
    extremal = agg in ("max", "min")
    n_old = len(keys)

    # -- resolve tombstones against pending inserts, then the base data ----
    removed = np.zeros(n_old, bool)
    ins_removed = np.zeros(len(ins_k), bool)
    for key, val in zip(del_k, del_v):
        cand = np.where(~ins_removed & (ins_k == key) & (ins_v == val))[0]
        if len(cand):
            ins_removed[cand[0]] = True
            continue
        i0 = np.searchsorted(keys, key, side="left")
        i1 = np.searchsorted(keys, key, side="right")
        live = np.where(~removed[i0:i1] & (meas[i0:i1] == val))[0]
        if not len(live):
            live = np.where(~removed[i0:i1])[0]
        if not len(live):
            raise KeyError(f"delete of key {key!r}: no live occurrence")
        removed[i0 + live[0]] = True

    keep = ~removed
    kept_old = np.where(keep)[0]
    ik = ins_k[~ins_removed]
    iv = ins_v[~ins_removed]
    all_k = np.concatenate([keys[keep], ik])
    all_v = np.concatenate([meas[keep], iv])
    order = np.argsort(all_k, kind="stable")   # base entries first on ties
    new_k, new_m = all_k[order], all_v[order]
    if len(new_k) == 0:
        raise ValueError("merge would empty the dataset")

    # old position -> new position, for the CF shift of clean segments
    inv = np.empty(len(order), np.int64)
    inv[order] = np.arange(len(order))
    old_to_new = np.full(n_old, -1, np.int64)
    old_to_new[kept_old] = inv[: len(kept_old)]

    # -- mark dirty segments (locate() rule: searchsorted right - 1) -------
    seg_lo = np.asarray(index.seg_lo)
    seg_hi = np.asarray(index.seg_hi)
    coeffs = np.asarray(index.coeffs)
    seg_start = np.asarray(index.seg_start)
    seg_err = (np.asarray(index.seg_err) if index.seg_err is not None
               else np.full(len(seg_lo), delta))
    h = len(seg_lo)
    changed = np.concatenate([ins_k, del_k])
    dirty = np.zeros(h, bool)
    dirty[np.clip(np.searchsorted(seg_lo, changed, side="right") - 1,
                  0, h - 1)] = True
    # duplicate keys straddling a boundary can leave a "clean" segment whose
    # anchor position was removed — refit it rather than shift blindly
    for s in range(h):
        if not dirty[s] and old_to_new[seg_start[s]] < 0:
            dirty[s] = True

    old_F = np.cumsum(meas) if not extremal else meas
    new_F = np.cumsum(new_m) if not extremal else new_m
    ins_sorted = np.sort(ik)
    keep_cum = np.concatenate([[0], np.cumsum(keep)])

    def new_boundary(p: int) -> int:
        """New-array position of old boundary position p (start of seg)."""
        if p >= n_old:
            return len(new_k)
        # kept base keys before p + inserted keys sorting strictly before
        # keys[p] (stable merge puts equal inserted keys after the base run)
        return int(keep_cum[p]) + int(np.searchsorted(ins_sorted, keys[p],
                                                      side="left"))

    fitter = FastAcceptFitter(exact=fit_minimax_lp, delta=delta,
                              post=_continuum_post if extremal else None)
    segs: List[PolyModel] = []
    i = 0
    while i < h:
        if not dirty[i]:
            c = coeffs[i].copy()
            if not extremal:
                np_pos = old_to_new[seg_start[i]]
                c[0] += new_F[np_pos] - old_F[seg_start[i]]
            segs.append(PolyModel(float(seg_lo[i]), float(seg_hi[i]), c,
                                  float(seg_err[i])))
            i += 1
            continue
        j = i
        while j < h and dirty[j]:
            j += 1
        start = 0 if i == 0 else new_boundary(int(seg_start[i]))
        end = len(new_k) if j >= h else new_boundary(int(seg_start[j]))
        if end > start:
            segs.extend(greedy_segmentation(new_k[start:end],
                                            new_F[start:end], deg, delta,
                                            fitter=fitter))
        i = j

    new_index = assemble_index_1d(segs, new_k, new_m, agg, deg, delta,
                                  keep_exact=True)
    return new_index, new_k, new_m


# ---------------------------------------------------------------------------
# the dynamic engines
# ---------------------------------------------------------------------------

class _DeltaBufferedEngine:
    """Shared delta-buffer bookkeeping + (background) refit machinery.

    Subclasses implement ``_snapshot()`` (immutable view of the data + op
    logs for the merge thread) and ``_merge(snap, mark)`` (the merge pass,
    ending in a locked ``_install``); everything about thread lifecycle,
    drain-until-empty waiting, residual-op marks, and error surfacing
    lives here once.
    """

    _refit_error: Optional[BaseException] = None

    def _init_dynamic(self, *, backend: str, capacity: int,
                      interpret: Optional[bool], bq: int,
                      min_bucket: int, auto_refit: bool,
                      background: bool) -> None:
        check_pow2("capacity", capacity)
        check_pow2("bq", bq)
        check_pow2("min_bucket", min_bucket)
        self.backend = backend
        self.capacity = capacity
        self.interpret = resolve_interpret(interpret)
        self.bq = bq
        self.min_bucket = min_bucket
        self.auto_refit = auto_refit
        self.background = background
        self.refit_count = 0
        self._lock = threading.RLock()
        self._thread: Optional[threading.Thread] = None
        self._install_listeners: List = []
        # (ins, del) log lengths captured by the in-flight merge snapshot;
        # None when no merge is running.  Extremal deletes that NaN-cancel
        # a pending insert the snapshot already copied must be replayed at
        # install (the merge bakes the un-cancelled copy into the new base).
        self._merge_mark: Optional[Tuple[int, int]] = None

    def place(self, device) -> None:
        """Commit the serving state — plan and delta buffer — to ``device``
        (the session builds on the host and places once, see
        ``api/session.py``)."""
        with self._lock:
            self._state = jax.device_put(self._state, device)

    def add_install_listener(self, fn) -> None:
        """Register ``fn(preview)`` to run on the merge thread with the
        about-to-be-installed state *before* the atomic install.  The
        serving engine uses this to pre-lower the incoming plan's bucket
        ladder so post-swap dispatches never pay a relower; listener
        errors propagate as refit errors (the install does not happen)."""
        self._install_listeners.append(fn)

    def _notify_install_listeners(self, preview) -> None:
        for fn in list(self._install_listeners):
            fn(preview)

    @property
    def n_pending(self) -> int:
        return self._n_pending

    def snapshot(self):
        """The current immutable (plan, delta-buffer) pair, as one atomic
        read — the state queries execute against.  External executors
        (e.g. ``engine.sharded``) must take both from one snapshot so the
        buffer matches the installed plan."""
        return self._state

    def _ensure_room(self, m: int) -> None:
        if m > self.capacity:
            raise ValueError(f"batch of {m} exceeds buffer capacity "
                             f"{self.capacity}; split the batch")
        if self._n_pending + m > self.capacity:
            self.refit(wait=True)   # drains every pending op (see refit)

    def flush(self) -> None:
        """Synchronously merge all buffered ops into a fresh plan."""
        self.refit(wait=True)

    def refit(self, wait: Optional[bool] = None) -> None:
        """Run (or join) a merge pass.  ``wait=False`` returns immediately
        with the merge running on a daemon thread; queries keep executing
        against the old (plan, buffer) snapshot until the atomic install.

        ``wait=True`` drains *every* pending op before returning: a joined
        thread may be a stale background merge whose snapshot predates ops
        logged since (they are replayed into the fresh buffer as
        residuals), so keep merging until nothing is pending.  MAX/MIN
        delete correctness relies on this — a residual tombstone would sit
        in a buffer the extremum executor never reads."""
        wait = (not self.background) if wait is None else wait
        t = self._start_refit()
        if wait:
            while t is not None:
                t.join()
                self._raise_refit_error()
                t = self._start_refit()
        self._raise_refit_error()

    def _raise_refit_error(self) -> None:
        if self._refit_error is not None:
            err, self._refit_error = self._refit_error, None
            raise err

    def _has_forced_work(self) -> bool:
        """Subclass hook: True when a merge must run even with zero pending
        buffered ops (e.g. the LSM shadow-fraction fold, which compacts
        tombstone-heavy levels that carry no new inserts)."""
        return False

    def _start_refit(self) -> Optional[threading.Thread]:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self._thread
            if self._n_pending == 0 and not self._has_forced_work():
                return None
            snap = self._snapshot()
            mark = (len(self._ins_log), len(self._del_log))
            t = threading.Thread(target=self._merge_and_install,
                                 args=(snap, mark), daemon=True)
            self._thread = t
        t.start()
        return t

    def _merge_and_install(self, snap, mark) -> None:
        try:
            self._merge(snap, mark)
        except BaseException as e:   # surface on the caller's next refit()
            self._refit_error = e
        finally:
            self._thread = None

    @staticmethod
    def _flatten(log: List[Tuple[np.ndarray, np.ndarray]]):
        if not log:
            z = np.zeros((0,))
            return z, z
        return (np.concatenate([k for k, _ in log]),
                np.concatenate([v for _, v in log]))


class DynamicEngine(_DeltaBufferedEngine):
    """Updatable 1-D plan: buffered inserts/deletes, fused exact
    correction, selective (optionally background) refit.

    Single-writer: ``insert``/``delete``/``refit`` are serialized by an
    internal lock; queries are lock-free against an immutable
    (plan, buffer) snapshot, so a refit never blocks them.
    """

    def __init__(self, index: PolyFitIndex1D, *, backend: str = "xla",
                 capacity: int = 1024, interpret: Optional[bool] = None,
                 bq: int = DEFAULT_BQ, min_bucket: int = 64,
                 auto_refit: bool = True, background: bool = False,
                 drift_floor: float = 0.05):
        if index.exact_sum is None and index.exact_max is None:
            raise ValueError("DynamicEngine requires an index built with "
                             "keep_exact=True (merge needs the raw data)")
        self._init_dynamic(backend=backend, capacity=capacity,
                           interpret=interpret, bq=bq,
                           min_bucket=min_bucket, auto_refit=auto_refit,
                           background=background)
        self.drift_floor = drift_floor
        self._agg = index.agg
        if index.exact_sum is not None:
            keys = np.asarray(index.exact_sum.keys)
            cf = np.asarray(index.exact_sum.cf)
            meas = np.diff(np.concatenate([[0.0], cf]))
        else:
            keys = np.asarray(index.exact_max.keys)
            meas = np.asarray(index.exact_max.measures)   # internal space
        self._install(index, keys, meas)

    # -- state ----------------------------------------------------------

    def _install(self, index: PolyFitIndex1D, keys: np.ndarray,
                 meas: np.ndarray, residual_ins: Optional[list] = None,
                 residual_del: Optional[list] = None,
                 residual_vic: Optional[list] = None,
                 plan: Optional[IndexPlan] = None) -> None:
        """Swap in a fresh (index, plan, empty-or-replayed buffer).

        ``plan`` lets the merge thread pass the plan it already built (and
        pre-lowered via the install listeners) so the installed object is
        the *same* identity the serving AOT cache was warmed against."""
        with self._lock:
            self._index = index
            self._keys = keys
            self._meas = meas
            self._seg_lo_host = np.asarray(index.seg_lo)
            err = (np.asarray(index.seg_err) if index.seg_err is not None
                   else np.zeros(index.h))
            self._budget = np.maximum(index.delta - err,
                                      self.drift_floor * index.delta)
            self._drift = np.zeros(index.h)
            self._ins_log: List[Tuple[np.ndarray, np.ndarray]] = []
            self._del_log: List[Tuple[np.ndarray, np.ndarray]] = []
            self._n_pending = 0
            self._vic: List[int] = []
            self._residual_vic: List[Tuple[float, float]] = []
            self._merge_mark = None
            if plan is None:
                plan = build_plan(index)
            # the insert-log sparse table is only read by the locate->gather
            # MAX correction, so only that backend pays its upkeep
            buf = DeltaBuffer.empty(
                self.capacity, plan.dtype,
                with_st=(self._agg in ("max", "min")
                         and self.backend == "pallas"))
            self._state = (plan, buf)
            for k, v in (residual_ins or []):
                if len(k):
                    self._log_ops(k, v, delete=False)
            if self._agg in ("max", "min"):
                # extremal residuals re-resolve through the victim path so
                # the fresh buffer's shadow mask covers them immediately
                nan_dirty = False
                for karr, varr in (residual_del or []):
                    for k, v in zip(karr, varr):
                        nan_dirty |= self._delete_extremal_resolved(
                            float(k), float(v))
                for k, v in (residual_vic or []):
                    nan_dirty |= self._delete_extremal_resolved(k, v)
                if nan_dirty:
                    self._rebuild_ins_buf()
                if self._vic:
                    self._refresh_vic_buf()
            else:
                for k, v in (residual_del or []):
                    if len(k):
                        self._log_ops(k, v, delete=True)

    @property
    def plan(self) -> IndexPlan:
        return self._state[0]

    @property
    def index(self) -> PolyFitIndex1D:
        return self._index

    @property
    def agg(self) -> str:
        return self._agg

    # -- updates --------------------------------------------------------

    def _log_ops(self, keys: np.ndarray, vals: np.ndarray,
                 delete: bool) -> None:
        """Append a batch to the device buffer + host log + drift (locked)."""
        if self._n_pending + len(keys) > self.capacity:
            # _append_sorted would silently drop the largest keys past cap;
            # overflowing here means the single-writer contract was broken
            raise RuntimeError("delta buffer overflow: concurrent writers "
                               "bypassed _ensure_room")
        plan, buf = self._state
        dt = plan.dtype
        big = big_sentinel(dt)
        pk = _pad_batch(keys, big, dt)
        pv = _pad_batch(vals, 0.0, dt)
        # one fused jitted dispatch per chunk (log + every derived structure)
        if delete:
            dk, dv, dcf, _ = _append_1d(buf.del_keys, buf.del_vals, pk, pv,
                                        cap=buf.cap, with_st=False)
            buf = dataclasses.replace(buf, del_keys=dk, del_vals=dv,
                                      del_cf=dcf)
            self._del_log.append((keys, vals))
        else:
            ik, iv, icf, st = _append_1d(buf.ins_keys, buf.ins_vals, pk, pv,
                                         cap=buf.cap,
                                         with_st=buf.ins_st is not None)
            buf = dataclasses.replace(buf, ins_keys=ik, ins_vals=iv,
                                      ins_cf=icf, ins_st=st)
            self._ins_log.append((keys, vals))
        self._state = (plan, buf)
        self._n_pending += len(keys)
        if delete and self._agg in ("max", "min"):
            # extremal tombstones leave the fitted function and its
            # certificate untouched (the victim shadow answers exactly),
            # so they ride the capacity trigger only, never drift
            return
        seg = np.clip(np.searchsorted(self._seg_lo_host, keys, side="right")
                      - 1, 0, len(self._seg_lo_host) - 1)
        np.add.at(self._drift, seg, np.abs(vals))

    def insert(self, keys, measures=None) -> None:
        """Buffer a batch of new (key, measure) records."""
        # always copy: the host log owns these arrays (extremal deletes
        # NaN-cancel pending inserts in place)
        keys = np.atleast_1d(np.array(keys, np.float64))
        if measures is None:
            if self._agg != "count":
                raise ValueError("measures required unless agg='count'")
            measures = np.ones_like(keys)
        measures = np.broadcast_to(
            np.asarray(measures, np.float64), keys.shape).copy()
        if self._agg == "count":
            measures = np.ones_like(keys)
        if self._agg == "min":
            measures = -measures
        self._ensure_room(len(keys))
        with self._lock:
            self._log_ops(keys, measures, delete=False)
            trigger = self._should_refit()
        if trigger:
            self.refit(wait=not self.background)

    def delete(self, keys) -> None:
        """Buffer delete tombstones for existing records (KeyError if a key
        has no live occurrence).  MAX/MIN deletes shadow their victim (the
        buffer's ``vic_keys``/``live_st`` mask) instead of merging eagerly:
        queries covering the victim refine against the victim-masked exact
        sparse table, and the physical removal rides the next ordinary
        merge — no delete pays a refit on the write path."""
        keys = np.atleast_1d(np.asarray(keys, np.float64))
        self._ensure_room(len(keys))
        if self._agg in ("max", "min"):
            with self._lock:
                nan_dirty = False
                for k in keys:
                    nan_dirty |= self._delete_extremal_one(float(k))
                if nan_dirty:
                    self._rebuild_ins_buf()
                self._refresh_vic_buf()
                trigger = self._should_refit()
            if trigger:
                self.refit(wait=not self.background)
            return
        with self._lock:
            vals = []
            batch_tomb: dict = {}   # duplicates within this batch advance
            for k in keys:          # the victim cursor too
                off = batch_tomb.get(float(k), 0)
                vals.append(self._find_victim(float(k), extra_tomb=off))
                batch_tomb[float(k)] = off + 1
            self._log_ops(keys, np.array(vals), delete=True)
            trigger = self._should_refit()
        if trigger:
            self.refit(wait=not self.background)

    def _delete_extremal_one(self, key: float) -> bool:
        """Resolve one extremal delete: shadow the leftmost unshadowed base
        occurrence (victim mask + ordinary tombstone for the next merge),
        else NaN-cancel a pending insert.  Returns True when a pending
        insert was cancelled (the device insert arrays need a rebuild)."""
        i0 = np.searchsorted(self._keys, key, side="left")
        i1 = np.searchsorted(self._keys, key, side="right")
        vic_set = set(self._vic)
        for pos in range(i0, i1):
            if pos not in vic_set:
                self._vic.append(pos)
                self._log_ops(np.array([key]),
                              np.array([float(self._meas[pos])]),
                              delete=True)
                return False
        for e, (karr, varr) in enumerate(self._ins_log):
            hit = np.where((karr == key) & ~np.isnan(karr))[0]
            if len(hit):
                j = int(hit[0])
                val = float(varr[j])
                karr[j] = varr[j] = np.nan
                self._n_pending -= 1
                if (self._merge_mark is not None
                        and e < self._merge_mark[0]):
                    # the in-flight merge copied this entry before the mark
                    # and will bake it into the new base — replay there
                    self._residual_vic.append((key, val))
                return True
        raise KeyError(f"delete of key {key!r}: no live occurrence")

    def _delete_extremal_resolved(self, key: float, val: float) -> bool:
        """Replay a residual extremal delete against the freshly installed
        base (value-matched victim preferred, then a pending insert, then
        any live occurrence).  Locked; returns True on a NaN-cancel."""
        i0 = np.searchsorted(self._keys, key, side="left")
        i1 = np.searchsorted(self._keys, key, side="right")
        vic_set = set(self._vic)
        cand = [p for p in range(i0, i1) if p not in vic_set]
        pos = next((p for p in cand if self._meas[p] == val),
                   cand[0] if cand else None)
        if pos is not None:
            self._vic.append(pos)
            self._log_ops(np.array([key]),
                          np.array([float(self._meas[pos])]), delete=True)
            return False
        for karr, varr in self._ins_log:
            hit = np.where((karr == key) & (varr == val)
                           & ~np.isnan(karr))[0]
            if len(hit):
                j = int(hit[0])
                karr[j] = varr[j] = np.nan
                self._n_pending -= 1
                return True
        raise KeyError(f"delete of key {key!r}: no live occurrence")

    def _refresh_vic_buf(self) -> None:
        """Rebuild the buffer's victim mask (sorted shadow keys + the
        victim-masked exact sparse table) and swap it in atomically."""
        plan, buf = self._state
        dt = plan.dtype
        if not self._vic:
            if buf.vic_keys is not None:
                buf = dataclasses.replace(buf, vic_keys=None, live_st=None)
                self._state = (plan, buf)
            return
        nv = len(self._vic)
        vcap = self.capacity
        while vcap < nv:
            vcap *= 2
        vk = np.full((vcap,), big_sentinel(np.float64))
        vk[:nv] = np.sort(self._keys[np.asarray(self._vic)])
        m = np.array(self._meas, np.float64, copy=True)
        m[np.asarray(self._vic)] = -np.inf
        buf = dataclasses.replace(
            buf, vic_keys=jnp.asarray(vk, dt),
            live_st=jnp.asarray(build_sparse_table(m), dt))
        self._state = (plan, buf)

    def _rebuild_ins_buf(self) -> None:
        """Rebuild the device insert log from the non-NaN host entries
        (one fused append), after a pending insert was cancelled."""
        plan, buf = self._state
        dt = plan.dtype
        with_st = buf.ins_st is not None
        fresh = DeltaBuffer.empty(self.capacity, dt, with_st=with_st)
        ik, iv = self._flatten(self._ins_log)
        if len(ik):
            alive = ~np.isnan(ik)
            ik, iv = ik[alive], iv[alive]
        if len(ik):
            big = big_sentinel(dt)
            nk, nv_, ncf, nst = _append_1d(
                fresh.ins_keys, fresh.ins_vals, _pad_batch(ik, big, dt),
                _pad_batch(iv, 0.0, dt), cap=self.capacity, with_st=with_st)
        else:
            nk, nv_, ncf, nst = (fresh.ins_keys, fresh.ins_vals,
                                 fresh.ins_cf, fresh.ins_st)
        buf = dataclasses.replace(buf, ins_keys=nk, ins_vals=nv_,
                                  ins_cf=ncf, ins_st=nst)
        self._state = (plan, buf)

    def _find_victim(self, key: float, extra_tomb: int = 0) -> float:
        """Measure (internal space) of the occurrence a tombstone removes:
        base occurrences first (left to right), then pending inserts."""
        tomb = extra_tomb + sum(int(np.sum(k == key))
                                for k, _ in self._del_log)
        i0 = np.searchsorted(self._keys, key, side="left")
        i1 = np.searchsorted(self._keys, key, side="right")
        pool = list(self._meas[i0:i1])
        for k, v in self._ins_log:
            pool.extend(v[k == key])
        if tomb >= len(pool):
            raise KeyError(f"delete of key {key!r}: no live occurrence")
        return float(pool[tomb])

    def _should_refit(self) -> bool:
        if not self.auto_refit:
            return False
        return (self._n_pending >= self.capacity
                or bool((self._drift > self._budget).any()))

    # -- merge / refit (lifecycle in _DeltaBufferedEngine) ----------------

    def _snapshot(self):
        # deep-copy the log arrays: extremal deletes NaN-cancel pending
        # inserts *in place* on the host log, which must not race the merge
        # thread's reads of this snapshot
        self._merge_mark = (len(self._ins_log), len(self._del_log))
        self._residual_vic = []
        return (self._index, self._keys, self._meas,
                [(k.copy(), v.copy()) for k, v in self._ins_log],
                [(k.copy(), v.copy()) for k, v in self._del_log])

    def _merge(self, snap, mark) -> None:
        index, keys, meas, ins_log, del_log = snap
        ik, iv = self._flatten(ins_log)
        if len(ik):
            alive = ~np.isnan(ik)   # NaN-cancelled pending inserts
            ik, iv = ik[alive], iv[alive]
        dk, dv = self._flatten(del_log)
        new_index, new_k, new_m = _merge_1d(index, keys, meas, ik, iv, dk, dv)
        # build the plan OFF the lock and hand the pre-lowered identity to
        # _install: the install listeners (serving AOT pre-compilation) see
        # the exact object queries will dispatch against after the swap
        new_plan = build_plan(new_index)
        self._notify_install_listeners(new_plan)
        with self._lock:
            residual_ins = [(k[~np.isnan(k)], v[~np.isnan(k)])
                            for k, v in self._ins_log[mark[0]:]]
            residual_del = self._del_log[mark[1]:]
            residual_vic = list(self._residual_vic)
            self._install(new_index, new_k, new_m, residual_ins,
                          residual_del, residual_vic, plan=new_plan)
            self.refit_count += 1

    # -- queries ---------------------------------------------------------

    def _prepare(self, lq, uq):
        lq, uq = jnp.asarray(lq), jnp.asarray(uq)
        n = lq.shape[0]
        size = _bucket_size(n, self.min_bucket)
        return lq, uq, n, size, min(self.bq, size)

    def sum(self, lq, uq, eps_rel: Optional[float] = None) -> QueryResult:
        assert self._agg in ("sum", "count"), self._agg
        plan, buf = self._state
        if eps_rel is not None and plan.ref_cf is None:
            raise ValueError("Q_rel refinement requires exact arrays")
        lq, uq, n, size, bq = self._prepare(lq, uq)
        fill = plan.domain_lo.astype(lq.dtype)
        ans, approx, refined = _exec_dyn_sum(
            plan, buf, _pad_bucket(lq, size, fill),
            _pad_bucket(uq, size, fill), backend=self.backend,
            eps_rel=eps_rel, interpret=self.interpret, bq=bq)
        return QueryResult(ans[:n], approx[:n], refined[:n])

    count = sum

    def quantile(self, q) -> QuantileResult:
        """Certified quantile fractions against the live plan-plus-buffer
        state: the delta buffer enters through its exact prefix-sum
        correction, so no flush is needed (DESIGN.md §16)."""
        assert self._agg in ("sum", "count"), self._agg
        plan, buf = self._state
        if plan.deg < 1:
            raise ValueError("quantile inversion needs a plan with "
                             "deg >= 1")
        q = jnp.asarray(q)
        n = q.shape[0]
        size = _bucket_size(n, self.min_bucket)
        ans, lo, hi = _exec_dyn_quantile(
            plan, buf, _pad_bucket(q, size, 0.5), backend=self.backend,
            interpret=self.interpret, bq=min(self.bq, size))
        return QuantileResult(ans[:n], lo[:n], hi[:n])

    def extremum(self, lq, uq, eps_rel: Optional[float] = None) -> QueryResult:
        assert self._agg in ("max", "min"), self._agg
        plan, buf = self._state
        if eps_rel is not None and plan.ref_st is None:
            raise ValueError("Q_rel refinement requires exact arrays")
        backend = self.backend
        if backend in ("pallas", "pallas_scan", "ref") and plan.deg > 3:
            backend = "xla"   # no in-kernel closed form past deg 3
        lq, uq, n, size, bq = self._prepare(lq, uq)
        fill = plan.domain_lo.astype(lq.dtype)
        ans, approx, refined = _exec_dyn_extremum(
            plan, buf, _pad_bucket(lq, size, fill),
            _pad_bucket(uq, size, fill), backend=backend,
            eps_rel=eps_rel, interpret=self.interpret, bq=bq)
        return QueryResult(ans[:n], approx[:n], refined[:n])

    def query(self, lq, uq, eps_rel: Optional[float] = None) -> QueryResult:
        if self._agg in ("sum", "count"):
            return self.sum(lq, uq, eps_rel=eps_rel)
        return self.extremum(lq, uq, eps_rel=eps_rel)


class DynamicEngine2D(_DeltaBufferedEngine):
    """Updatable 2-key plan (COUNT/SUM/dominance MAX/MIN): buffered point
    inserts/deletes with the fused exact correction; the merge pass runs
    ``core.index2d.selective_refit_2d``, touching only the leaves whose
    regions the changed points' dominance boundaries cross (stats of the
    last merge in ``last_refit_stats``)."""

    def __init__(self, index: PolyFitIndex2D, *, backend: str = "xla",
                 capacity: int = 1024, interpret: Optional[bool] = None,
                 bq: int = DEFAULT_BQ, min_bucket: int = 64,
                 auto_refit: bool = True, background: bool = False):
        if index.exact is None:
            raise ValueError("DynamicEngine2D requires keep_exact=True")
        self._init_dynamic(backend=backend, capacity=capacity,
                           interpret=interpret, bq=bq,
                           min_bucket=min_bucket, auto_refit=auto_refit,
                           background=background)
        self._agg = index.agg
        self.last_refit_stats: Optional[dict] = None
        px = np.asarray(index.exact.xs)
        py = np.asarray(index.exact.ys_levels[0])
        if self._weighted:
            if index.measures_sorted is None:
                raise ValueError(f"a {self._agg} DynamicEngine2D needs an "
                                 "index built with measures")
            pw = np.asarray(index.measures_sorted)
        else:
            pw = np.ones_like(px)
        self._install(index, px, py, pw)

    @property
    def _weighted(self) -> bool:
        return self._agg != "count2d"

    @property
    def agg(self) -> str:
        return self._agg

    def _install(self, index: PolyFitIndex2D, px: np.ndarray, py: np.ndarray,
                 pw: np.ndarray, residual_ins: Optional[list] = None,
                 residual_del: Optional[list] = None,
                 residual_vic: Optional[list] = None,
                 plan: Optional[IndexPlan2D] = None) -> None:
        with self._lock:
            self._index = index
            self._px = px
            self._py = py
            self._pw = pw
            self._ins_log: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
            self._del_log: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
            self._n_pending = 0
            self._vic: List[int] = []
            self._residual_vic: List[Tuple[float, float, float]] = []
            self._merge_mark = None
            if plan is None:
                plan = build_plan_2d(index)
            buf = DeltaBuffer2D.empty(self.capacity, plan.dtype,
                                      weighted=self._weighted)
            self._state = (plan, buf)
            for x, y, w in (residual_ins or []):
                if len(x):
                    self._log_ops(x, y, w, delete=False)
            if self._agg in ("max2d", "min2d"):
                nan_dirty = False
                for xa, ya, wa in (residual_del or []):
                    for x, y, w in zip(xa, ya, wa):
                        nan_dirty |= self._delete_extremal_resolved(
                            float(x), float(y), float(w))
                for x, y, w in (residual_vic or []):
                    nan_dirty |= self._delete_extremal_resolved(x, y, w)
                if nan_dirty:
                    self._rebuild_ins_buf()
                if self._vic:
                    self._refresh_vic_buf()
            else:
                for x, y, w in (residual_del or []):
                    if len(x):
                        self._log_ops(x, y, w, delete=True)

    @property
    def plan(self) -> IndexPlan2D:
        return self._state[0]

    @property
    def index(self) -> PolyFitIndex2D:
        return self._index

    def _log_ops(self, xs: np.ndarray, ys: np.ndarray, ws: np.ndarray,
                 delete: bool) -> None:
        if self._n_pending + len(xs) > self.capacity:
            raise RuntimeError("delta buffer overflow: concurrent writers "
                               "bypassed _ensure_room")
        plan, buf = self._state
        dt = plan.dtype
        big = big_sentinel(dt)
        pkx = _pad_batch(xs, big, dt)
        pky = _pad_batch(ys, big, dt)
        pkw = _pad_batch(ws, 0.0, dt)
        # merge-sort-tree levels are only read by the locate->gather
        # correction, so only that backend pays the per-append block sorts;
        # either way the whole append is ONE fused jitted dispatch
        lv = self.backend == "pallas"
        if delete:
            bx, by, bw = buf.del_x, buf.del_y, buf.del_w
        else:
            bx, by, bw = buf.ins_x, buf.ins_y, buf.ins_w
        x, y, w, ylv, wcum, wpmax = _append_2d(
            bx, by, bw if self._weighted else bx, pkx, pky, pkw,
            cap=buf.cap, levels=lv, weighted=self._weighted)
        if delete:
            buf = dataclasses.replace(
                buf, del_x=x, del_y=y,
                del_w=w if self._weighted else None,
                del_ylv=ylv if lv else buf.del_ylv,
                del_wcum=wcum if (lv and self._weighted) else buf.del_wcum)
        else:
            buf = dataclasses.replace(
                buf, ins_x=x, ins_y=y,
                ins_w=w if self._weighted else None,
                ins_ylv=ylv if lv else buf.ins_ylv,
                ins_wcum=wcum if (lv and self._weighted) else buf.ins_wcum,
                ins_wpmax=(wpmax if (lv and self._weighted)
                           else buf.ins_wpmax))
        (self._del_log if delete else self._ins_log).append((xs, ys, ws))
        self._state = (plan, buf)
        self._n_pending += len(xs)

    def insert(self, xs, ys, ws=None) -> None:
        """Buffer new points; ``ws`` are the measures for sum2d/max2d/min2d
        tables (count2d counts records, measures must be omitted).

        A dominance MAX/MIN insert *below the frozen extremal floor*
        merges eagerly: the plan's clamp over-reports every query that
        dominates only the new point, and no monotone correction covers
        it — ``selective_refit_2d`` re-freezes the floor and refits
        exactly the leaves the old clamp touched."""
        # always copy: the host log owns these arrays (extremal deletes
        # NaN-cancel pending inserts in place)
        xs = np.atleast_1d(np.array(xs, np.float64))
        ys = np.atleast_1d(np.array(ys, np.float64))
        if not self._weighted:
            if ws is not None:
                raise ValueError("measures only apply to sum2d/max2d/min2d")
            ws = np.ones_like(xs)
        else:
            if ws is None:
                raise ValueError(f"measures required for agg={self._agg!r}")
            ws = np.broadcast_to(
                np.asarray(ws, np.float64), xs.shape).copy()
            if self._agg == "min2d":
                ws = -ws
        self._ensure_room(len(xs))
        with self._lock:
            self._log_ops(xs, ys, ws, delete=False)
            trigger = self.auto_refit and self._n_pending >= self.capacity
            floor = (self._index.extremal_floor
                     if self._agg in ("max2d", "min2d") else None)
            below_floor = floor is not None and bool((ws < floor).any())
        if below_floor:
            self.refit(wait=True)
        elif trigger:
            self.refit(wait=not self.background)

    def delete(self, xs, ys) -> None:
        """Buffer delete tombstones for existing points (KeyError if a
        point has no live occurrence).  Dominance MAX/MIN deletes shadow
        their victim (``vic_x``/``vic_y``/``live_wpmax`` in the buffer)
        instead of merging eagerly: corners dominating the victim refine
        against the victim-masked merge-sort tree, and the physical
        removal rides the next ordinary merge (the 1-D rule, DESIGN.md
        §9/§15)."""
        xs = np.atleast_1d(np.asarray(xs, np.float64))
        ys = np.atleast_1d(np.asarray(ys, np.float64))
        self._ensure_room(len(xs))
        if self._agg in ("max2d", "min2d"):
            with self._lock:
                nan_dirty = False
                for x, y in zip(xs, ys):
                    nan_dirty |= self._delete_extremal_one(float(x),
                                                           float(y))
                if nan_dirty:
                    self._rebuild_ins_buf()
                self._refresh_vic_buf()
                trigger = self.auto_refit and self._n_pending >= self.capacity
            if trigger:
                self.refit(wait=not self.background)
            return
        with self._lock:
            ws = []
            batch_tomb: dict = {}   # duplicates within this batch count too
            for x, y in zip(xs, ys):
                pt = (float(x), float(y))
                ws.append(self._find_victim(*pt,
                                            extra_tomb=batch_tomb.get(pt, 0)))
                batch_tomb[pt] = batch_tomb.get(pt, 0) + 1
            self._log_ops(xs, ys, np.asarray(ws), delete=True)
            trigger = self.auto_refit and self._n_pending >= self.capacity
        if trigger:
            self.refit(wait=not self.background)

    def _delete_extremal_one(self, x: float, y: float) -> bool:
        """Resolve one dominance MAX/MIN delete: shadow the leftmost
        unshadowed base occurrence of (x, y), else NaN-cancel a pending
        insert.  Returns True on a NaN-cancel (device rebuild needed)."""
        i0 = np.searchsorted(self._px, x, side="left")
        i1 = np.searchsorted(self._px, x, side="right")
        vic_set = set(self._vic)
        for pos in range(i0, i1):
            if self._py[pos] == y and pos not in vic_set:
                self._vic.append(pos)
                self._log_ops(np.array([x]), np.array([y]),
                              np.array([float(self._pw[pos])]), delete=True)
                return False
        for e, (xa, ya, wa) in enumerate(self._ins_log):
            hit = np.where((xa == x) & (ya == y) & ~np.isnan(xa))[0]
            if len(hit):
                j = int(hit[0])
                w = float(wa[j])
                xa[j] = ya[j] = wa[j] = np.nan
                self._n_pending -= 1
                if (self._merge_mark is not None
                        and e < self._merge_mark[0]):
                    self._residual_vic.append((x, y, w))
                return True
        raise KeyError(f"delete of point ({x!r}, {y!r}): not present")

    def _delete_extremal_resolved(self, x: float, y: float,
                                  w: float) -> bool:
        """Replay a residual dominance delete against the fresh base
        (measure-matched victim preferred, then a pending insert, then any
        live occurrence).  Locked; returns True on a NaN-cancel."""
        i0 = np.searchsorted(self._px, x, side="left")
        i1 = np.searchsorted(self._px, x, side="right")
        vic_set = set(self._vic)
        cand = [p for p in range(i0, i1)
                if self._py[p] == y and p not in vic_set]
        pos = next((p for p in cand if self._pw[p] == w),
                   cand[0] if cand else None)
        if pos is not None:
            self._vic.append(pos)
            self._log_ops(np.array([x]), np.array([y]),
                          np.array([float(self._pw[pos])]), delete=True)
            return False
        for xa, ya, wa in self._ins_log:
            hit = np.where((xa == x) & (ya == y) & (wa == w)
                           & ~np.isnan(xa))[0]
            if len(hit):
                j = int(hit[0])
                xa[j] = ya[j] = wa[j] = np.nan
                self._n_pending -= 1
                return True
        raise KeyError(f"delete of point ({x!r}, {y!r}): not present")

    def _refresh_vic_buf(self) -> None:
        """Rebuild the buffer's victim mask (shadow points + the
        victim-masked weighted merge-sort tree) and swap it in."""
        plan, buf = self._state
        dt = plan.dtype
        if not self._vic:
            if buf.vic_x is not None:
                buf = dataclasses.replace(buf, vic_x=None, vic_y=None,
                                          live_wpmax=None)
                self._state = (plan, buf)
            return
        nv = len(self._vic)
        vcap = self.capacity
        while vcap < nv:
            vcap *= 2
        vic = np.asarray(self._vic)
        big = big_sentinel(np.float64)
        vx = np.full((vcap,), big)
        vy = np.full((vcap,), big)
        vx[:nv] = self._px[vic]
        vy[:nv] = self._py[vic]
        ws = np.array(self._pw, np.float64, copy=True)
        ws[vic] = -np.inf
        # self._px is x-sorted, so MergeSortTree.build's stable argsort is
        # the identity and the tree's positions align with plan.ref_*
        t = MergeSortTree.build(self._px, self._py, ws=ws)
        buf = dataclasses.replace(
            buf, vic_x=jnp.asarray(vx, dt), vic_y=jnp.asarray(vy, dt),
            live_wpmax=jnp.asarray(t.wpmax_levels, dt))
        self._state = (plan, buf)

    def _rebuild_ins_buf(self) -> None:
        """Rebuild the device insert log from the non-NaN host entries
        (one fused append), after a pending insert was cancelled."""
        plan, buf = self._state
        dt = plan.dtype
        fresh = DeltaBuffer2D.empty(self.capacity, dt,
                                    weighted=self._weighted)
        ix, iy, iw = self._flatten3(self._ins_log)
        if len(ix):
            alive = ~np.isnan(ix)
            ix, iy, iw = ix[alive], iy[alive], iw[alive]
        if len(ix):
            big = big_sentinel(dt)
            lv = self.backend == "pallas"
            x, y, w, ylv, wcum, wpmax = _append_2d(
                fresh.ins_x, fresh.ins_y,
                fresh.ins_w if self._weighted else fresh.ins_x,
                _pad_batch(ix, big, dt), _pad_batch(iy, big, dt),
                _pad_batch(iw, 0.0, dt), cap=self.capacity, levels=lv,
                weighted=self._weighted)
            buf = dataclasses.replace(
                buf, ins_x=x, ins_y=y,
                ins_w=w if self._weighted else None,
                ins_ylv=ylv if lv else fresh.ins_ylv,
                ins_wcum=(wcum if (lv and self._weighted)
                          else fresh.ins_wcum),
                ins_wpmax=(wpmax if (lv and self._weighted)
                           else fresh.ins_wpmax))
        else:
            buf = dataclasses.replace(
                buf, ins_x=fresh.ins_x, ins_y=fresh.ins_y,
                ins_w=fresh.ins_w, ins_ylv=fresh.ins_ylv,
                ins_wcum=fresh.ins_wcum, ins_wpmax=fresh.ins_wpmax)
        self._state = (plan, buf)

    def _point_pool(self, x: float, y: float) -> list:
        """Measures (internal space) of the live-or-tombstoned occurrences
        of (x, y): base occurrences first (x-order), then pending inserts."""
        i0 = np.searchsorted(self._px, x, side="left")
        i1 = np.searchsorted(self._px, x, side="right")
        pool = list(self._pw[i0:i1][self._py[i0:i1] == y])
        for lx, ly, lw in self._ins_log:
            pool.extend(lw[(lx == x) & (ly == y)])
        return pool

    def _find_victim(self, x: float, y: float, extra_tomb: int = 0) -> float:
        """Measure of the occurrence this tombstone removes (KeyError when
        every occurrence is already tombstoned)."""
        tomb = extra_tomb + sum(int(np.sum((lx == x) & (ly == y)))
                                for lx, ly, _ in self._del_log)
        pool = self._point_pool(x, y)
        if tomb >= len(pool):
            raise KeyError(f"delete of point ({x!r}, {y!r}): not present")
        return float(pool[tomb])

    # -- merge / refit (lifecycle in _DeltaBufferedEngine) ----------------

    def _snapshot(self):
        # deep-copy the log arrays: extremal deletes NaN-cancel pending
        # inserts in place on the host log (see DynamicEngine._snapshot)
        self._merge_mark = (len(self._ins_log), len(self._del_log))
        self._residual_vic = []
        return (self._index, self._px, self._py, self._pw,
                [tuple(a.copy() for a in e) for e in self._ins_log],
                [tuple(a.copy() for a in e) for e in self._del_log])

    @staticmethod
    def _flatten3(log):
        if not log:
            z = np.zeros((0,))
            return z, z, z
        return tuple(np.concatenate([e[i] for e in log]) for i in range(3))

    def _merge(self, snap, mark) -> None:
        index, px, py, pw, ins_log, del_log = snap
        ix, iy, iw = (np.array(a) for a in self._flatten3(ins_log))
        dx, dy, dw = self._flatten3(del_log)
        keep = np.ones(len(px), bool)
        for x, y, w in zip(dx, dy, dw):
            # a tombstone cancels a matching pending insert first, then the
            # base occurrence carrying the victim's measure
            m = np.where((ix == x) & (iy == y) & (iw == w)
                         & ~np.isnan(ix))[0]
            if len(m):
                ix[m[0]] = iy[m[0]] = iw[m[0]] = np.nan
                continue
            cand = np.where(keep & (px == x) & (py == y) & (pw == w))[0]
            if not len(cand):
                cand = np.where(keep & (px == x) & (py == y))[0]
            if not len(cand):
                raise KeyError(f"delete of point ({x!r}, {y!r})")
            keep[cand[0]] = False
        alive = ~np.isnan(ix) if len(ix) else np.zeros(0, bool)
        new_px = np.concatenate([px[keep], ix[alive]])
        new_py = np.concatenate([py[keep], iy[alive]])
        new_pw = np.concatenate([pw[keep], iw[alive]])
        if len(new_px) == 0:
            raise ValueError("merge would empty the dataset")
        # net changes only: an insert+delete pair that cancelled inside the
        # buffer never touched the fitted function
        removed = ~keep
        cx = np.concatenate([ix[alive], px[removed]])
        cy = np.concatenate([iy[alive], py[removed]])
        cw = np.concatenate([iw[alive], -pw[removed]])
        new_index, stats = selective_refit_2d(index, new_px, new_py, new_pw,
                                              cx, cy, cw)
        order = np.argsort(new_px, kind="stable")
        # plan built off-lock; listeners (serving AOT pre-compilation) warm
        # against the exact object that will be installed
        new_plan = build_plan_2d(new_index)
        self._notify_install_listeners(new_plan)
        with self._lock:
            residual_ins = [tuple(a[~np.isnan(e[0])] for a in e)
                            for e in self._ins_log[mark[0]:]]
            residual_del = self._del_log[mark[1]:]
            residual_vic = list(self._residual_vic)
            self._install(new_index, new_px[order], new_py[order],
                          new_pw[order], residual_ins, residual_del,
                          residual_vic, plan=new_plan)
            self.last_refit_stats = stats
            self.refit_count += 1

    # -- queries ---------------------------------------------------------

    def _run_rect(self, executor, lx, ux, ly, uy, eps_rel):
        plan, buf = self._state
        if eps_rel is not None and plan.ref_xs is None:
            raise ValueError("Q_rel refinement requires exact arrays")
        qs = [jnp.asarray(q) for q in (lx, ux, ly, uy)]
        n = qs[0].shape[0]
        size = _bucket_size(n, self.min_bucket)
        bq = min(self.bq, size)
        x0, _, y0, _ = plan.root
        fills = (x0, x0, y0, y0)
        padded = [_pad_bucket(q, size, f) for q, f in zip(qs, fills)]
        ans, approx, refined = executor(
            plan, buf, *padded, backend=self.backend, eps_rel=eps_rel,
            interpret=self.interpret, bq=bq)
        return QueryResult(ans[:n], approx[:n], refined[:n])

    def count2d(self, lx, ux, ly, uy,
                eps_rel: Optional[float] = None) -> QueryResult:
        assert self._agg == "count2d", self._agg
        return self._run_rect(_exec_dyn_count2d, lx, ux, ly, uy, eps_rel)

    def sum2d(self, lx, ux, ly, uy,
              eps_rel: Optional[float] = None) -> QueryResult:
        assert self._agg == "sum2d", self._agg
        return self._run_rect(_exec_dyn_sum2d, lx, ux, ly, uy, eps_rel)

    def extremum2d(self, u, v,
                   eps_rel: Optional[float] = None) -> QueryResult:
        assert self._agg in ("max2d", "min2d"), self._agg
        plan, buf = self._state
        if eps_rel is not None and plan.ref_wpmax is None:
            raise ValueError("Q_rel refinement requires exact arrays")
        u, v = jnp.asarray(u), jnp.asarray(v)
        n = u.shape[0]
        size = _bucket_size(n, self.min_bucket)
        bq = min(self.bq, size)
        x0, _, y0, _ = plan.root
        ans, approx, refined = _exec_dyn_dommax2d(
            plan, buf, _pad_bucket(u, size, x0), _pad_bucket(v, size, y0),
            backend=self.backend, eps_rel=eps_rel, interpret=self.interpret,
            bq=bq)
        return QueryResult(ans[:n], approx[:n], refined[:n])

    def query(self, *ranges, eps_rel: Optional[float] = None) -> QueryResult:
        if self._agg == "count2d":
            return self.count2d(*ranges, eps_rel=eps_rel)
        if self._agg == "sum2d":
            return self.sum2d(*ranges, eps_rel=eps_rel)
        return self.extremum2d(*ranges, eps_rel=eps_rel)
