"""Sharded plans: segment tables partitioned across devices (ROADMAP item).

``shard_plan`` splits an ``IndexPlan``'s segment table (and its exact
refinement arrays) into contiguous key ranges — shard ``s`` owns segments
``[off_s, off_{s+1})`` and therefore every key in ``[seg_lo[off_s],
seg_lo[off_{s+1}])`` — stacks the per-shard slices on a leading axis, and a
``shard_map`` executor answers query batches with each shard computing only
the part of the answer its key range owns:

* **SUM/COUNT** — the raw answer is ``F(uq) - F(lq)`` (Eq. 14); each
  endpoint is evaluated by exactly one owner shard (the clamped query is
  masked elsewhere), the two totals are combined with ``psum`` (one nonzero
  term each), and the final subtraction happens on the replicated totals —
  the identical operation sequence as the single-device executor, so
  answers are **bit-identical**, not merely close.  A naive
  "clamp-to-shard-range and sum partial sums" scheme would not be: segment
  fits are discontinuous at boundaries, so telescoping F over shard edges
  adds up to ``2*delta*(S-1)`` of spurious error.
* **MAX/MIN** — Eq. 17 decomposes exactly: the boundary-segment closed-form
  extrema are computed by the shards owning ``lq``/``uq`` (same arithmetic
  as ``core.queries.max_eval_segments``), interior segments reduce through
  per-shard sparse tables, and a cross-shard max combines (``_pmax``) —
  floating-point ``max`` is associative, so this too is bit-identical to
  the XLA backend.
* **Exact refinement / delta buffers** — the refinement CF arrays and the
  ``DeltaBuffer`` logs are partitioned by the same key ranges.  Prefix-CF
  lookups use *global* prefix values stored at local positions (owner-masked
  psum again), masked buffer maxima ride ``_pmax``, so Q_rel refinement and
  post-insert/delete dynamic answers stay bit-identical as well.

The mapped body runs the XLA primitive path (``eval_segments`` /
``poly_max_on_interval`` / ``sparse_table_range_max``) regardless of the
engine backend — exactly the arithmetic of ``backend='xla'`` (and of
``'ref'`` for SUM/COUNT, which shares ``eval_segments``).  Kernel backends
still apply *within* each unsharded plan; sharding is about datasets larger
than one device, where each shard's table again becomes a candidate for the
locate->gather kernels (a follow-up once multi-device Pallas lowering is
validated on hardware).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.exact import build_sparse_table, sparse_table_range_max
from ..core.index2d import mst_count_prefix, mst_weighted_prefix
from ..core.poly import eval_segments, locate, scale_unit
from ..core.queries import QueryResult, poly_max_on_interval
from ..kernels import ref as _ref
from ..kernels.leaf_eval2d import _bivariate_horner
from ..kernels.locate import INT_SENTINEL, bsearch_count, interleave2
from ..kernels.poly_eval import DEFAULT_BQ, resolve_interpret
from .dynamic import (DeltaBuffer, DeltaBuffer2D, _exec_dyn_count2d,
                      _exec_dyn_dommax2d, _exec_dyn_sum2d)
from .engine import (_bucket_size, _exec_extremum2d, _exec_rect2d,
                     _pad_bucket, check_pow2)
from .plan import IndexPlan, IndexPlan2D, big_sentinel

__all__ = ["ShardedPlan", "ShardedDelta", "ShardedEngine", "shard_plan",
           "shard_buffer", "make_shard_mesh", "ShardedPlan2D",
           "ShardedEngine2D", "shard_plan_2d", "ShardedLsmPlan",
           "ShardedLsmPlan2D", "shard_lsm_plan", "shard_lsm_plan_2d",
           "execute_lsm_sharded"]

_AXIS = "shards"


def _on_mesh(tree, mesh: Mesh, specs):
    """Commit a partitioned pytree to ``mesh`` under the executors' own
    in_specs (one spec for the whole tree, or a spec pytree): each shard's
    slice lives on its own device and replicated arrays on every device,
    so a dispatch moves no plan bytes between devices."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(tree, shardings)


def make_shard_mesh(nshards: int) -> Mesh:
    """A 1-axis mesh over the first ``nshards`` local devices."""
    devs = jax.devices()
    if nshards > len(devs):
        raise ValueError(f"nshards={nshards} exceeds the {len(devs)} "
                         "available devices (force host devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return Mesh(np.array(devs[:nshards]), (_AXIS,))


@dataclasses.dataclass(frozen=True)
class ShardedPlan:
    """Per-shard slices of an ``IndexPlan``, stacked on a leading S axis.

    ``bounds`` (static metadata) are the S+1 owning-range edges
    ``(-inf, seg_lo[off_1], ..., +inf)``; ``rlo``/``rhi`` carry the same
    values as per-shard arrays for the mapped body's ownership masks.
    ``ref_cf`` holds *global* inclusive-prefix values at local positions
    (entry ``i`` of shard ``s`` is ``CF[a_s + i]`` of the unsharded array),
    so an owner shard's lookup returns exactly the unsharded value.
    """

    # -- static metadata ------------------------------------------------
    agg: str
    deg: int
    delta: float
    h: int                    # true global segment count
    n: int
    nshards: int
    domain_lo: float
    bounds: Tuple[float, ...]  # S+1 owning-range edges (host copy)
    # -- per-shard range/offset arrays (S,) ------------------------------
    rlo: jnp.ndarray
    rhi: jnp.ndarray
    off: jnp.ndarray          # int32 global index of first owned segment
    hloc: jnp.ndarray         # int32 owned segment count
    # -- stacked segment tables (S, Hs[, deg+1]) -------------------------
    seg_lo: jnp.ndarray
    seg_hi: jnp.ndarray
    coeffs: jnp.ndarray
    seg_agg: Optional[jnp.ndarray]   # max/min only
    st: Optional[jnp.ndarray]        # (S, L, Hs) local sparse tables
    # -- sharded exact-refinement arrays ---------------------------------
    ref_keys: Optional[jnp.ndarray]  # (S, R) sentinel-padded key slices
    ref_cf: Optional[jnp.ndarray]    # (S, R+1) global-prefix CF slices
    ref_st: Optional[jnp.ndarray]    # (S, L2, R) local measure tables

    @property
    def dtype(self):
        return self.coeffs.dtype


jax.tree_util.register_dataclass(
    ShardedPlan,
    data_fields=["rlo", "rhi", "off", "hloc", "seg_lo", "seg_hi", "coeffs",
                 "seg_agg", "st", "ref_keys", "ref_cf", "ref_st"],
    meta_fields=["agg", "deg", "delta", "h", "n", "nshards", "domain_lo",
                 "bounds"],
)


@dataclasses.dataclass(frozen=True)
class ShardedDelta:
    """Per-shard slices of a ``DeltaBuffer``, partitioned by the plan's
    owning key ranges.  ``ins_cf``/``del_cf`` hold *global* exclusive
    prefix sums at local positions (same trick as ``ShardedPlan.ref_cf``)."""

    ins_keys: jnp.ndarray   # (S, C) sentinel-padded
    ins_vals: jnp.ndarray   # (S, C)
    ins_cf: jnp.ndarray     # (S, C+1)
    del_keys: jnp.ndarray
    del_vals: jnp.ndarray
    del_cf: jnp.ndarray
    cap: int

    @property
    def dtype(self):
        return self.ins_vals.dtype


jax.tree_util.register_dataclass(
    ShardedDelta,
    data_fields=["ins_keys", "ins_vals", "ins_cf", "del_keys", "del_vals",
                 "del_cf"],
    meta_fields=["cap"],
)


# ---------------------------------------------------------------------------
# host-side partitioning
# ---------------------------------------------------------------------------

def _pad2(rows, length, fill):
    """Stack host rows padded to ``length`` along their first axis."""
    out = np.full((len(rows), length) + rows[0].shape[1:], fill,
                  rows[0].dtype)   # empty slices still carry the dtype
    for s, r in enumerate(rows):
        out[s, : len(r)] = r
    return jnp.asarray(out)


def shard_plan(plan: IndexPlan, nshards: int) -> ShardedPlan:
    """Partition a 1-D plan's segment table into ``nshards`` contiguous
    key ranges (balanced by segment count), shard-local sparse tables and
    refinement slices included.  Plans with fewer segments than shards
    leave the surplus shards empty (they own the degenerate range
    [+inf, +inf) and contribute the psum/pmax identity).  An
    ``LsmPlan`` ladder routes to ``shard_lsm_plan`` (every level sharded
    independently)."""
    if nshards < 1:
        raise ValueError(f"nshards must be >= 1, got {nshards}")
    if hasattr(plan, "levels"):
        return shard_lsm_plan(plan, nshards)
    h = plan.h
    dt = plan.dtype
    big = big_sentinel(dt)
    seg_lo = np.asarray(plan.seg_lo)[:h]
    seg_hi = np.asarray(plan.seg_hi)[:h]
    coeffs = np.asarray(plan.coeffs)[:h]
    seg_agg = np.asarray(plan.seg_agg)[:h]
    cuts = np.round(np.linspace(0, h, nshards + 1)).astype(np.int64)
    inner = np.where(cuts[1:-1] < h,
                     seg_lo[np.minimum(cuts[1:-1], h - 1)], np.inf)
    bounds = np.concatenate([[-np.inf], inner, [np.inf]])

    lo_rows = [seg_lo[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    hi_rows = [seg_hi[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    cf_rows = [coeffs[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    ag_rows = [seg_agg[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    hs = max(int(b - a) for a, b in zip(cuts[:-1], cuts[1:]))

    extremal = plan.agg in ("max", "min")
    st = None
    if extremal:
        st = jnp.asarray(np.stack([
            build_sparse_table(np.concatenate(
                [r, np.full(hs - len(r), -np.inf)])) for r in ag_rows]))

    ref_keys = ref_cf = ref_st = None
    if plan.ref_keys is not None:
        keys = np.asarray(plan.ref_keys)
        splits = np.searchsorted(keys, bounds[1:-1], side="left")
        edges = np.concatenate([[0], splits, [len(keys)]]).astype(np.int64)
        k_rows = [keys[a:b] for a, b in zip(edges[:-1], edges[1:])]
        r = max(len(kr) for kr in k_rows)
        ref_keys = _pad2(k_rows, r, big)
        if plan.ref_cf is not None:
            pcf = np.concatenate([[0.0], np.asarray(plan.ref_cf)])
            # local slice of the *global* padded prefix CF; tail repeats the
            # last value (owner lookups never index past their true length)
            rows = []
            for a, b in zip(edges[:-1], edges[1:]):
                sl = pcf[a: b + 1]
                rows.append(np.concatenate(
                    [sl, np.full(r + 1 - len(sl), sl[-1])]))
            ref_cf = jnp.asarray(np.stack(rows))
        if plan.ref_st is not None:
            meas = np.asarray(plan.ref_st)[0]   # level 0 = raw measures
            ref_st = jnp.asarray(np.stack([
                build_sparse_table(np.concatenate(
                    [meas[a:b], np.full(r - (b - a), -np.inf)]))
                for a, b in zip(edges[:-1], edges[1:])]))

    return ShardedPlan(
        agg=plan.agg, deg=plan.deg, delta=plan.delta, h=h, n=plan.n,
        nshards=nshards, domain_lo=float(seg_lo[0]),
        bounds=tuple(float(b) for b in bounds),
        rlo=jnp.asarray(bounds[:-1], dt), rhi=jnp.asarray(bounds[1:], dt),
        off=jnp.asarray(cuts[:-1], jnp.int32),
        hloc=jnp.asarray(np.diff(cuts), jnp.int32),
        seg_lo=_pad2(lo_rows, hs, big), seg_hi=_pad2(hi_rows, hs, big),
        coeffs=_pad2(cf_rows, hs, 0.0),
        seg_agg=_pad2(ag_rows, hs, -np.inf) if extremal else None,
        st=st, ref_keys=ref_keys, ref_cf=ref_cf, ref_st=ref_st,
    )


def shard_buffer(buf: DeltaBuffer, splan: ShardedPlan) -> ShardedDelta:
    """Partition a delta buffer by the plan's owning key ranges.

    Sentinel slots sort past every real key and land on the last shard with
    value 0 (they fail every membership/ownership test).  The CF slices keep
    global prefix values so owner lookups reproduce the unsharded arithmetic
    bit for bit.
    """
    cap = buf.cap
    inner = np.asarray(splan.bounds[1:-1])
    big = big_sentinel(splan.dtype)

    def split(keys, vals, cf):
        k = np.asarray(keys)
        v = np.asarray(vals)
        c = np.asarray(cf)
        edges = np.concatenate(
            [[0], np.searchsorted(k, inner, side="left"), [cap]]
        ).astype(np.int64)
        krs, vrs, crs = [], [], []
        for a, b in zip(edges[:-1], edges[1:]):
            krs.append(k[a:b])
            vrs.append(v[a:b])
            sl = c[a: b + 1]
            crs.append(np.concatenate(
                [sl, np.full(cap + 1 - len(sl), sl[-1])]))
        return (_pad2(krs, cap, big), _pad2(vrs, cap, 0.0),
                jnp.asarray(np.stack(crs)))

    ik, iv, icf = split(buf.ins_keys, buf.ins_vals, buf.ins_cf)
    dk, dv, dcf = split(buf.del_keys, buf.del_vals, buf.del_cf)
    return ShardedDelta(ik, iv, icf, dk, dv, dcf, cap)


# ---------------------------------------------------------------------------
# mapped-body helpers (each runs on one shard's local block; the leading
# length-1 mapped axis is stripped with [0])
# ---------------------------------------------------------------------------

def _own(q, rlo, rhi):
    return (q >= rlo) & (q < rhi)


def _psum_owned(val, own, zero=0.0):
    return jax.lax.psum(jnp.where(own, val, zero), _AXIS)


def _pmax(val):
    """Max across shards, exactly.  The TPU compiler lowers only summing
    f64 all-reduces, so each shard writes its maxima into its own row of a
    zero block, a psum gathers the rows (``x + 0 == x``), and every device
    reduces the same values ``lax.pmax`` would."""
    n = jax.lax.axis_size(_AXIS)
    mine = (jnp.arange(n) == jax.lax.axis_index(_AXIS)).reshape(
        (n,) + (1,) * val.ndim)
    rows = jnp.where(mine, val[None], jnp.zeros((), val.dtype))
    return jnp.max(jax.lax.psum(rows, _AXIS), axis=0)


def _sum_endpoints(sp: ShardedPlan, lqc, uqc):
    """(F(lq), F(uq)) totals — each endpoint evaluated by its owner only."""
    rlo, rhi = sp.rlo[0], sp.rhi[0]
    args = (sp.seg_lo[0], sp.seg_hi[0], sp.coeffs[0])
    fl = _psum_owned(eval_segments(lqc, *args), _own(lqc, rlo, rhi))
    fu = _psum_owned(eval_segments(uqc, *args), _own(uqc, rlo, rhi))
    return fl, fu


def _extremum_raw(sp: ShardedPlan, lqc, uqc):
    """Eq. 17 decomposed: owner-computed boundary extrema + per-shard
    interior sparse-table maxima, combined with pmax (exact for max)."""
    rlo, rhi = sp.rlo[0], sp.rhi[0]
    seg_lo, seg_hi, coeffs = sp.seg_lo[0], sp.seg_hi[0], sp.coeffs[0]
    off, hloc = sp.off[0], sp.hloc[0]
    own_l = _own(lqc, rlo, rhi)
    own_u = _own(uqc, rlo, rhi)
    il_loc = locate(lqc, seg_lo)
    iu_loc = locate(uqc, seg_lo)
    il = _psum_owned(off + il_loc, own_l, 0)
    iu = _psum_owned(off + iu_loc, own_u, 0)
    same = il == iu
    ninf = -jnp.inf

    # left boundary segment: [lq, min(hi_l, uq)] — owner shard only
    lo_l, hi_l = seg_lo[il_loc], seg_hi[il_loc]
    ua_l = scale_unit(lqc, lo_l, hi_l)
    ub_l = scale_unit(jnp.minimum(hi_l, uqc), lo_l, hi_l)
    m_left = poly_max_on_interval(coeffs[il_loc], ua_l, ub_l)
    m_left = jnp.where(lqc <= hi_l, m_left, ninf)
    m_left = jnp.where(own_l, m_left, ninf)
    # right boundary segment: [max(lo_u, lq), uq] — owner shard only
    lo_u, hi_u = seg_lo[iu_loc], seg_hi[iu_loc]
    ua_u = scale_unit(jnp.maximum(lo_u, lqc), lo_u, hi_u)
    ub_u = scale_unit(uqc, lo_u, hi_u)
    m_right = jnp.where(same | ~own_u, ninf,
                        poly_max_on_interval(coeffs[iu_loc], ua_u, ub_u))
    # interior fully-covered segments owned by this shard
    a = jnp.clip(il + 1 - off, 0, hloc)
    b = jnp.clip(iu - off, 0, hloc)
    m_mid = sparse_table_range_max(sp.st[0], a, b)
    part = jnp.maximum(jnp.maximum(m_left, m_right), m_mid)
    return _pmax(part)


def _truth_sum_tot(sp: ShardedPlan, lq, uq):
    """Exact static SUM over (lq, uq] from the sharded refinement CF."""
    rlo, rhi = sp.rlo[0], sp.rhi[0]
    keys, pcf = sp.ref_keys[0], sp.ref_cf[0]
    cl = _psum_owned(pcf[jnp.searchsorted(keys, lq, side="right")],
                     _own(lq, rlo, rhi))
    cu = _psum_owned(pcf[jnp.searchsorted(keys, uq, side="right")],
                     _own(uq, rlo, rhi))
    return cu - cl


def _truth_extremum_tot(sp: ShardedPlan, lq, uq):
    """Exact static MAX over [lq, uq] — per-shard slice maxima + pmax."""
    keys = sp.ref_keys[0]
    i = jnp.searchsorted(keys, lq, side="left")
    j = jnp.searchsorted(keys, uq, side="right")
    return _pmax(sparse_table_range_max(sp.ref_st[0], i, j))


def _delta_sum_tot(keys, pcf, lq, uq, rlo, rhi):
    """Exact buffered SUM over (lq, uq] — owner-masked global-prefix diffs."""
    cl = _psum_owned(pcf[jnp.searchsorted(keys, lq, side="right")],
                     _own(lq, rlo, rhi))
    cu = _psum_owned(pcf[jnp.searchsorted(keys, uq, side="right")],
                     _own(uq, rlo, rhi))
    return cu - cl


def _delta_max_tot(keys, vals, lq, uq):
    """Exact buffered MAX over [lq, uq] — per-shard masked max + pmax."""
    member = (lq[:, None] <= keys[None, :]) & (keys[None, :] <= uq[:, None])
    part = jnp.max(jnp.where(member, vals[None, :], -jnp.inf), axis=1)
    return _pmax(part)


# ---------------------------------------------------------------------------
# fused sharded executors (one compilation per mesh/bucket/layout signature)
# ---------------------------------------------------------------------------

def _specs(mesh, n_in):
    return dict(mesh=mesh, in_specs=(P(_AXIS),) * n_in + (P(), P()),
                out_specs=(P(), P(), P()))


@partial(jax.jit, static_argnames=("mesh", "eps_rel"))
def _exec_shard_sum(splan: ShardedPlan, lq, uq, *, mesh: Mesh,
                    eps_rel: Optional[float]):
    def body(sp, lq, uq):
        dt = sp.coeffs.dtype
        lqc = jnp.maximum(lq.astype(dt), sp.domain_lo)
        uqc = jnp.maximum(uq.astype(dt), sp.domain_lo)
        fl, fu = _sum_endpoints(sp, lqc, uqc)
        approx = fu - fl
        if eps_rel is None:
            return approx, approx, jnp.zeros(approx.shape, bool)
        two_d = 2.0 * sp.delta
        ok = ((approx - two_d > 0) &
              (two_d / jnp.maximum(approx - two_d, 1e-300) <= eps_rel))
        truth = _truth_sum_tot(sp, lq, uq)
        return jnp.where(ok, approx, truth), approx, ~ok

    return shard_map(body, **_specs(mesh, 1))(splan, lq, uq)


@partial(jax.jit, static_argnames=("mesh", "eps_rel"))
def _exec_shard_extremum(splan: ShardedPlan, lq, uq, *, mesh: Mesh,
                         eps_rel: Optional[float]):
    def body(sp, lq, uq):
        dt = sp.coeffs.dtype
        lqc = jnp.maximum(lq.astype(dt), sp.domain_lo)
        uqc = jnp.maximum(uq.astype(dt), sp.domain_lo)
        approx = _extremum_raw(sp, lqc, uqc)
        neg = sp.agg == "min"
        if eps_rel is None:
            out = -approx if neg else approx
            return out, out, jnp.zeros(out.shape, bool)
        ok = approx >= sp.delta * (1.0 + 1.0 / eps_rel)
        truth = _truth_extremum_tot(sp, lq, uq)
        ans = jnp.where(ok, approx, truth)
        if neg:
            ans, approx = -ans, -approx
        return ans, approx, ~ok

    return shard_map(body, **_specs(mesh, 1))(splan, lq, uq)


@partial(jax.jit, static_argnames=("mesh", "eps_rel"))
def _exec_shard_dyn_sum(splan: ShardedPlan, sbuf: ShardedDelta, lq, uq, *,
                        mesh: Mesh, eps_rel: Optional[float]):
    def body(sp, sb, lq, uq):
        dt = sp.coeffs.dtype
        rlo, rhi = sp.rlo[0], sp.rhi[0]
        lqr, uqr = lq.astype(dt), uq.astype(dt)
        lqc = jnp.maximum(lqr, sp.domain_lo)
        uqc = jnp.maximum(uqr, sp.domain_lo)
        fl, fu = _sum_endpoints(sp, lqc, uqc)
        static = fu - fl
        # exact correction over (lq, uq] — unclamped, as in _exec_dyn_sum
        corr = (_delta_sum_tot(sb.ins_keys[0], sb.ins_cf[0],
                               lqr, uqr, rlo, rhi)
                - _delta_sum_tot(sb.del_keys[0], sb.del_cf[0],
                                 lqr, uqr, rlo, rhi))
        approx = static + corr
        if eps_rel is None:
            return approx, approx, jnp.zeros(approx.shape, bool)
        two_d = 2.0 * sp.delta
        ok = ((approx - two_d > 0) &
              (two_d / jnp.maximum(approx - two_d, 1e-300) <= eps_rel))
        truth = _truth_sum_tot(sp, lqr, uqr) + corr
        return jnp.where(ok, approx, truth), approx, ~ok

    return shard_map(body, **_specs(mesh, 2))(splan, sbuf, lq, uq)


@partial(jax.jit, static_argnames=("mesh", "eps_rel"))
def _exec_shard_dyn_extremum(splan: ShardedPlan, sbuf: ShardedDelta, lq, uq,
                             *, mesh: Mesh, eps_rel: Optional[float]):
    def body(sp, sb, lq, uq):
        dt = sp.coeffs.dtype
        lqr, uqr = lq.astype(dt), uq.astype(dt)
        lqc = jnp.maximum(lqr, sp.domain_lo)
        uqc = jnp.maximum(uqr, sp.domain_lo)
        static = _extremum_raw(sp, lqc, uqc)
        ins = _delta_max_tot(sb.ins_keys[0], sb.ins_vals[0], lqr, uqr)
        approx = jnp.maximum(static, ins)
        neg = sp.agg == "min"
        if eps_rel is None:
            out = -approx if neg else approx
            return out, out, jnp.zeros(out.shape, bool)
        ok = approx >= sp.delta * (1.0 + 1.0 / eps_rel)
        truth = jnp.maximum(_truth_extremum_tot(sp, lqr, uqr), ins)
        ans = jnp.where(ok, approx, truth)
        if neg:
            ans, approx = -ans, -approx
        return ans, approx, ~ok

    return shard_map(body, **_specs(mesh, 2))(splan, sbuf, lq, uq)


# ---------------------------------------------------------------------------
# the sharded engine
# ---------------------------------------------------------------------------

class ShardedEngine:
    """Executes queries against device-partitioned 1-D plans.

    ``shard(plan)`` partitions (and caches) a plan; ``sum``/``extremum``
    accept either an ``IndexPlan`` (sharded on first use) or a prepared
    ``ShardedPlan``.  Passing ``buf=`` a ``DeltaBuffer`` (e.g. a
    ``DynamicEngine``'s live buffer) folds buffered updates in exactly,
    keeping dynamic answers bit-identical to the single-device path.
    """

    def __init__(self, nshards: int, *, mesh: Optional[Mesh] = None,
                 min_bucket: int = 64, interpret: Optional[bool] = None):
        check_pow2("nshards", nshards)
        check_pow2("min_bucket", min_bucket)
        self.nshards = nshards
        self.mesh = mesh if mesh is not None else make_shard_mesh(nshards)
        self.min_bucket = min_bucket
        self.interpret = resolve_interpret(interpret)
        self._plan_cache: dict = {}
        self._buf_cache: dict = {}

    # -- partition caches ------------------------------------------------

    def shard(self, plan: IndexPlan) -> ShardedPlan:
        if isinstance(plan, ShardedPlan):
            return plan
        if hasattr(plan, "levels") or isinstance(plan, ShardedLsmPlan):
            return _lsm_cache_shard(self, plan, shard_lsm_plan)
        hit = self._plan_cache.get(id(plan))
        if hit is None or hit[0] is not plan:
            splan = _on_mesh(shard_plan(plan, self.nshards), self.mesh,
                             P(_AXIS))
            self._plan_cache = {id(plan): (plan, splan)}
            hit = self._plan_cache[id(plan)]
        return hit[1]

    def _shard_buf(self, splan: ShardedPlan,
                   buf: DeltaBuffer) -> ShardedDelta:
        # a partition is only valid for the owning ranges it was split
        # with, so the (single-entry) cache keys on buffer identity AND
        # the plan's bounds
        hit = self._buf_cache.get(id(buf))
        if hit is None or hit[0] is not buf or hit[1] != splan.bounds:
            sbuf = _on_mesh(shard_buffer(buf, splan), self.mesh, P(_AXIS))
            self._buf_cache = {id(buf): (buf, splan.bounds, sbuf)}
            hit = self._buf_cache[id(buf)]
        return hit[2]

    # -- queries ---------------------------------------------------------

    def _run(self, plan, lq, uq, eps_rel, buf, exec_static, exec_dyn,
             need_ref):
        splan = self.shard(plan)
        if eps_rel is not None and getattr(splan, need_ref) is None:
            raise ValueError("Q_rel refinement requires a plan built with "
                             "with_exact=True")
        lq, uq = jnp.asarray(lq), jnp.asarray(uq)
        n = lq.shape[0]
        size = _bucket_size(n, self.min_bucket)
        fill = jnp.asarray(splan.domain_lo, lq.dtype)
        args = (_pad_bucket(lq, size, fill), _pad_bucket(uq, size, fill))
        if buf is None:
            ans, approx, refined = exec_static(
                splan, *args, mesh=self.mesh, eps_rel=eps_rel)
        else:
            sbuf = self._shard_buf(splan, buf)
            ans, approx, refined = exec_dyn(
                splan, sbuf, *args, mesh=self.mesh, eps_rel=eps_rel)
        return QueryResult(ans[:n], approx[:n], refined[:n])

    def sum(self, plan, lq, uq, eps_rel: Optional[float] = None,
            buf: Optional[DeltaBuffer] = None) -> QueryResult:
        assert (plan.agg in ("sum", "count")), plan.agg
        return self._run(plan, lq, uq, eps_rel, buf, _exec_shard_sum,
                         _exec_shard_dyn_sum, "ref_cf")

    count = sum

    def extremum(self, plan, lq, uq, eps_rel: Optional[float] = None,
                 buf: Optional[DeltaBuffer] = None) -> QueryResult:
        assert plan.agg in ("max", "min"), plan.agg
        return self._run(plan, lq, uq, eps_rel, buf, _exec_shard_extremum,
                         _exec_shard_dyn_extremum, "ref_st")

    def quantile(self, plan, qs, buf: Optional[DeltaBuffer] = None):
        """Certified quantiles over an *unsharded* ``IndexPlan``.

        CF inversion is O(Q log H) scalar work — a handful of binary
        searches and closed-form root extractions per query, with no
        per-segment reduction to distribute — so partitioning the segment
        table buys nothing and would only add collectives.  The method
        exists so sharded sessions keep one entry point: it routes to the
        single-device executors (replicated on every device by XLA as
        usual) and rejects plans that have already been partitioned.
        """
        if isinstance(plan, (ShardedPlan, ShardedLsmPlan)) \
                or hasattr(plan, "levels"):
            raise ValueError(
                "quantile inversion runs on the unsharded IndexPlan — "
                "pass the original plan, not a ShardedPlan/LsmPlan "
                "(inversion is O(Q log H) scalar work; there is no "
                "per-segment reduction to shard)")
        from .dynamic import _exec_dyn_quantile
        from .engine import QuantileResult, execute_quantile
        if buf is None:
            return execute_quantile(plan, qs, backend="xla",
                                    min_bucket=self.min_bucket)
        qs = jnp.asarray(qs)
        n = qs.shape[0]
        size = _bucket_size(n, self.min_bucket)
        qp = _pad_bucket(qs, size, jnp.asarray(0.5, qs.dtype))
        ans, lo, hi = _exec_dyn_quantile(plan, buf, qp, backend="xla",
                                         interpret=self.interpret,
                                         bq=min(DEFAULT_BQ, size))
        return QuantileResult(ans[:n], lo[:n], hi[:n])

    def query(self, plan, lq, uq, eps_rel: Optional[float] = None,
              buf: Optional[DeltaBuffer] = None) -> QueryResult:
        if hasattr(plan, "levels"):
            return self.query_lsm(plan, lq, uq, eps_rel=eps_rel, buf=buf)
        if plan.agg in ("sum", "count"):
            return self.sum(plan, lq, uq, eps_rel, buf)
        return self.extremum(plan, lq, uq, eps_rel, buf)

    def query_lsm(self, lsm, lq, uq, eps_rel: Optional[float] = None,
                  buf: Optional[DeltaBuffer] = None) -> QueryResult:
        slsm = _lsm_cache_shard(self, lsm, shard_lsm_plan)
        return execute_lsm_sharded(slsm, buf, (lq, uq), mesh=self.mesh,
                                   eps_rel=eps_rel,
                                   min_bucket=self.min_bucket,
                                   interpret=self.interpret)


# ---------------------------------------------------------------------------
# 2-D: the Morton-ordered leaf table partitioned by contiguous z-ranges
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedPlan2D:
    """Per-shard z-range slices of an ``IndexPlan2D``'s Morton leaf table.

    Shard ``s`` owns the leaves whose z-interval starts fall in
    ``[zbounds[s], zbounds[s+1])`` — quadtree leaves are disjoint intervals
    in Z-order, so a (clamped) query corner's Morton code names exactly one
    owner shard.  The dyadic cut grids are replicated (they are
    O(2^depth) scalars and every shard needs them to code corners), as are
    the exact-refinement merge-sort-tree arrays and, in the dynamic
    executors, the (capacity-bounded) delta buffer: the refinement/buffer
    arithmetic runs identically on every shard with no collective, which
    keeps those answers trivially bit-identical; only the leaf-table
    evaluation is sharded and psum/pmax-combined.  Sharding the refinement
    arrays themselves stays on the ROADMAP (the BIT block structure does
    not split at arbitrary x cuts).
    """

    # -- static metadata ------------------------------------------------
    agg: str
    deg: int
    delta: float
    n: int
    n_leaves: int
    nshards: int
    max_depth: int
    root: Tuple[float, float, float, float]
    zbounds: Tuple[int, ...]     # S+1 owning z-range edges (host copy)
    # -- per-shard ownership + stacked leaf tables (S, ...) ---------------
    zlo: jnp.ndarray             # (S,) int32
    zhi: jnp.ndarray             # (S,) int32
    leaf_z: jnp.ndarray          # (S, Ls) int32 sentinel-padded
    leaf_bounds: jnp.ndarray     # (S, Ls, 4)
    leaf_coeffs: jnp.ndarray     # (S, Ls, (deg+1)^2)
    # -- replicated arrays ------------------------------------------------
    xcuts: jnp.ndarray           # (2^depth - 1,)
    ycuts: jnp.ndarray
    ref_xs: Optional[jnp.ndarray]
    ref_ys_levels: Optional[jnp.ndarray]
    ref_wcum: Optional[jnp.ndarray]
    ref_wpmax: Optional[jnp.ndarray]

    @property
    def dtype(self):
        return self.leaf_coeffs.dtype


jax.tree_util.register_dataclass(
    ShardedPlan2D,
    data_fields=["zlo", "zhi", "leaf_z", "leaf_bounds", "leaf_coeffs",
                 "xcuts", "ycuts", "ref_xs", "ref_ys_levels", "ref_wcum",
                 "ref_wpmax"],
    meta_fields=["agg", "deg", "delta", "n", "n_leaves", "nshards",
                 "max_depth", "root", "zbounds"],
)


def shard_plan_2d(plan: IndexPlan2D, nshards: int) -> ShardedPlan2D:
    """Partition a 2-D plan's Morton-ordered leaf table into ``nshards``
    contiguous z-ranges (balanced by leaf count).  Plans with fewer leaves
    than shards leave the surplus shards empty (they own the degenerate
    range [sentinel, sentinel) and contribute the psum/pmax identity).
    An ``LsmPlan2D`` ladder routes to ``shard_lsm_plan_2d``."""
    if nshards < 1:
        raise ValueError(f"nshards must be >= 1, got {nshards}")
    if hasattr(plan, "levels"):
        return shard_lsm_plan_2d(plan, nshards)
    if plan.leaf_z is None:
        raise ValueError(
            "2-D sharding requires the Morton leaf layout (max_depth <= "
            "MAX_MORTON_DEPTH and strictly increasing cut grids)")
    nl = plan.n_leaves
    leaf_z = np.asarray(plan.leaf_z)[:nl]
    bounds = np.asarray(plan.leaf_bounds)[:nl]
    coeffs = np.asarray(plan.leaf_coeffs)[:nl]
    cuts = np.round(np.linspace(0, nl, nshards + 1)).astype(np.int64)
    inner = np.where(cuts[1:-1] < nl,
                     leaf_z[np.minimum(cuts[1:-1], nl - 1)], INT_SENTINEL)
    zb = np.concatenate([[0], inner, [INT_SENTINEL]]).astype(np.int64)

    z_rows = [leaf_z[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    b_rows = [bounds[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    c_rows = [coeffs[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    ls = max(int(b - a) for a, b in zip(cuts[:-1], cuts[1:]))

    return ShardedPlan2D(
        agg=plan.agg, deg=plan.deg, delta=plan.delta, n=plan.n,
        n_leaves=nl, nshards=nshards, max_depth=plan.max_depth,
        root=plan.root, zbounds=tuple(int(z) for z in zb),
        zlo=jnp.asarray(zb[:-1], jnp.int32),
        zhi=jnp.asarray(zb[1:], jnp.int32),
        leaf_z=_pad2(z_rows, ls, INT_SENTINEL),
        leaf_bounds=_pad2(b_rows, ls, 0.0),
        leaf_coeffs=_pad2(c_rows, ls, 0.0),
        xcuts=plan.xcuts, ycuts=plan.ycuts,
        ref_xs=plan.ref_xs, ref_ys_levels=plan.ref_ys_levels,
        ref_wcum=plan.ref_wcum, ref_wpmax=plan.ref_wpmax,
    )


def _plan2d_inspec(sp: ShardedPlan2D) -> ShardedPlan2D:
    """The shard_map in_spec pytree for a ShardedPlan2D: leaf tables and
    ownership ranges partitioned on their leading S axis, cut grids and
    refinement arrays replicated."""
    kw = dict(zlo=P(_AXIS), zhi=P(_AXIS), leaf_z=P(_AXIS),
              leaf_bounds=P(_AXIS), leaf_coeffs=P(_AXIS),
              xcuts=P(), ycuts=P())
    for f in ("ref_xs", "ref_ys_levels", "ref_wcum", "ref_wpmax"):
        if getattr(sp, f) is not None:
            kw[f] = P()
    return dataclasses.replace(sp, **kw)


def _corner_eval2d_shard(sp: ShardedPlan2D, qx, qy):
    """Single-corner evaluation: the owner shard gathers the corner's leaf
    row, a psum replicates it, and the bivariate Horner runs on the
    replicated row.

    The z-locate (three binary searches, kernels/locate.py) and the gather
    are integer/selection ops — exact by construction — and the psum of
    one owner row plus zeros reproduces the owner's bits.  Deferring the
    *float* evaluation until after the collective keeps its compilation
    context independent of the mesh size and of each shard's local table
    length, so answers stay bit-identical across shard counts; fusing the
    Horner into the per-shard body instead lets XLA's FP-contraction
    choices vary with the surrounding program, costing a final ulp on
    some corners.
    """
    k = (sp.deg + 1) * (sp.deg + 1)
    ix = bsearch_count(sp.xcuts, qx, side="right")
    iy = bsearch_count(sp.ycuts, qy, side="right")
    z = interleave2(ix, iy, sp.max_depth)
    own = (z >= sp.zlo[0]) & (z < sp.zhi[0])
    row = jnp.maximum(bsearch_count(sp.leaf_z[0], z, side="right") - 1, 0)
    c = jnp.take(sp.leaf_coeffs[0], row, axis=0)
    b = jnp.take(sp.leaf_bounds[0], row, axis=0)
    cb = jnp.concatenate([c, b], axis=1)
    cb = jax.lax.psum(jnp.where(own[:, None], cb, 0.0), _AXIS)
    return _bivariate_horner(qx, qy, cb[:, :k], cb[:, k:], sp.deg)


def _rect2d_raw(sp: ShardedPlan2D, lxc, uxc, lyc, uyc):
    """4-corner inclusion-exclusion: each corner's leaf row gathered by
    its owner shard, psum-replicated, evaluated, combined with signs —
    the single-device op sequence, so bit-identical."""
    vals = [_corner_eval2d_shard(sp, qx, qy)
            for qx, qy in ((uxc, uyc), (lxc, uyc), (uxc, lyc), (lxc, lyc))]
    return vals[0] - vals[1] - vals[2] + vals[3]


def _truth_rect2d(sp: ShardedPlan2D, lx, ux, ly, uy):
    """Exact rectangle COUNT/SUM from the replicated refinement arrays
    (identical computation on every shard — no collective needed).

    The x-prefix rank comes from ``bsearch_count`` rather than
    ``jnp.searchsorted``: searchsorted's default scan lowering trips
    shard_map's replication checker on replicated operands, and the
    unrolled binary search returns the same exact integers.
    """
    if sp.agg == "sum2d":
        def cf(u, v):
            i = bsearch_count(sp.ref_xs, u, side="right")
            return mst_weighted_prefix(sp.ref_xs, sp.ref_ys_levels,
                                       sp.ref_wcum, i, v, mode="sum")
    else:
        def cf(u, v):
            i = bsearch_count(sp.ref_xs, u, side="right")
            return mst_count_prefix(sp.ref_xs, sp.ref_ys_levels, i, v)
    return (cf(ux, uy) - cf(lx, uy) - cf(ux, ly) + cf(lx, ly)).astype(
        sp.dtype)


def _truth_dommax2d(sp: ShardedPlan2D, u, v):
    """Exact dominance MAX from the replicated refinement arrays (same
    searchsorted-avoidance as ``_truth_rect2d``)."""
    i = bsearch_count(sp.ref_xs, u, side="right")
    return mst_weighted_prefix(sp.ref_xs, sp.ref_ys_levels, sp.ref_wpmax,
                               i, v, mode="max").astype(sp.dtype)


def _clamp2d(sp: ShardedPlan2D, qs):
    dt = sp.dtype
    x0, x1, y0, y1 = sp.root
    lx, ux, ly, uy = (q.astype(dt) for q in qs)
    return ((lx, ux, ly, uy),
            (jnp.clip(lx, x0, x1), jnp.clip(ux, x0, x1),
             jnp.clip(ly, y0, y1), jnp.clip(uy, y0, y1)))


@partial(jax.jit, static_argnames=("mesh", "eps_rel"))
def _exec_shard_rect2d(sp: ShardedPlan2D, lx, ux, ly, uy, *, mesh: Mesh,
                       eps_rel: Optional[float]):
    def body(sp, lx, ux, ly, uy):
        (lxr, uxr, lyr, uyr), clamped = _clamp2d(sp, (lx, ux, ly, uy))
        approx = _rect2d_raw(sp, *clamped)
        if eps_rel is None:
            return approx, approx, jnp.zeros(approx.shape, bool)
        ok = approx >= 4.0 * sp.delta * (1.0 + 1.0 / eps_rel)   # Lemma 6.4
        truth = _truth_rect2d(sp, lxr, uxr, lyr, uyr)
        return jnp.where(ok, approx, truth), approx, ~ok

    return shard_map(body, mesh=mesh,
                     in_specs=(_plan2d_inspec(sp),) + (P(),) * 4,
                     out_specs=(P(), P(), P()))(sp, lx, ux, ly, uy)


@partial(jax.jit, static_argnames=("mesh", "eps_rel"))
def _exec_shard_dyn_rect2d(sp: ShardedPlan2D, buf: DeltaBuffer2D,
                           lx, ux, ly, uy, *, mesh: Mesh,
                           eps_rel: Optional[float]):
    def body(sp, buf, lx, ux, ly, uy):
        (lxr, uxr, lyr, uyr), clamped = _clamp2d(sp, (lx, ux, ly, uy))
        static = _rect2d_raw(sp, *clamped)
        # replicated exact correction — the dense (xla-backend) arithmetic
        # of the single-device dynamic executor, unclamped
        if sp.agg == "sum2d":
            corr = (_ref.delta_sum2d_ref(lxr, uxr, lyr, uyr, buf.ins_x,
                                         buf.ins_y, buf.ins_w)
                    - _ref.delta_sum2d_ref(lxr, uxr, lyr, uyr, buf.del_x,
                                           buf.del_y, buf.del_w))
        else:
            corr = (_ref.delta_count2d_ref(lxr, uxr, lyr, uyr, buf.ins_x,
                                           buf.ins_y, dtype=sp.dtype)
                    - _ref.delta_count2d_ref(lxr, uxr, lyr, uyr, buf.del_x,
                                             buf.del_y, dtype=sp.dtype))
        approx = static + corr
        if eps_rel is None:
            return approx, approx, jnp.zeros(approx.shape, bool)
        ok = approx >= 4.0 * sp.delta * (1.0 + 1.0 / eps_rel)
        truth = _truth_rect2d(sp, lxr, uxr, lyr, uyr) + corr
        return jnp.where(ok, approx, truth), approx, ~ok

    return shard_map(body, mesh=mesh,
                     in_specs=(_plan2d_inspec(sp), P()) + (P(),) * 4,
                     out_specs=(P(), P(), P()))(sp, buf, lx, ux, ly, uy)


@partial(jax.jit, static_argnames=("mesh", "eps_rel"))
def _exec_shard_dommax2d(sp: ShardedPlan2D, u, v, *, mesh: Mesh,
                         eps_rel: Optional[float]):
    def body(sp, u, v):
        dt = sp.dtype
        x0, x1, y0, y1 = sp.root
        ur, vr = u.astype(dt), v.astype(dt)
        uc = jnp.clip(ur, x0, x1)
        vc = jnp.clip(vr, y0, y1)
        approx = _corner_eval2d_shard(sp, uc, vc)
        neg = sp.agg == "min2d"
        if eps_rel is None:
            out = -approx if neg else approx
            return out, out, jnp.zeros(out.shape, bool)
        ok = approx >= sp.delta * (1.0 + 1.0 / eps_rel)
        truth = _truth_dommax2d(sp, ur, vr)
        ans = jnp.where(ok, approx, truth)
        if neg:
            ans, approx = -ans, -approx
        return ans, approx, ~ok

    return shard_map(body, mesh=mesh,
                     in_specs=(_plan2d_inspec(sp), P(), P()),
                     out_specs=(P(), P(), P()))(sp, u, v)


@partial(jax.jit, static_argnames=("mesh", "eps_rel"))
def _exec_shard_dyn_dommax2d(sp: ShardedPlan2D, buf: DeltaBuffer2D, u, v,
                             *, mesh: Mesh, eps_rel: Optional[float]):
    def body(sp, buf, u, v):
        dt = sp.dtype
        x0, x1, y0, y1 = sp.root
        ur, vr = u.astype(dt), v.astype(dt)
        uc = jnp.clip(ur, x0, x1)
        vc = jnp.clip(vr, y0, y1)
        static = _corner_eval2d_shard(sp, uc, vc)
        ins = _ref.delta_dommax2d_ref(ur, vr, buf.ins_x, buf.ins_y,
                                      buf.ins_w)
        approx = jnp.maximum(static, ins)
        neg = sp.agg == "min2d"
        if eps_rel is None:
            out = -approx if neg else approx
            return out, out, jnp.zeros(out.shape, bool)
        ok = approx >= sp.delta * (1.0 + 1.0 / eps_rel)
        truth = jnp.maximum(_truth_dommax2d(sp, ur, vr), ins)
        ans = jnp.where(ok, approx, truth)
        if neg:
            ans, approx = -ans, -approx
        return ans, approx, ~ok

    return shard_map(body, mesh=mesh,
                     in_specs=(_plan2d_inspec(sp), P(), P(), P()),
                     out_specs=(P(), P(), P()))(sp, buf, u, v)


class ShardedEngine2D:
    """Executes 2-key queries against z-range-partitioned leaf tables.

    ``shard(plan)`` partitions (and caches) an ``IndexPlan2D``; at
    ``nshards >= 2`` the query methods accept either the raw plan or a
    prepared ``ShardedPlan2D``; ``nshards=1`` routes through the
    single-device executors (that is what keeps S=1 bit-identical to the
    engine), so it requires the unsharded plan.  Passing ``buf=`` a live ``DeltaBuffer2D``
    (e.g. a ``DynamicEngine2D`` snapshot's buffer) folds buffered updates
    in exactly — the buffer is replicated, so dynamic answers stay
    bit-identical to the single-device xla path.
    """

    def __init__(self, nshards: int, *, mesh: Optional[Mesh] = None,
                 min_bucket: int = 64, interpret: Optional[bool] = None):
        check_pow2("nshards", nshards)
        check_pow2("min_bucket", min_bucket)
        self.nshards = nshards
        self.mesh = mesh if mesh is not None else make_shard_mesh(nshards)
        self.min_bucket = min_bucket
        self.interpret = resolve_interpret(interpret)
        self._plan_cache: dict = {}

    def shard(self, plan) -> ShardedPlan2D:
        if isinstance(plan, ShardedPlan2D):
            return plan
        if hasattr(plan, "levels") or isinstance(plan, ShardedLsmPlan2D):
            return _lsm_cache_shard(self, plan, shard_lsm_plan_2d)
        hit = self._plan_cache.get(id(plan))
        if hit is None or hit[0] is not plan:
            sp = shard_plan_2d(plan, self.nshards)
            sp = _on_mesh(sp, self.mesh, _plan2d_inspec(sp))
            self._plan_cache = {id(plan): (plan, sp)}
            hit = self._plan_cache[id(plan)]
        return hit[1]

    def _prepare(self, qs, fills):
        qs = [jnp.asarray(q) for q in qs]
        n = qs[0].shape[0]
        size = _bucket_size(n, self.min_bucket)
        return [_pad_bucket(q, size, f) for q, f in zip(qs, fills)], n

    @staticmethod
    def _require_unsharded(plan) -> None:
        if not isinstance(plan, IndexPlan2D):
            raise ValueError(
                "nshards=1 runs the single-device executors (that is what "
                "keeps S=1 bit-identical) and needs the unsharded "
                "IndexPlan2D, not a pre-partitioned ShardedPlan2D")

    def _rect(self, plan, lx, ux, ly, uy, eps_rel, buf, want_agg):
        sp = self.shard(plan)
        assert sp.agg in want_agg, sp.agg
        if eps_rel is not None and sp.ref_xs is None:
            raise ValueError("Q_rel refinement requires a plan built with "
                             "with_exact=True")
        x0, _, y0, _ = sp.root
        args, n = self._prepare((lx, ux, ly, uy), (x0, x0, y0, y0))
        if self.nshards == 1:
            # S = 1 *is* the single-device path: run its executor directly
            # (inside shard_map, XLA elides the psum and fuses the body
            # differently, costing a final ulp of bit-identity)
            self._require_unsharded(plan)
            bq = min(64, args[0].shape[0])
            if buf is None:
                out = _exec_rect2d(plan, *args, backend="xla",
                                   eps_rel=eps_rel, interpret=self.interpret,
                                   bq=bq)
            else:
                dyn_exec = (_exec_dyn_sum2d if sp.agg == "sum2d"
                            else _exec_dyn_count2d)
                out = dyn_exec(plan, buf, *args, backend="xla",
                               eps_rel=eps_rel, interpret=self.interpret,
                               bq=bq)
        elif buf is None:
            out = _exec_shard_rect2d(sp, *args, mesh=self.mesh,
                                     eps_rel=eps_rel)
        else:
            out = _exec_shard_dyn_rect2d(sp, buf, *args, mesh=self.mesh,
                                         eps_rel=eps_rel)
        return QueryResult(out[0][:n], out[1][:n], out[2][:n])

    def count2d(self, plan, lx, ux, ly, uy,
                eps_rel: Optional[float] = None,
                buf: Optional[DeltaBuffer2D] = None) -> QueryResult:
        return self._rect(plan, lx, ux, ly, uy, eps_rel, buf, ("count2d",))

    def sum2d(self, plan, lx, ux, ly, uy,
              eps_rel: Optional[float] = None,
              buf: Optional[DeltaBuffer2D] = None) -> QueryResult:
        return self._rect(plan, lx, ux, ly, uy, eps_rel, buf, ("sum2d",))

    def extremum2d(self, plan, u, v, eps_rel: Optional[float] = None,
                   buf: Optional[DeltaBuffer2D] = None) -> QueryResult:
        sp = self.shard(plan)
        assert sp.agg in ("max2d", "min2d"), sp.agg
        if eps_rel is not None and sp.ref_wpmax is None:
            raise ValueError("Q_rel refinement requires a plan built with "
                             "with_exact=True")
        x0, _, y0, _ = sp.root
        args, n = self._prepare((u, v), (x0, y0))
        if self.nshards == 1:
            self._require_unsharded(plan)
            bq = min(64, args[0].shape[0])
            if buf is None:
                out = _exec_extremum2d(plan, *args, backend="xla",
                                       eps_rel=eps_rel,
                                       interpret=self.interpret, bq=bq)
            else:
                out = _exec_dyn_dommax2d(plan, buf, *args, backend="xla",
                                         eps_rel=eps_rel,
                                         interpret=self.interpret, bq=bq)
        elif buf is None:
            out = _exec_shard_dommax2d(sp, *args, mesh=self.mesh,
                                       eps_rel=eps_rel)
        else:
            out = _exec_shard_dyn_dommax2d(sp, buf, *args, mesh=self.mesh,
                                           eps_rel=eps_rel)
        return QueryResult(out[0][:n], out[1][:n], out[2][:n])

    def query(self, plan, *ranges, eps_rel: Optional[float] = None,
              buf: Optional[DeltaBuffer2D] = None) -> QueryResult:
        if hasattr(plan, "levels"):
            return self.query_lsm(plan, *ranges, eps_rel=eps_rel, buf=buf)
        agg = plan.agg
        if agg == "count2d":
            return self.count2d(plan, *ranges, eps_rel=eps_rel, buf=buf)
        if agg == "sum2d":
            return self.sum2d(plan, *ranges, eps_rel=eps_rel, buf=buf)
        return self.extremum2d(plan, *ranges, eps_rel=eps_rel, buf=buf)

    def query_lsm(self, lsm, *ranges, eps_rel: Optional[float] = None,
                  buf: Optional[DeltaBuffer2D] = None) -> QueryResult:
        slsm = _lsm_cache_shard(self, lsm, shard_lsm_plan_2d)
        return execute_lsm_sharded(slsm, buf, ranges, mesh=self.mesh,
                                   eps_rel=eps_rel,
                                   min_bucket=self.min_bucket,
                                   interpret=self.interpret)


# ---------------------------------------------------------------------------
# LSM ladders: each immutable level's data plan sharded independently
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedLsmPlan:
    """A 1-D level ladder with every level's fitted ``IndexPlan`` sharded.

    ``levels`` keeps the original (replicated) ``LsmLevel`` tuple: the
    exact side arrays — tombstone prefix sums, victim keys, live sparse
    tables, refinement keys — stay whole on every device, matching the
    documented 2-D sharding simplification (refinement arrays do not
    split at arbitrary cuts).  Only the per-level segment-table
    evaluation is distributed; the exact boundary corrections and the
    cross-level fusion run replicated, so fused answers reproduce the
    unsharded ``execute_lsm(backend='xla')`` bits."""

    agg: str
    nshards: int
    levels: tuple          # original LsmLevel tuple (replicated)
    slevels: tuple         # per-level ShardedPlan, same order

    @property
    def dtype(self):
        return self.levels[0].plan.dtype

    @property
    def deltas(self) -> Tuple[float, ...]:
        return tuple(lvl.plan.delta for lvl in self.levels)


@dataclasses.dataclass(frozen=True)
class ShardedLsmPlan2D:
    """2-D counterpart of ``ShardedLsmPlan`` (z-range-sharded leaf tables
    per level, replicated merge-sort-tree side arrays)."""

    agg: str
    nshards: int
    levels: tuple          # original LsmLevel2D tuple (replicated)
    slevels: tuple         # per-level ShardedPlan2D, same order

    @property
    def dtype(self):
        return self.levels[0].plan.dtype

    @property
    def deltas(self) -> Tuple[float, ...]:
        return tuple(lvl.plan.delta for lvl in self.levels)


def shard_lsm_plan(lsm, nshards: int) -> ShardedLsmPlan:
    """Shard every level of an ``LsmPlan`` (1-D) into ``nshards`` key
    ranges.  Levels are partitioned independently — a compaction that
    rebuilds one slot re-shards only that level's fresh plan."""
    return ShardedLsmPlan(
        agg=lsm.agg, nshards=nshards, levels=tuple(lsm.levels),
        slevels=tuple(shard_plan(l.plan, nshards) for l in lsm.levels))


def shard_lsm_plan_2d(lsm, nshards: int) -> ShardedLsmPlan2D:
    """Shard every level of an ``LsmPlan2D`` into ``nshards`` z-ranges."""
    return ShardedLsmPlan2D(
        agg=lsm.agg, nshards=nshards, levels=tuple(lsm.levels),
        slevels=tuple(shard_plan_2d(l.plan, nshards) for l in lsm.levels))


def _lsm_cache_shard(engine, lsm, shard_fn):
    """Single-entry per-engine ladder cache keyed on ladder identity."""
    if isinstance(lsm, (ShardedLsmPlan, ShardedLsmPlan2D)):
        return lsm
    cache = getattr(engine, "_lsm_cache", None)
    if cache is None or cache[0] is not lsm:
        engine._lsm_cache = (lsm, shard_fn(lsm, engine.nshards))
        cache = engine._lsm_cache
    return cache[1]


@partial(jax.jit, static_argnames=("mesh",))
def _exec_shard_eval2d(sp: ShardedPlan2D, qx, qy, *, mesh: Mesh):
    """Sharded single-corner CF evaluation (the owner-gather + deferred
    Horner of ``_corner_eval2d_shard``, exposed standalone so the LSM
    level cores can apply their own boundary corrections per corner)."""
    def body(sp, qx, qy):
        return (_corner_eval2d_shard(sp, qx, qy),)

    return shard_map(body, mesh=mesh,
                     in_specs=(_plan2d_inspec(sp), P(), P()),
                     out_specs=(P(),))(sp, qx, qy)[0]


def _lsm_level_sum_sharded(lvl, sp, qs, mesh):
    """Sharded twin of ``lsm._level_sum`` — the raw range sum runs on the
    owner shards; the m0 below-domain addend and the exact tombstone
    subtraction are replicated (same floats as the unsharded core)."""
    from .lsm import _tomb_sum_1d
    lq, uq = qs
    part = _exec_shard_sum(sp, lq, uq, mesh=mesh, eps_rel=None)[0]
    p = lvl.plan
    lo = p.seg_lo[0]
    part = part + jnp.where((lq < lo) & (uq >= lo), p.ref_cf[0],
                            jnp.zeros((), p.dtype))
    if lvl.tomb_keys is not None:
        part = part - _tomb_sum_1d(lvl, lq, uq)
    return (part,)


def _lsm_level_extremum_sharded(lvl, sp, qs, mesh):
    """Sharded twin of ``lsm._level_extremum``: the fitted staircase max
    reduces through per-shard sparse tables + pmax; the exact live
    maximum and the victim threat test read the replicated level arrays."""
    lq, uq = qs
    p = lvl.plan
    lo = p.seg_lo[0]
    hi = p.seg_hi[p.h - 1]
    lqc = jnp.clip(lq, lo, hi)
    uqc = jnp.clip(uq, lo, hi)
    out = _exec_shard_extremum(sp, lqc, uqc, mesh=mesh, eps_rel=None)[0]
    raw = -out if p.agg == "min" else out   # back to MAX space
    st = lvl.live_st if lvl.live_st is not None else p.ref_st
    i = jnp.searchsorted(p.ref_keys, lq, side="left")
    j = jnp.searchsorted(p.ref_keys, uq, side="right")
    exact = sparse_table_range_max(st, i, j)
    valid = (uq >= lo) & (lq <= hi) & (exact > -jnp.inf)
    part = jnp.where(valid, raw, -jnp.inf)
    if lvl.vic_keys is not None:
        vk = lvl.vic_keys[None, :]
        threat = jnp.any((lq[:, None] <= vk) & (vk <= uq[:, None]), axis=1)
    else:
        threat = jnp.zeros(lq.shape, bool)
    return part, exact, threat


def _lsm_level_rect_sharded(lvl, sp, qs, mesh):
    """Sharded twin of ``lsm._level_rect``: each clamped corner is one
    owner-gathered sharded evaluation; the below-root corner corrections
    reuse the *same* corner values (as the flat core reuses
    ``raw_eval2d``), and tombstones subtract replicated."""
    from .lsm import _tomb_rect_2d
    lx, ux, ly, uy = qs
    p = lvl.plan
    x0, x1, y0, y1 = p.root
    lxc, uxc = (jnp.clip(q, x0, x1) for q in (lx, ux))
    lyc, uyc = (jnp.clip(q, y0, y1) for q in (ly, uy))
    ev = lambda a, b: _exec_shard_eval2d(sp, a, b, mesh=mesh)
    v = (ev(uxc, uyc), ev(lxc, uyc), ev(uxc, lyc), ev(lxc, lyc))
    part = v[0] - v[1] - v[2] + v[3]
    zero = jnp.zeros((), p.dtype)
    for a, b, e, s in ((ux, uy, v[0], 1.0), (lx, uy, v[1], -1.0),
                       (ux, ly, v[2], -1.0), (lx, ly, v[3], 1.0)):
        part = part + jnp.where((a < x0) | (b < y0), -s * e, zero)
    if lvl.tomb_xs is not None:
        part = part - _tomb_rect_2d(lvl, lx, ux, ly, uy, p.dtype)
    return (part,)


def _lsm_level_dommax_sharded(lvl, sp, qs, mesh):
    """Sharded twin of ``lsm._level_dommax``."""
    from ..core.index2d import mst_dommax
    u, v = qs
    p = lvl.plan
    x0, x1, y0, y1 = p.root
    out = _exec_shard_dommax2d(sp, u, v, mesh=mesh, eps_rel=None)[0]
    raw = -out if p.agg == "min2d" else out   # back to MAX space
    wp = lvl.live_wpmax if lvl.live_wpmax is not None else p.ref_wpmax
    exact = mst_dommax(p.ref_xs, p.ref_ys_levels, wp, u, v).astype(p.dtype)
    valid = (u >= x0) & (v >= y0) & (exact > -jnp.inf)
    part = jnp.where(valid, raw, -jnp.inf)
    if lvl.vic_x is not None:
        threat = jnp.any((lvl.vic_x[None, :] <= u[:, None])
                         & (lvl.vic_y[None, :] <= v[:, None]), axis=1)
    else:
        threat = jnp.zeros(u.shape, bool)
    return part, exact, threat


_LSM_SHARD_CORES = {
    "sum": _lsm_level_sum_sharded, "count": _lsm_level_sum_sharded,
    "max": _lsm_level_extremum_sharded, "min": _lsm_level_extremum_sharded,
    "count2d": _lsm_level_rect_sharded, "sum2d": _lsm_level_rect_sharded,
    "max2d": _lsm_level_dommax_sharded, "min2d": _lsm_level_dommax_sharded,
}


def execute_lsm_sharded(slsm, buf, ranges, *, mesh: Mesh, eps_rel=None,
                        min_bucket: int = 64,
                        interpret: Optional[bool] = None) -> QueryResult:
    """Fuse a query batch across a sharded level ladder (Q_abs only).

    Per-level raw evaluations run sharded; the exact corrections and the
    cross-level combiner (``lsm.combine_levels`` with ``backend='xla'``)
    run replicated, so answers are bit-identical to the unsharded
    ``execute_lsm(..., backend='xla', eps_rel=None)``.  Q_rel refinement
    would need the per-level refinement arrays partitioned (they are
    replicated here) — query the unsharded ladder for that."""
    if eps_rel is not None:
        raise ValueError(
            "sharded LSM execution is Q_abs-only (host-composed per-level "
            "fusion over replicated exact arrays); pass eps_rel=None or "
            "query the unsharded ladder")
    from .engine import pad_fills
    from .lsm import combine_levels, composed_bound
    check_pow2("min_bucket", min_bucket)
    agg = slsm.agg
    dt = slsm.dtype
    qs = [jnp.asarray(q).astype(dt) for q in ranges]
    n = qs[0].shape[0]
    size = _bucket_size(n, min_bucket)
    fills = pad_fills(slsm.levels[0].plan)
    qs = [_pad_bucket(q, size, jnp.asarray(f, dt))
          for q, f in zip(qs, fills)]
    core = _LSM_SHARD_CORES[agg]
    outs = [core(lvl, sp, qs, mesh)
            for lvl, sp in zip(slsm.levels, slsm.slevels)]
    bound = composed_bound(agg, slsm.deltas)
    ans, approx, refined = combine_levels(
        agg, outs, buf, qs, backend="xla", eps_rel=None, interpret=interpret,
        bq=min(64, size), bound=bound)
    return QueryResult(ans[:n], approx[:n], refined[:n])
