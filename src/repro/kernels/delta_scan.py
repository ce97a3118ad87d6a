"""Pallas TPU kernels: exact delta-buffer scans for dynamic plans.

A ``DynamicEngine`` (engine/dynamic.py) buffers inserts/deletes in fixed-
capacity, sentinel-padded device arrays between merges.  Queries fuse the
static plan's approximation with an *exact* correction over the buffer, so
the certified error bounds survive updates: the only approximation error
left is the static plan's own E(I) <= delta.

All three kernels reuse the one-hot membership matmul pattern of
``poly_eval.py``/``range_sum.py`` — membership of each buffered key in each
query range is a (BQ, BD) compare tile, turned into a gathered reduction on
the MXU (SUM/COUNT) or a masked VPU max (MAX/MIN), accumulated across
buffer tiles in VMEM scratch:

* ``delta_sum_pallas``     — sum of buffered measures with key in (lq, uq]
                             (the CF-difference range of Eq. 5);
* ``delta_max_pallas``     — max of buffered measures with key in [lq, uq]
                             (MAX range semantics; -inf on empty);
* ``delta_count2d_pallas`` — count of buffered points in the half-open
                             rectangle (lx, ux] x (ly, uy] (Eq. 19).

Empty buffer slots hold a huge-but-finite sentinel key (``plan.big_sentinel``)
so they fail every membership test without needing a separate count input —
the kernels are oblivious to the fill level.

The ``*_gather_pallas`` variants are the O(Q*log D) locate->gather rewrites
(DESIGN.md §10) the engine's ``pallas`` backend uses (the scans above stay
available as ``pallas_scan``).  They exploit structure the buffers already
maintain on append (engine/dynamic.py):

* SUM — the log is sorted, so an exclusive prefix-sum array turns the
  correction into two binary searches and a subtraction;
* MAX — a sparse table over the sorted log answers the located span in
  O(1) (two gathers), exactly like interior segments in range_max;
* 2-D COUNT — per-level block-sorted y arrays (the merge-sort-tree layout
  of ``core.index2d``) answer each corner's dominance count in O(log^2 D).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.index2d import mst_count_prefix, mst_weighted_prefix
from .locate import bsearch_count, rmq_gather
from .poly_eval import DEFAULT_BH, DEFAULT_BQ, resolve_interpret

__all__ = ["delta_sum_pallas", "delta_max_pallas", "delta_count2d_pallas",
           "delta_sum_gather_pallas", "delta_max_gather_pallas",
           "delta_count2d_gather_pallas", "delta_sum2d_pallas",
           "delta_sum2d_gather_pallas", "delta_dommax2d_pallas",
           "delta_dommax2d_gather_pallas"]


def _delta_sum_kernel(lq_ref, uq_ref, k_ref, v_ref, out_ref, acc,
                      *, n_tiles: int):
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    lq = lq_ref[...]
    uq = uq_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    # (BQ, BD) membership in (lq, uq]; sentinel-padded slots never match
    member = ((lq[:, None] < k[None, :]) &
              (k[None, :] <= uq[:, None])).astype(v.dtype)
    acc[...] += jnp.dot(member, v, preferred_element_type=v.dtype)

    @pl.when(d == n_tiles - 1)
    def _finalize():
        out_ref[...] = acc[...]


def delta_sum_pallas(lq, uq, keys, vals, bq: int = DEFAULT_BQ,
                     bd: int = DEFAULT_BH, interpret: Optional[bool] = None):
    """Exact sum of buffered measures with key in (lq, uq] per query."""
    Q, D = lq.shape[0], keys.shape[0]
    bd = min(bd, D)
    assert Q % bq == 0 and D % bd == 0, (Q, D, bq, bd)
    n_tiles = D // bd
    kernel = functools.partial(_delta_sum_kernel, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), vals.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), vals.dtype)],
        interpret=resolve_interpret(interpret),
    )(lq, uq, keys, vals)


def _delta_sum_gather_kernel(lq_ref, uq_ref, k_ref, cf_ref, out_ref):
    k = k_ref[...]
    cf = cf_ref[...]
    # membership (lq, uq]: prefix sums at the "# keys <= q" counts
    cu = bsearch_count(k, uq_ref[...], side="right")
    cl = bsearch_count(k, lq_ref[...], side="right")
    out_ref[...] = jnp.take(cf, cu) - jnp.take(cf, cl)


def delta_sum_gather_pallas(lq, uq, keys, cf, bq: int = DEFAULT_BQ,
                            interpret: Optional[bool] = None):
    """Exact sum of buffered measures with key in (lq, uq] via the buffer's
    exclusive prefix-sum array ``cf`` ((D+1,), cf[i] = sum(vals[:i]),
    maintained on append): two O(log D) binary searches + a subtraction."""
    Q, D = lq.shape[0], keys.shape[0]
    assert Q % bq == 0 and cf.shape[0] == D + 1, (Q, bq, cf.shape, D)
    return pl.pallas_call(
        _delta_sum_gather_kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((D + 1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), cf.dtype),
        interpret=resolve_interpret(interpret),
    )(lq, uq, keys, cf)


def _delta_max_kernel(lq_ref, uq_ref, k_ref, v_ref, out_ref, acc,
                      *, n_tiles: int):
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        acc[...] = jnp.full_like(acc, -jnp.inf)

    lq = lq_ref[...]
    uq = uq_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    member = (lq[:, None] <= k[None, :]) & (k[None, :] <= uq[:, None])
    tile_max = jnp.max(jnp.where(member, v[None, :], -jnp.inf), axis=1)
    acc[...] = jnp.maximum(acc[...], tile_max)

    @pl.when(d == n_tiles - 1)
    def _finalize():
        out_ref[...] = acc[...]


def delta_max_pallas(lq, uq, keys, vals, bq: int = DEFAULT_BQ,
                     bd: int = DEFAULT_BH, interpret: Optional[bool] = None):
    """Exact max of buffered measures with key in [lq, uq] (-inf if none)."""
    Q, D = lq.shape[0], keys.shape[0]
    bd = min(bd, D)
    assert Q % bq == 0 and D % bd == 0, (Q, D, bq, bd)
    n_tiles = D // bd
    kernel = functools.partial(_delta_max_kernel, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), vals.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), vals.dtype)],
        interpret=resolve_interpret(interpret),
    )(lq, uq, keys, vals)


def _delta_max_gather_kernel(lq_ref, uq_ref, k_ref, st_ref, out_ref):
    k = k_ref[...]
    # membership [lq, uq]: the sorted log's covered span is [i0, i1)
    i0 = bsearch_count(k, lq_ref[...], side="left")
    i1 = bsearch_count(k, uq_ref[...], side="right")
    out_ref[...] = rmq_gather(st_ref[...], i0, i1)


def delta_max_gather_pallas(lq, uq, keys, st, bq: int = DEFAULT_BQ,
                            interpret: Optional[bool] = None):
    """Exact max of buffered measures with key in [lq, uq] (-inf if none):
    locate the sorted log's covered span, then an O(1) two-gather RMQ
    against the buffer's sparse table (rebuilt on append)."""
    Q, D = lq.shape[0], keys.shape[0]
    assert Q % bq == 0 and st.shape[1] == D, (Q, bq, st.shape, D)
    levels = st.shape[0]
    return pl.pallas_call(
        _delta_max_gather_kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((levels, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), st.dtype),
        interpret=resolve_interpret(interpret),
    )(lq, uq, keys, st)


def _delta_count2d_kernel(lx_ref, ux_ref, ly_ref, uy_ref, kx_ref, ky_ref,
                          out_ref, acc, *, n_tiles: int):
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    lx = lx_ref[...]
    ux = ux_ref[...]
    ly = ly_ref[...]
    uy = uy_ref[...]
    kx = kx_ref[...]
    ky = ky_ref[...]
    member = ((lx[:, None] < kx[None, :]) & (kx[None, :] <= ux[:, None]) &
              (ly[:, None] < ky[None, :]) & (ky[None, :] <= uy[:, None])
              ).astype(acc.dtype)
    ones = jnp.ones((member.shape[1],), acc.dtype)
    acc[...] += jnp.dot(member, ones, preferred_element_type=acc.dtype)

    @pl.when(d == n_tiles - 1)
    def _finalize():
        out_ref[...] = acc[...]


def delta_count2d_pallas(lx, ux, ly, uy, keys_x, keys_y,
                         bq: int = DEFAULT_BQ, bd: int = DEFAULT_BH,
                         interpret: Optional[bool] = None, dtype=None):
    """Exact count of buffered points in (lx, ux] x (ly, uy] per query."""
    Q, D = lx.shape[0], keys_x.shape[0]
    bd = min(bd, D)
    assert Q % bq == 0 and D % bd == 0, (Q, D, bq, bd)
    dtype = dtype or keys_x.dtype
    n_tiles = D // bd
    kernel = functools.partial(_delta_count2d_kernel, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), dtype),
        scratch_shapes=[pltpu.VMEM((bq,), dtype)],
        interpret=resolve_interpret(interpret),
    )(lx, ux, ly, uy, keys_x, keys_y)


def _delta_count2d_gather_kernel(lx_ref, ux_ref, ly_ref, uy_ref,
                                 kx_ref, ylv_ref, out_ref, *, dtype):
    kx = kx_ref[...]
    ylv = ylv_ref[...]

    def cf(x, y):
        # dominance count #(px <= x & py <= y): x-prefix by binary search,
        # then the merge-sort-tree prefix count (same op sequence as the
        # exact-refinement path in core.index2d)
        i = bsearch_count(kx, x, side="right")
        return mst_count_prefix(kx, ylv, i, y).astype(dtype)

    lx, ux, ly, uy = lx_ref[...], ux_ref[...], ly_ref[...], uy_ref[...]
    out_ref[...] = cf(ux, uy) - cf(lx, uy) - cf(ux, ly) + cf(lx, ly)


def delta_count2d_gather_pallas(lx, ux, ly, uy, keys_x, ys_levels,
                                bq: int = DEFAULT_BQ,
                                interpret: Optional[bool] = None,
                                dtype=None):
    """Exact count of buffered points in (lx, ux] x (ly, uy] per query in
    O(log^2 D): the buffer is x-sorted and ``ys_levels`` ((L, D), level l =
    y values sorted within blocks of 2^l, rebuilt on append) decomposes any
    x-prefix into <= L sorted blocks, each answered by a binary search —
    the merge-sort-tree scheme of core.index2d applied to the delta log."""
    Q, D = lx.shape[0], keys_x.shape[0]
    assert Q % bq == 0 and ys_levels.shape[1] == D, (Q, bq, ys_levels.shape)
    dtype = dtype or keys_x.dtype
    levels = ys_levels.shape[0]
    kernel = functools.partial(_delta_count2d_gather_kernel, dtype=dtype)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((levels, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), dtype),
        interpret=resolve_interpret(interpret),
    )(lx, ux, ly, uy, keys_x, ys_levels)


def _delta_sum2d_kernel(lx_ref, ux_ref, ly_ref, uy_ref, kx_ref, ky_ref,
                        w_ref, out_ref, acc, *, n_tiles: int):
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    lx = lx_ref[...]
    ux = ux_ref[...]
    ly = ly_ref[...]
    uy = uy_ref[...]
    kx = kx_ref[...]
    ky = ky_ref[...]
    w = w_ref[...]
    member = ((lx[:, None] < kx[None, :]) & (kx[None, :] <= ux[:, None]) &
              (ly[:, None] < ky[None, :]) & (ky[None, :] <= uy[:, None])
              ).astype(w.dtype)
    acc[...] += jnp.dot(member, w, preferred_element_type=w.dtype)

    @pl.when(d == n_tiles - 1)
    def _finalize():
        out_ref[...] = acc[...]


def delta_sum2d_pallas(lx, ux, ly, uy, keys_x, keys_y, wv,
                       bq: int = DEFAULT_BQ, bd: int = DEFAULT_BH,
                       interpret: Optional[bool] = None):
    """Exact sum of buffered measures over points in (lx, ux] x (ly, uy]
    per query (the weighted twin of ``delta_count2d_pallas``)."""
    Q, D = lx.shape[0], keys_x.shape[0]
    bd = min(bd, D)
    assert Q % bq == 0 and D % bd == 0, (Q, D, bq, bd)
    n_tiles = D // bd
    kernel = functools.partial(_delta_sum2d_kernel, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), wv.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), wv.dtype)],
        interpret=resolve_interpret(interpret),
    )(lx, ux, ly, uy, keys_x, keys_y, wv)


def _delta_sum2d_gather_kernel(lx_ref, ux_ref, ly_ref, uy_ref,
                               kx_ref, ylv_ref, wcum_ref, out_ref):
    kx = kx_ref[...]
    ylv = ylv_ref[...]
    wcum = wcum_ref[...]

    def cf(x, y):
        i = bsearch_count(kx, x, side="right")
        return mst_weighted_prefix(kx, ylv, wcum, i, y, mode="sum")

    lx, ux, ly, uy = lx_ref[...], ux_ref[...], ly_ref[...], uy_ref[...]
    out_ref[...] = cf(ux, uy) - cf(lx, uy) - cf(ux, ly) + cf(lx, ly)


def delta_sum2d_gather_pallas(lx, ux, ly, uy, keys_x, ys_levels, wcum_levels,
                              bq: int = DEFAULT_BQ,
                              interpret: Optional[bool] = None):
    """Exact sum of buffered measures over (lx, ux] x (ly, uy] in
    O(log^2 D): the weighted merge-sort-tree correction — per-level
    block-sorted y arrays plus per-block inclusive weight prefix sums,
    both rebuilt on append (engine/dynamic.py)."""
    Q, D = lx.shape[0], keys_x.shape[0]
    assert Q % bq == 0 and ys_levels.shape[1] == D, (Q, bq, ys_levels.shape)
    levels = ys_levels.shape[0]
    return pl.pallas_call(
        _delta_sum2d_gather_kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((levels, D), lambda i: (0, 0)),
            pl.BlockSpec((levels, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), wcum_levels.dtype),
        interpret=resolve_interpret(interpret),
    )(lx, ux, ly, uy, keys_x, ys_levels, wcum_levels)


def _delta_dommax2d_kernel(u_ref, v_ref, kx_ref, ky_ref, w_ref, out_ref,
                           acc, *, n_tiles: int):
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        acc[...] = jnp.full_like(acc, -jnp.inf)

    u = u_ref[...]
    v = v_ref[...]
    kx = kx_ref[...]
    ky = ky_ref[...]
    w = w_ref[...]
    member = (kx[None, :] <= u[:, None]) & (ky[None, :] <= v[:, None])
    tile_max = jnp.max(jnp.where(member, w[None, :], -jnp.inf), axis=1)
    acc[...] = jnp.maximum(acc[...], tile_max)

    @pl.when(d == n_tiles - 1)
    def _finalize():
        out_ref[...] = acc[...]


def delta_dommax2d_pallas(u, v, keys_x, keys_y, wv, bq: int = DEFAULT_BQ,
                          bd: int = DEFAULT_BH,
                          interpret: Optional[bool] = None):
    """Exact dominance max of buffered measures over {x <= u, y <= v} per
    query corner (-inf if none dominated)."""
    Q, D = u.shape[0], keys_x.shape[0]
    bd = min(bd, D)
    assert Q % bq == 0 and D % bd == 0, (Q, D, bq, bd)
    n_tiles = D // bd
    kernel = functools.partial(_delta_dommax2d_kernel, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), wv.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), wv.dtype)],
        interpret=resolve_interpret(interpret),
    )(u, v, keys_x, keys_y, wv)


def _delta_dommax2d_gather_kernel(u_ref, v_ref, kx_ref, ylv_ref, wpmax_ref,
                                  out_ref):
    kx = kx_ref[...]
    i = bsearch_count(kx, u_ref[...], side="right")
    out_ref[...] = mst_weighted_prefix(kx, ylv_ref[...], wpmax_ref[...], i,
                                       v_ref[...], mode="max")


def delta_dommax2d_gather_pallas(u, v, keys_x, ys_levels, wpmax_levels,
                                 bq: int = DEFAULT_BQ,
                                 interpret: Optional[bool] = None):
    """Exact dominance max over {x <= u, y <= v} in O(log^2 D): the
    merge-sort-tree decomposition with per-block inclusive prefix *maxima*
    instead of prefix sums."""
    Q, D = u.shape[0], keys_x.shape[0]
    assert Q % bq == 0 and ys_levels.shape[1] == D, (Q, bq, ys_levels.shape)
    levels = ys_levels.shape[0]
    return pl.pallas_call(
        _delta_dommax2d_gather_kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((levels, D), lambda i: (0, 0)),
            pl.BlockSpec((levels, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), wpmax_levels.dtype),
        interpret=resolve_interpret(interpret),
    )(u, v, keys_x, ys_levels, wpmax_levels)
