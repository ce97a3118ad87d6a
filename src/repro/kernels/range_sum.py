"""Pallas TPU kernels: fused range-SUM/COUNT query evaluation (Eq. 14).

Two implementations of A = P_{I(u)}(u) - P_{I(l)}(l) per (l, u) range:

* ``range_sum_gather_pallas`` — the locate->gather path (DESIGN.md §10,
  the engine's ``pallas`` backend): both endpoints are resolved with the
  branch-free binary search of ``locate.py`` in O(log H) probe rounds,
  then exactly one (deg+1)-coefficient row per endpoint is gathered and
  Horner-evaluated.  Per-query work is independent of the table size.
* ``range_sum_pallas`` — the original one-hot membership scan (the
  ``pallas_scan`` backend, kept for A/B benchmarking): both endpoints'
  one-hot rows are resolved against each resident segment tile with an MXU
  matmul — O(Q*H) work, memory-bound on the table when H is large.

Both paths gather the same rows and share ``core.poly.horner``/
``scale_unit``, so their answers are bit-identical.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.poly import horner, scale_unit
from .locate import locate_segments
from .poly_eval import DEFAULT_BH, DEFAULT_BQ, resolve_interpret

__all__ = ["range_sum_pallas", "range_sum_gather_pallas"]


def _range_sum_gather_kernel(lq_ref, uq_ref, lo_ref, hi_ref, coef_ref,
                             out_ref):
    lo = lo_ref[...]
    hi = hi_ref[...]
    coef = coef_ref[...]
    vals = []
    for q_ref in (lq_ref, uq_ref):
        q = q_ref[...]
        idx = locate_segments(lo, q)                       # O(log H)
        c = jnp.take(coef, idx, axis=0)                    # (BQ, deg+1)
        u = scale_unit(q, jnp.take(lo, idx), jnp.take(hi, idx))
        vals.append(horner(c, u))
    out_ref[...] = vals[1] - vals[0]


def range_sum_gather_pallas(lq, uq, seg_lo, seg_hi, coeffs,
                            bq: int = DEFAULT_BQ,
                            interpret: Optional[bool] = None):
    """Locate->gather range SUM: grid over query blocks only, the whole
    (sentinel-padded) segment table resident per block."""
    Q, H = lq.shape[0], seg_lo.shape[0]
    assert Q % bq == 0, (Q, bq)
    deg = coeffs.shape[1] - 1
    return pl.pallas_call(
        _range_sum_gather_kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((H,), lambda i: (0,)),
            pl.BlockSpec((H,), lambda i: (0,)),
            pl.BlockSpec((H, deg + 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        interpret=resolve_interpret(interpret),
    )(lq, uq, seg_lo, seg_hi, coeffs)


def _range_sum_kernel(lq_ref, uq_ref, lo_ref, nxt_ref, hi_ref, coef_ref,
                      out_ref, acc, *, n_tiles: int, deg: int):
    """acc layout: (BQ, 2*(deg+3)): per endpoint [coef x (deg+1), lo, hi]."""
    h = pl.program_id(1)
    ncol = deg + 3

    @pl.when(h == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    lo = lo_ref[...]
    nxt = nxt_ref[...]
    hi = hi_ref[...]
    coef = coef_ref[...]
    # (BH, deg+3): coeffs | scale-lo | scale-hi — one matmul gathers all
    table = jnp.concatenate([coef, lo[:, None], hi[:, None]], axis=1)

    for slot, q_ref in ((0, lq_ref), (1, uq_ref)):
        q = q_ref[...]
        one_hot = ((lo[None, :] <= q[:, None]) &
                   (q[:, None] < nxt[None, :])).astype(coef.dtype)
        acc[:, slot * ncol:(slot + 1) * ncol] += jnp.dot(
            one_hot, table, preferred_element_type=coef.dtype)

    @pl.when(h == n_tiles - 1)
    def _finalize():
        vals = []
        for slot, q_ref in ((0, lq_ref), (1, uq_ref)):
            q = q_ref[...]
            c = acc[:, slot * ncol:slot * ncol + deg + 1]
            slo = acc[:, slot * ncol + deg + 1]
            shi = acc[:, slot * ncol + deg + 2]
            vals.append(horner(c, scale_unit(q, slo, shi)))
        out_ref[...] = vals[1] - vals[0]


def range_sum_pallas(lq, uq, seg_lo, seg_next, seg_hi, coeffs,
                     bq: int = DEFAULT_BQ, bh: int = DEFAULT_BH,
                     interpret: Optional[bool] = None):
    Q, H = lq.shape[0], seg_lo.shape[0]
    assert Q % bq == 0 and H % bh == 0, (Q, H, bq, bh)
    deg = coeffs.shape[1] - 1
    n_tiles = H // bh
    kernel = functools.partial(_range_sum_kernel, n_tiles=n_tiles, deg=deg)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh, deg + 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 2 * (deg + 3)), coeffs.dtype)],
        interpret=resolve_interpret(interpret),
    )(lq, uq, seg_lo, seg_next, seg_hi, coeffs)
