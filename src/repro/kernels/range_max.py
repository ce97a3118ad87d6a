"""Pallas TPU kernels: fused range-MAX query evaluation (Eq. 17).

* ``range_max_gather_pallas`` — locate->gather (DESIGN.md §10, the engine's
  ``pallas`` backend): both boundary segments are located with the
  branch-free binary search of ``locate.py`` (O(log H)), their coefficient
  rows gathered, and the strictly-interior span (il, iu) answered in O(1)
  with two gathers against the plan's per-segment sparse table — the same
  two-window RMQ the XLA backend uses, so no scan over seg_agg remains.
* ``range_max_pallas`` — the original one-hot membership scan (the
  ``pallas_scan`` backend): boundary rows via MXU matmul, interior via a
  dense masked reduction over every resident tile — O(Q*H).

Both compute boundary extrema with ``core.poly.clipped_poly_max``
(closed-form zero-derivative points, deg <= 3 — the paper's recommended
MAX degree; higher degrees use the XLA path in core.queries), and MIN is
served on negated aggregates, so answers are bit-identical across paths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.poly import clipped_poly_max
from .locate import locate_segments, rmq_gather
from .poly_eval import DEFAULT_BH, DEFAULT_BQ, resolve_interpret

__all__ = ["range_max_pallas", "range_max_gather_pallas"]

_NEG = -jnp.inf


def _range_max_gather_kernel(lq_ref, uq_ref, lo_ref, hi_ref, coef_ref,
                             st_ref, out_ref):
    lq = lq_ref[...]
    uq = uq_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]
    coef = coef_ref[...]
    il = locate_segments(lo, lq)
    iu = locate_segments(lo, uq)
    lo_l, hi_l = jnp.take(lo, il), jnp.take(hi, il)
    lo_u, hi_u = jnp.take(lo, iu), jnp.take(hi, iu)
    cl = jnp.take(coef, il, axis=0)
    cu = jnp.take(coef, iu, axis=0)
    same = il == iu
    # left boundary: [lq, min(hi_l, uq)], suppressed when lq past hi_l
    m_left = clipped_poly_max(cl, lo_l, hi_l, lq, jnp.minimum(hi_l, uq))
    m_left = jnp.where(lq <= hi_l, m_left, _NEG)
    # right boundary: [max(lo_u, lq), uq], suppressed when same segment
    m_right = clipped_poly_max(cu, lo_u, hi_u, jnp.maximum(lo_u, lq), uq)
    m_right = jnp.where(same, _NEG, m_right)
    # interior segments are exactly (il, iu): seg_lo[j] > lq <=> j > il and
    # seg_next[j] <= uq <=> j < iu — an O(1) sparse-table range max
    m_int = rmq_gather(st_ref[...], il + 1, iu)
    out_ref[...] = jnp.maximum(jnp.maximum(m_left, m_right), m_int)


def range_max_gather_pallas(lq, uq, seg_lo, seg_hi, coeffs, st,
                            bq: int = DEFAULT_BQ,
                            interpret: Optional[bool] = None):
    """Locate->gather range MAX; ``st`` is the plan's (L, h) sparse table
    over per-segment aggregates (unpadded — in-domain queries never locate
    the sentinel tail)."""
    Q, H = lq.shape[0], seg_lo.shape[0]
    assert Q % bq == 0, (Q, bq)
    deg = coeffs.shape[1] - 1
    assert deg <= 3, "in-kernel closed forms cover deg<=3 (paper's MAX range)"
    # monotone cast: per-entry rounding commutes with max, so an f32 table
    # sees exactly the f32 per-segment aggregates the one-hot path scans
    st = st.astype(coeffs.dtype)
    levels, h = st.shape
    return pl.pallas_call(
        _range_max_gather_kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((H,), lambda i: (0,)),
            pl.BlockSpec((H,), lambda i: (0,)),
            pl.BlockSpec((H, deg + 1), lambda i: (0, 0)),
            pl.BlockSpec((levels, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        interpret=resolve_interpret(interpret),
    )(lq, uq, seg_lo, seg_hi, coeffs, st)


def _range_max_kernel(lq_ref, uq_ref, lo_ref, nxt_ref, hi_ref, coef_ref,
                      agg_ref, out_ref, acc, acc_int, *, n_tiles: int, deg: int):
    """acc: (BQ, 2*(deg+3)) boundary gather; acc_int: (BQ,) interior max."""
    h = pl.program_id(1)
    ncol = deg + 3

    @pl.when(h == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        acc_int[...] = jnp.full_like(acc_int, _NEG)

    lq = lq_ref[...]
    uq = uq_ref[...]
    lo = lo_ref[...]
    nxt = nxt_ref[...]
    hi = hi_ref[...]
    coef = coef_ref[...]
    agg = agg_ref[...]
    table = jnp.concatenate([coef, lo[:, None], hi[:, None]], axis=1)

    for slot, q in ((0, lq), (1, uq)):
        one_hot = ((lo[None, :] <= q[:, None]) &
                   (q[:, None] < nxt[None, :])).astype(coef.dtype)
        acc[:, slot * ncol:(slot + 1) * ncol] += jnp.dot(
            one_hot, table, preferred_element_type=coef.dtype)

    # interior: strictly between the two boundary segments
    interior = ((lo[None, :] > lq[:, None]) &
                (nxt[None, :] <= uq[:, None]))                    # (BQ, BH)
    tile_max = jnp.max(jnp.where(interior, agg[None, :], _NEG), axis=1)
    acc_int[...] = jnp.maximum(acc_int[...], tile_max)

    @pl.when(h == n_tiles - 1)
    def _finalize():
        cl = acc[:, 0:deg + 1]
        slo_l = acc[:, deg + 1]
        shi_l = acc[:, deg + 2]
        cu = acc[:, ncol:ncol + deg + 1]
        slo_u = acc[:, ncol + deg + 1]
        shi_u = acc[:, ncol + deg + 2]
        same = (slo_l == slo_u) & (shi_l == shi_u)
        # left boundary: [lq, min(hi_l, uq)], suppressed when lq past hi_l
        m_left = clipped_poly_max(cl, slo_l, shi_l, lq, jnp.minimum(shi_l, uq))
        m_left = jnp.where(lq <= shi_l, m_left, _NEG)
        # right boundary: [max(lo_u, lq), uq], suppressed when same segment
        m_right = clipped_poly_max(cu, slo_u, shi_u, jnp.maximum(slo_u, lq), uq)
        m_right = jnp.where(same, _NEG, m_right)
        out_ref[...] = jnp.maximum(jnp.maximum(m_left, m_right), acc_int[...])


def range_max_pallas(lq, uq, seg_lo, seg_next, seg_hi, coeffs, seg_agg,
                     bq: int = DEFAULT_BQ, bh: int = DEFAULT_BH,
                     interpret: Optional[bool] = None):
    Q, H = lq.shape[0], seg_lo.shape[0]
    assert Q % bq == 0 and H % bh == 0, (Q, H, bq, bh)
    deg = coeffs.shape[1] - 1
    assert deg <= 3, "in-kernel closed forms cover deg<=3 (paper's MAX range)"
    n_tiles = H // bh
    kernel = functools.partial(_range_max_kernel, n_tiles=n_tiles, deg=deg)
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
            pl.BlockSpec((bh,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 2 * (deg + 3)), coeffs.dtype),
            pltpu.VMEM((bq,), coeffs.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(lq, uq, seg_lo, seg_next, seg_hi, coeffs, seg_agg)
