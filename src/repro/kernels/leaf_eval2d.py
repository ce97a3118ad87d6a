"""Pallas TPU kernel: fused 2-key range-COUNT query evaluation (Eq. 19).

The quadtree descent of ``core.index2d`` is pointer chasing — unvectorizable
on the VPU — so the engine flattens the quadtree's *leaves* into a tile-
padded table and resolves each query corner with the same one-hot membership
trick as the 1-D kernels (DESIGN.md §7): leaves partition the root rectangle,
and membership

    one_hot[q, j] = (mx0[j] <= qx < mx1[j]) & (my0[j] <= qy < my1[j])

is locally decidable per tile.  ``mx1``/``my1`` are the leaf's upper bounds
with right/top root-edge leaves widened to a huge sentinel, reproducing the
descent's tie rule (coordinates exactly on an interior split line belong to
the higher-coordinate leaf; the root's own upper edge stays inside).

All four inclusion-exclusion corners of a COUNT query — (ux,uy), (lx,uy),
(ux,ly), (lx,ly) — are resolved against the same resident leaf tile, so the
leaf table is read once per query block instead of four times.  Finalization
evaluates each corner's bivariate polynomial (Horner in v inside Horner in
u, on the leaf's scaled coordinates) and combines with signs (+,-,-,+).

Grid: (num_query_blocks, num_leaf_tiles), leaf tiles innermost; the
(BQ, 4*(K+4)) gather accumulator lives in VMEM scratch across the inner
loop (K = (deg+1)^2 coefficients + 4 scaling bounds per corner slot).

``corner_count2d_gather_pallas`` is the O(Q*log L) locate->gather rewrite
(the engine's ``pallas`` backend; the one-hot scan above stays available as
``pallas_scan``): leaves are disjoint intervals in Morton (Z-order) space,
so a corner resolves with three branch-free binary searches instead of a
membership scan — see kernels/locate.py and DESIGN.md §10.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .locate import locate_leaf2d
from .poly_eval import DEFAULT_BH, DEFAULT_BQ, resolve_interpret

__all__ = ["corner_count2d_pallas", "corner_count2d_gather_pallas",
           "corner_eval2d_pallas", "corner_eval2d_gather_pallas"]


def _bivariate_horner(qx, qy, c, b, deg: int):
    """P(u(qx), v(qy)) per row from gathered coeff rows c (BQ, (deg+1)^2)
    and scaling bounds b (BQ, 4) — the exact op sequence of the one-hot
    kernel's finalize step, so results are bit-identical."""
    span_x = jnp.where(b[:, 1] > b[:, 0], b[:, 1] - b[:, 0], 1.0)
    span_y = jnp.where(b[:, 3] > b[:, 2], b[:, 3] - b[:, 2], 1.0)
    us = jnp.clip((2.0 * qx - b[:, 0] - b[:, 1]) / span_x, -1.0, 1.0)
    vs = jnp.clip((2.0 * qy - b[:, 2] - b[:, 3]) / span_y, -1.0, 1.0)
    v = jnp.zeros_like(us)
    for i in range(deg, -1, -1):
        inner = jnp.zeros_like(vs)
        for j in range(deg, -1, -1):
            inner = inner * vs + c[:, i * (deg + 1) + j]
        v = v * us + inner
    return v


def _corner_count2d_gather_kernel(lx_ref, ux_ref, ly_ref, uy_ref,
                                  xcuts_ref, ycuts_ref, z_ref,
                                  bounds_ref, coef_ref, out_ref,
                                  *, deg: int, depth: int):
    xcuts = xcuts_ref[...]
    ycuts = ycuts_ref[...]
    z = z_ref[...]
    bounds = bounds_ref[...]
    coef = coef_ref[...]
    corners = ((ux_ref[...], uy_ref[...]), (lx_ref[...], uy_ref[...]),
               (ux_ref[...], ly_ref[...]), (lx_ref[...], ly_ref[...]))
    vals = []
    for qx, qy in corners:
        leaf = locate_leaf2d(qx, qy, xcuts, ycuts, z, depth)   # O(log L)
        c = jnp.take(coef, leaf, axis=0)
        b = jnp.take(bounds, leaf, axis=0)
        vals.append(_bivariate_horner(qx, qy, c, b, deg))
    out_ref[...] = vals[0] - vals[1] - vals[2] + vals[3]


def corner_count2d_gather_pallas(lx, ux, ly, uy, xcuts, ycuts, leaf_z,
                                 bounds, coeffs, deg: int, depth: int,
                                 bq: int = DEFAULT_BQ,
                                 interpret: Optional[bool] = None):
    """Locate->gather 4-corner COUNT (DESIGN.md §10): the quadtree leaves
    are disjoint Morton intervals, so each corner resolves with three
    binary searches (cell x, cell y, leaf z) and one gathered bivariate
    Horner — no scan over the leaf table.  ``leaf_z`` must be sorted
    ascending (the plan stores the whole leaf table in z order) and
    sentinel-padded; corners must be pre-clamped into the root region.
    """
    Q, L = lx.shape[0], leaf_z.shape[0]
    assert Q % bq == 0, (Q, bq)
    k = (deg + 1) * (deg + 1)
    assert coeffs.shape[1] == k, coeffs.shape
    nx, ny = xcuts.shape[0], ycuts.shape[0]
    kernel = functools.partial(_corner_count2d_gather_kernel, deg=deg,
                               depth=depth)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((nx,), lambda i: (0,)),
            pl.BlockSpec((ny,), lambda i: (0,)),
            pl.BlockSpec((L,), lambda i: (0,)),
            pl.BlockSpec((L, 4), lambda i: (0, 0)),
            pl.BlockSpec((L, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        interpret=resolve_interpret(interpret),
    )(lx, ux, ly, uy, xcuts, ycuts, leaf_z, bounds, coeffs)


def _corner_eval2d_gather_kernel(u_ref, v_ref, xcuts_ref, ycuts_ref, z_ref,
                                 bounds_ref, coef_ref, out_ref,
                                 *, deg: int, depth: int):
    u = u_ref[...]
    v = v_ref[...]
    leaf = locate_leaf2d(u, v, xcuts_ref[...], ycuts_ref[...], z_ref[...],
                         depth)
    c = jnp.take(coef_ref[...], leaf, axis=0)
    b = jnp.take(bounds_ref[...], leaf, axis=0)
    out_ref[...] = _bivariate_horner(u, v, c, b, deg)


def corner_eval2d_gather_pallas(u, v, xcuts, ycuts, leaf_z, bounds, coeffs,
                                deg: int, depth: int, bq: int = DEFAULT_BQ,
                                interpret: Optional[bool] = None):
    """Single-corner leaf evaluation P_{leaf(u,v)}(u, v) via locate->gather
    (DESIGN.md §12): three binary searches resolve the corner's leaf in the
    z-sorted table, one gathered bivariate Horner evaluates it.  This is
    the dominance MAX/MIN query kernel — dominance queries touch exactly
    one leaf, so there is no inclusion-exclusion combination step.
    Corners must be pre-clamped into the root region."""
    Q, L = u.shape[0], leaf_z.shape[0]
    assert Q % bq == 0, (Q, bq)
    k = (deg + 1) * (deg + 1)
    assert coeffs.shape[1] == k, coeffs.shape
    nx, ny = xcuts.shape[0], ycuts.shape[0]
    kernel = functools.partial(_corner_eval2d_gather_kernel, deg=deg,
                               depth=depth)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((nx,), lambda i: (0,)),
            pl.BlockSpec((ny,), lambda i: (0,)),
            pl.BlockSpec((L,), lambda i: (0,)),
            pl.BlockSpec((L, 4), lambda i: (0, 0)),
            pl.BlockSpec((L, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        interpret=resolve_interpret(interpret),
    )(u, v, xcuts, ycuts, leaf_z, bounds, coeffs)


def _corner_eval2d_kernel(u_ref, v_ref, mx0_ref, mx1_ref, my0_ref, my1_ref,
                          bounds_ref, coef_ref, out_ref, acc,
                          *, n_tiles: int, deg: int):
    h = pl.program_id(1)
    k = (deg + 1) * (deg + 1)

    @pl.when(h == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    qx = u_ref[...]
    qy = v_ref[...]
    coef = coef_ref[...]                                   # (BH, K)
    table = jnp.concatenate([coef, bounds_ref[...]], axis=1)  # (BH, K+4)
    one_hot = ((mx0_ref[...][None, :] <= qx[:, None]) &
               (qx[:, None] < mx1_ref[...][None, :]) &
               (my0_ref[...][None, :] <= qy[:, None]) &
               (qy[:, None] < my1_ref[...][None, :])).astype(coef.dtype)
    acc[...] += jnp.dot(one_hot, table, preferred_element_type=coef.dtype)

    @pl.when(h == n_tiles - 1)
    def _finalize():
        out_ref[...] = _bivariate_horner(qx, qy, acc[:, :k], acc[:, k:], deg)


def corner_eval2d_pallas(u, v, mx0, mx1, my0, my1, bounds, coeffs,
                         deg: int, bq: int = DEFAULT_BQ,
                         bh: int = DEFAULT_BH,
                         interpret: Optional[bool] = None):
    """Single-corner leaf evaluation over the flat leaf table — the one-hot
    membership twin of ``corner_eval2d_gather_pallas`` (the engine's
    ``pallas_scan`` backend and the deep-tree fallback).  Shapes pre-padded
    and corners pre-clamped by the caller."""
    Q, L = u.shape[0], mx0.shape[0]
    assert Q % bq == 0 and L % bh == 0, (Q, L, bq, bh)
    k = (deg + 1) * (deg + 1)
    assert coeffs.shape[1] == k, coeffs.shape
    n_tiles = L // bh
    kernel = functools.partial(_corner_eval2d_kernel, n_tiles=n_tiles,
                               deg=deg)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh, 4), lambda i, j: (j, 0)),
            pl.BlockSpec((bh, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        scratch_shapes=[pltpu.VMEM((bq, k + 4), coeffs.dtype)],
        interpret=resolve_interpret(interpret),
    )(u, v, mx0, mx1, my0, my1, bounds, coeffs)


def _corner_count2d_kernel(lx_ref, ux_ref, ly_ref, uy_ref,
                           mx0_ref, mx1_ref, my0_ref, my1_ref,
                           bounds_ref, coef_ref, out_ref, acc,
                           *, n_tiles: int, deg: int):
    h = pl.program_id(1)
    k = (deg + 1) * (deg + 1)
    ncol = k + 4

    @pl.when(h == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    mx0 = mx0_ref[...]
    mx1 = mx1_ref[...]
    my0 = my0_ref[...]
    my1 = my1_ref[...]
    coef = coef_ref[...]                                   # (BH, K)
    table = jnp.concatenate([coef, bounds_ref[...]], axis=1)  # (BH, K+4)

    corners = ((0, ux_ref[...], uy_ref[...]), (1, lx_ref[...], uy_ref[...]),
               (2, ux_ref[...], ly_ref[...]), (3, lx_ref[...], ly_ref[...]))
    for slot, qx, qy in corners:
        one_hot = ((mx0[None, :] <= qx[:, None]) & (qx[:, None] < mx1[None, :]) &
                   (my0[None, :] <= qy[:, None]) & (qy[:, None] < my1[None, :])
                   ).astype(coef.dtype)                    # (BQ, BH)
        acc[:, slot * ncol:(slot + 1) * ncol] += jnp.dot(
            one_hot, table, preferred_element_type=coef.dtype)

    @pl.when(h == n_tiles - 1)
    def _finalize():
        vals = []
        for slot, qx, qy in corners:
            c = acc[:, slot * ncol:slot * ncol + k]
            b0 = acc[:, slot * ncol + k + 0]
            b1 = acc[:, slot * ncol + k + 1]
            b2 = acc[:, slot * ncol + k + 2]
            b3 = acc[:, slot * ncol + k + 3]
            span_x = jnp.where(b1 > b0, b1 - b0, 1.0)
            span_y = jnp.where(b3 > b2, b3 - b2, 1.0)
            us = jnp.clip((2.0 * qx - b0 - b1) / span_x, -1.0, 1.0)
            vs = jnp.clip((2.0 * qy - b2 - b3) / span_y, -1.0, 1.0)
            v = jnp.zeros_like(us)
            for i in range(deg, -1, -1):
                inner = jnp.zeros_like(vs)
                for j in range(deg, -1, -1):
                    inner = inner * vs + c[:, i * (deg + 1) + j]
                v = v * us + inner
            vals.append(v)
        out_ref[...] = vals[0] - vals[1] - vals[2] + vals[3]


def corner_count2d_pallas(lx, ux, ly, uy, mx0, mx1, my0, my1, bounds, coeffs,
                          deg: int, bq: int = DEFAULT_BQ,
                          bh: int = DEFAULT_BH,
                          interpret: Optional[bool] = None):
    """4-corner COUNT over a flat leaf table; shapes pre-padded to block
    multiples and corners pre-clamped into the root region by the caller
    (the engine's count2d executor does both)."""
    Q, L = lx.shape[0], mx0.shape[0]
    assert Q % bq == 0 and L % bh == 0, (Q, L, bq, bh)
    assert coeffs.shape[1] == (deg + 1) * (deg + 1), coeffs.shape
    n_tiles = L // bh
    k = (deg + 1) * (deg + 1)
    kernel = functools.partial(_corner_count2d_kernel, n_tiles=n_tiles,
                               deg=deg)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh, 4), lambda i, j: (j, 0)),
            pl.BlockSpec((bh, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 4 * (k + 4)), coeffs.dtype)],
        interpret=resolve_interpret(interpret),
    )(lx, ux, ly, uy, mx0, mx1, my0, my1, bounds, coeffs)
