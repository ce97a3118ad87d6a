"""Branch-free vectorized segment/leaf location (the locate half of the
locate->gather kernel architecture, DESIGN.md §10).

Every one-hot membership kernel in this package does O(Q*H) work per batch:
the whole tile-padded table is compared against every query.  PolyFit's
complexity claim needs the lookup to be O(log H), so this module provides
the shared locate primitives the gather kernels are built on:

* ``bsearch_count`` — a branch-free binary search over a sorted array,
  returning per-lane ``searchsorted`` counts in ceil(log2 n) probe rounds.
  Each round is one clamped gather + compare + select, so the whole search
  vectorizes across the query batch with no per-lane control flow (the VPU
  analogue of Skarupke's branchless lower bound).  It is plain ``jnp`` on
  values, so the same function runs inside Pallas kernel bodies, inside the
  jnp oracles (``ref.py``), and in host-side tests.
* ``locate_segments`` — the kernel-side twin of ``core.poly.locate``:
  clip(searchsorted(seg_lo, q, right) - 1, 0, H-1).
* ``rmq_gather`` — O(1) sparse-table range max via two flattened gathers,
  mirroring ``core.exact.sparse_table_range_max`` (used for interior
  MAX spans and delta-buffer MAX corrections).
* ``interleave2`` / ``dyadic_cuts`` / ``leaf_morton_codes`` — the 2-D
  story: quadtree leaves are intervals in Morton (Z-order) space, so corner
  location becomes *three* binary searches (cell x, cell y, leaf z).  The
  cut grids are rebuilt with the exact midpoint recursion the quadtree
  build uses, so locating against them is bit-identical to the one-hot
  membership rule (ties on a split line go to the higher-coordinate leaf).
* ``locate_pallas`` — a standalone Pallas kernel exposing the 1-D segment
  locate (grid over query blocks, the whole boundary array resident in
  VMEM; compiled mode lowers the probe gathers to Mosaic dynamic gathers,
  interpret mode runs them as plain XLA gathers on CPU).

Sentinel-padded tails need no special casing anywhere: the padding value
exceeds every real key, so the counts never reach it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .poly_eval import DEFAULT_BQ, resolve_interpret

__all__ = [
    "bsearch_count", "locate_segments", "floor_log2", "rmq_gather",
    "interleave2", "locate_leaf2d", "dyadic_cuts", "leaf_morton_codes",
    "locate_pallas", "MAX_MORTON_DEPTH", "INT_SENTINEL",
]

# 2 bits per level must fit an int32 Morton code (sign bit reserved)
MAX_MORTON_DEPTH = 15
INT_SENTINEL = np.iinfo(np.int32).max


def bsearch_count(keys: jnp.ndarray, q: jnp.ndarray,
                  side: str = "right") -> jnp.ndarray:
    """Per-lane ``searchsorted(keys, q, side)`` in ceil(log2 n) rounds.

    Returns the number of ``keys`` entries <= q (side='right') or < q
    (side='left') as int32.  ``keys`` must be sorted ascending; each round
    probes index ``c + step - 1`` (clamped) and advances the count when the
    probe satisfies the predicate — branch-free, one gather per round.
    """
    n = keys.shape[0]
    c = jnp.zeros(q.shape, jnp.int32)
    step = 1 << max(0, (n - 1).bit_length())   # bit_ceil(n)
    while step >= 1:
        probe = c + (step - 1)
        pv = jnp.take(keys, jnp.minimum(probe, n - 1))
        ok = (pv <= q) if side == "right" else (pv < q)
        c = jnp.where((probe <= n - 1) & ok, c + step, c)
        step >>= 1
    return c


def locate_segments(seg_lo: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Segment id containing q — the gather-path twin of ``core.poly.locate``
    (clip(searchsorted(seg_lo, q, 'right') - 1, 0, H-1))."""
    return jnp.maximum(bsearch_count(seg_lo, q, side="right") - 1, 0)


def floor_log2(length: jnp.ndarray, max_levels: int) -> jnp.ndarray:
    """floor(log2(length)) for int vectors with 1 <= length < 2^max_levels
    (0 for length < 1) — a static sum of compares, no float log."""
    k = jnp.zeros(length.shape, jnp.int32)
    for i in range(1, max_levels):
        k = k + (length >= (1 << i)).astype(jnp.int32)
    return k


def rmq_gather(st: jnp.ndarray, i0: jnp.ndarray, i1: jnp.ndarray):
    """Max over [i0, i1) against a (L, n) sparse table; empty -> -inf.

    Two flattened gathers per lane — the in-kernel twin of
    ``core.exact.sparse_table_range_max`` (same two-window decomposition,
    so results are bit-identical).
    """
    levels, n = st.shape
    flat = st.reshape(-1)
    length = jnp.maximum(i1 - i0, 0)
    lvl = floor_log2(jnp.maximum(length, 1), levels)
    pow2 = jnp.left_shift(jnp.int32(1), lvl)
    left = jnp.take(flat, lvl * n + jnp.minimum(i0, n - 1))
    right = jnp.take(flat, lvl * n + jnp.clip(i1 - pow2, 0, n - 1))
    return jnp.where(length > 0, jnp.maximum(left, right), -jnp.inf)


# ---------------------------------------------------------------------------
# 2-D: quadtree leaves as Morton-interval table
# ---------------------------------------------------------------------------

def interleave2(ix: jnp.ndarray, iy: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Morton (Z-order) code of cell (ix, iy) at ``depth`` bits per axis."""
    z = jnp.zeros(jnp.shape(ix), jnp.int32)
    for b in range(depth):
        z = z | (((ix >> b) & 1) << (2 * b)) | (((iy >> b) & 1) << (2 * b + 1))
    return z


def locate_leaf2d(qx, qy, xcuts, ycuts, leaf_z, depth: int) -> jnp.ndarray:
    """Leaf-table row containing each (pre-clamped) query corner.

    Three binary searches: cell x = #xcuts <= qx, cell y = #ycuts <= qy
    (so a corner exactly on a split line lands in the higher cell — the
    quadtree descent's tie rule), then the Morton code's containing leaf
    interval in the z-sorted table.  O(log H) total.
    """
    ix = bsearch_count(xcuts, qx, side="right")
    iy = bsearch_count(ycuts, qy, side="right")
    z = interleave2(ix, iy, depth)
    return jnp.maximum(bsearch_count(leaf_z, z, side="right") - 1, 0)


def dyadic_cuts(lo: float, hi: float, depth: int) -> np.ndarray:
    """The 2^depth - 1 interior split lines of a midpoint-recursive quadtree
    axis, computed with the *same* float recursion as the tree build
    (``mid = 0.5*(lo + hi)`` of each node's own bounds), so every leaf
    boundary equals a cut value exactly."""
    m = 1 << depth
    g = np.empty(m + 1, np.float64)
    g[0], g[m] = lo, hi
    stack = [(0, m)]
    while stack:
        i0, i1 = stack.pop()
        if i1 - i0 < 2:
            continue
        im = (i0 + i1) // 2
        g[im] = 0.5 * (g[i0] + g[i1])
        stack.append((i0, im))
        stack.append((im, i1))
    return g[1:m]


def leaf_morton_codes(leaf_bounds: np.ndarray, xcuts: np.ndarray,
                      ycuts: np.ndarray, depth: int) -> np.ndarray:
    """Morton code of each leaf's lower-left cell (its z-interval start).

    A quadtree leaf at depth d covers a contiguous Z-order run of
    4^(depth-d) cells, so the starts sort the leaves into disjoint
    intervals covering [0, 4^depth).
    """
    ix0 = np.searchsorted(xcuts, leaf_bounds[:, 0], side="right")
    iy0 = np.searchsorted(ycuts, leaf_bounds[:, 2], side="right")
    z = np.zeros(len(leaf_bounds), np.int64)
    for b in range(depth):
        z |= ((ix0 >> b) & 1) << (2 * b)
        z |= ((iy0 >> b) & 1) << (2 * b + 1)
    return z.astype(np.int32)


# ---------------------------------------------------------------------------
# standalone locate kernel
# ---------------------------------------------------------------------------

def _locate_kernel(q_ref, lo_ref, out_ref):
    out_ref[...] = locate_segments(lo_ref[...], q_ref[...])


def locate_pallas(q, seg_lo, bq: int = DEFAULT_BQ,
                  interpret: Optional[bool] = None):
    """Segment id per query key: (Q,) int32 against sorted (Hp,) seg_lo.

    Grid over query blocks only — the boundary array is fully resident, and
    each block does ceil(log2 Hp) gather rounds, independent of Hp's size.
    """
    Q, H = q.shape[0], seg_lo.shape[0]
    assert Q % bq == 0, (Q, bq)
    return pl.pallas_call(
        _locate_kernel,
        grid=(Q // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((H,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q, seg_lo)
