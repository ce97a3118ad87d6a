"""Pallas TPU kernel: fused segment-resolve + Horner polynomial evaluation.

Evaluates P_{I(q)}(q) for a batch of query keys against a PolyFit segment
table — the hot inner loop of every SUM/COUNT query (Eq. 14 does two of
these per query; see range_sum.py for the fused two-endpoint version).

TPU adaptation (DESIGN.md §3): instead of a per-lane binary search (pointer
chasing — unvectorizable on the VPU), each (query-block x segment-tile) step
computes the *one-hot membership matrix*

    one_hot[q, j] = (seg_lo[j] <= q) & (q < seg_next[j])

which is locally decidable per tile because ``seg_next`` (the next segment's
start, +inf for the last) ships alongside ``seg_lo``.  Membership is then
turned into gathered coefficients with an MXU matmul ``one_hot @ coeffs``,
accumulated across segment tiles in VMEM scratch.  The wrapper clamps
queries to >= seg_lo[0], so the one-hots partition [seg_lo[0], +inf) and
out-of-domain queries resolve to the edge polynomials — identical to the XLA
path's clip semantics.

Grid: (num_query_blocks, num_segment_tiles), segment tiles innermost so the
scratch accumulators live across the inner loop and the output block is
written once at the last tile.

Block sizes: BQ=256 queries x BH=512 segments gives a (256, 512) f32
compare/matmul tile (512 KiB in VMEM) plus (512, deg+1) coefficients —
comfortably inside the ~16 MiB VMEM budget with MXU-aligned dims.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.poly import horner, scale_unit

__all__ = ["poly_eval_pallas", "resolve_interpret", "DEFAULT_BQ",
           "DEFAULT_BH"]

DEFAULT_BQ = 256
DEFAULT_BH = 512


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode, decided from the platform: kernels run in the
    interpreter exactly when the default backend is the CPU, and compile
    for the chip everywhere else.  ``None`` (every default in this package)
    asks the platform; an explicit bool is kept as given."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _poly_eval_kernel(q_ref, lo_ref, nxt_ref, hi_ref, coef_ref, out_ref,
                      acc_coef, acc_lo, acc_hi, *, n_tiles: int, deg: int):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        acc_coef[...] = jnp.zeros_like(acc_coef)
        acc_lo[...] = jnp.zeros_like(acc_lo)
        acc_hi[...] = jnp.zeros_like(acc_hi)

    q = q_ref[...]                      # (BQ,)
    lo = lo_ref[...]                    # (BH,)
    nxt = nxt_ref[...]                  # (BH,)
    hi = hi_ref[...]                    # (BH,)
    coef = coef_ref[...]                # (BH, deg+1)

    one_hot = ((lo[None, :] <= q[:, None]) &
               (q[:, None] < nxt[None, :])).astype(coef.dtype)   # (BQ, BH)
    # membership -> gathered coefficients / bounds, on the MXU
    acc_coef[...] += jnp.dot(one_hot, coef, preferred_element_type=coef.dtype)
    acc_lo[...] += one_hot @ lo
    acc_hi[...] += one_hot @ hi

    @pl.when(h == n_tiles - 1)
    def _finalize():
        u = scale_unit(q, acc_lo[...], acc_hi[...])
        out_ref[...] = horner(acc_coef[...], u)


def poly_eval_pallas(q, seg_lo, seg_next, seg_hi, coeffs,
                     bq: int = DEFAULT_BQ, bh: int = DEFAULT_BH,
                     interpret: Optional[bool] = None):
    """P_{I(q)}(q) for q (Q,) against H segments.  Shapes must be padded to
    block multiples by the caller (see ops.pad_index / ops.poly_eval)."""
    Q, H = q.shape[0], seg_lo.shape[0]
    assert Q % bq == 0 and H % bh == 0, (Q, H, bq, bh)
    deg = coeffs.shape[1] - 1
    n_tiles = H // bh
    kernel = functools.partial(_poly_eval_kernel, n_tiles=n_tiles, deg=deg)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq, n_tiles),
        in_specs=[
            pl.BlockSpec((bq,), lambda i, j: (i,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh,), lambda i, j: (j,)),
            pl.BlockSpec((bh, deg + 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), coeffs.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, deg + 1), coeffs.dtype),
            pltpu.VMEM((bq,), coeffs.dtype),
            pltpu.VMEM((bq,), coeffs.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(q, seg_lo, seg_next, seg_hi, coeffs)
