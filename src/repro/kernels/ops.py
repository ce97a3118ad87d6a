"""Jit'd public wrappers around the Pallas query kernels.

The segment-table layout these kernels consume is now the canonical
``repro.engine.plan.IndexPlan`` (``SegTable`` remains as an alias, and
``from_index`` as the adapter constructor, for callers that want the raw
kernels without the engine's fused refinement path).  The wrappers handle
the kernel ABI only: query clamping to the index domain and padding queries
to block multiples (with domain-minimum sentinels, sliced off afterwards).

``backend`` selects: 'pallas' (the locate->gather kernels, interpret-mode
on CPU — the TPU-shaped code path), 'pallas_scan' (the original one-hot
membership kernels, kept for A/B benchmarking) or 'ref' (plain XLA, faster
on CPU hosts; identical semantics, see ref.py).  Benchmarks run all of
them.  For the full engine — backend dispatch plus in-path Q_rel
refinement — use ``repro.engine.Engine``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..engine.plan import IndexPlan, build_plan
from . import ref as _ref
from .poly_eval import DEFAULT_BH, DEFAULT_BQ, poly_eval_pallas
from .range_sum import range_sum_gather_pallas, range_sum_pallas
from .range_max import range_max_gather_pallas, range_max_pallas

__all__ = ["SegTable", "from_index", "poly_eval", "range_sum", "range_max"]

# The flat tile-padded segment table was promoted into the engine's
# canonical plan; the historical name stays importable.
SegTable = IndexPlan


def from_index(index, dtype=jnp.float32, bh: int = DEFAULT_BH) -> IndexPlan:
    """Build a kernel-ready IndexPlan from a core.index.PolyFitIndex1D.

    Skips the exact-refinement arrays (raw-kernel callers measure the pure
    approximation path); ``engine.build_plan`` includes them.
    """
    return build_plan(index, dtype=dtype, bh=bh, with_exact=False)


def _pad_queries(q, bq, fill):
    n = q.shape[0]
    p = (-n) % bq
    if p:
        q = jnp.concatenate([q, jnp.full((p,), fill, q.dtype)])
    return q, n


@functools.partial(jax.jit, static_argnames=("backend", "bq", "bh", "interpret"))
def poly_eval(table: IndexPlan, q, backend: str = "pallas",
              bq: int = DEFAULT_BQ, bh: int = DEFAULT_BH,
              interpret: Optional[bool] = None):
    q = jnp.asarray(q, table.coeffs.dtype)
    dom_lo = table.seg_lo[0]
    q = jnp.maximum(q, dom_lo)
    if backend == "ref":
        # padded segments (sentinel lo) are never matched by locate/one-hot,
        # so ref can consume the padded table directly
        return _ref.poly_eval_ref(q, table.seg_lo, table.seg_next,
                                  table.seg_hi, table.coeffs)
    qp, n = _pad_queries(q, bq, dom_lo)
    out = poly_eval_pallas(qp, table.seg_lo, table.seg_next, table.seg_hi,
                           table.coeffs, bq=bq, bh=bh, interpret=interpret)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("backend", "bq", "bh", "interpret"))
def range_sum(table: IndexPlan, lq, uq, backend: str = "pallas",
              bq: int = DEFAULT_BQ, bh: int = DEFAULT_BH,
              interpret: Optional[bool] = None):
    dt = table.coeffs.dtype
    lq = jnp.maximum(jnp.asarray(lq, dt), table.seg_lo[0])
    uq = jnp.maximum(jnp.asarray(uq, dt), table.seg_lo[0])
    if backend == "ref":
        return _ref.range_sum_ref(lq, uq, table.seg_lo, table.seg_next,
                                  table.seg_hi, table.coeffs)
    lp, n = _pad_queries(lq, bq, table.seg_lo[0])
    up, _ = _pad_queries(uq, bq, table.seg_lo[0])
    if backend == "pallas_scan":
        out = range_sum_pallas(lp, up, table.seg_lo, table.seg_next,
                               table.seg_hi, table.coeffs,
                               bq=bq, bh=bh, interpret=interpret)
    else:
        out = range_sum_gather_pallas(lp, up, table.seg_lo, table.seg_hi,
                                      table.coeffs, bq=bq,
                                      interpret=interpret)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("backend", "bq", "bh", "interpret"))
def range_max(table: IndexPlan, lq, uq, backend: str = "pallas",
              bq: int = DEFAULT_BQ, bh: int = DEFAULT_BH,
              interpret: Optional[bool] = None):
    dt = table.coeffs.dtype
    lq = jnp.maximum(jnp.asarray(lq, dt), table.seg_lo[0])
    uq = jnp.maximum(jnp.asarray(uq, dt), table.seg_lo[0])
    if backend == "ref":
        return _ref.range_max_ref(lq, uq, table.seg_lo, table.seg_next,
                                  table.seg_hi, table.coeffs, table.seg_agg)
    lp, n = _pad_queries(lq, bq, table.seg_lo[0])
    up, _ = _pad_queries(uq, bq, table.seg_lo[0])
    if backend == "pallas_scan":
        out = range_max_pallas(lp, up, table.seg_lo, table.seg_next,
                               table.seg_hi, table.coeffs, table.seg_agg,
                               bq=bq, bh=bh, interpret=interpret)
    else:
        out = range_max_gather_pallas(lp, up, table.seg_lo, table.seg_hi,
                                      table.coeffs, table.st, bq=bq,
                                      interpret=interpret)
    return out[:n]
