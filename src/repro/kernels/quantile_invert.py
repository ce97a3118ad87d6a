"""Pallas TPU kernel: certified CF inversion for QUANTILE queries.

Per rank target the kernel runs the branch-free locate -> closed-form /
Newton solve -> key-grid snap pipeline of ``core.quantile`` entirely
on-chip and emits the (answer, lower, upper) triple in one launch:

* ``quantile_invert_pallas`` — the locate->gather path (the engine's
  ``pallas`` backend): the cummax'd segment-boundary array ``B`` is
  binary-searched with the same probe loop as ``kernels.locate``
  (O(log H) rounds), one coefficient row is gathered per target, and the
  per-segment root solve plus the exact-key snap run vectorised over the
  query block.  ``scan=True`` switches every searchsorted to the one-hot
  comparison sum — O(Q*(H+n)) work — which is the ``pallas_scan`` A/B
  twin; the summed predicate equals the bsearch predicate, so both
  variants return bit-identical keys.

The boundary array ``B`` and the exact key grid ``ref_keys`` are
computed *outside* the kernel and passed as inputs: ``lax.cummax`` is a
host-side prefix pass over the (H,) table, not per-query work, and
keeping the kernel body pure gather/arithmetic avoids relying on
associative-scan lowering inside Mosaic.  Rank-slack is folded into the
target arrays before launch (``certified_quantile_shifted`` form)
because the slack is a traced scalar.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.quantile import certified_quantile_shifted
from .poly_eval import DEFAULT_BQ, resolve_interpret

__all__ = ["quantile_invert_pallas"]


def _quantile_invert_kernel(tm_ref, tl_ref, th_ref, B_ref, lo_ref, hi_ref,
                            coef_ref, err_ref, keys_ref, mid_ref, out_lo_ref,
                            out_hi_ref, *, h, n, delta, scan):
    mid, x_lo, x_hi = certified_quantile_shifted(
        tm_ref[...], tl_ref[...], th_ref[...],
        seg_lo=lo_ref[...], seg_hi=hi_ref[...], coeffs=coef_ref[...],
        seg_err=err_ref[...], h=h, delta=delta, B=B_ref[...],
        ref_keys=keys_ref[...], n=n, scan=scan)
    mid_ref[...] = mid
    out_lo_ref[...] = x_lo
    out_hi_ref[...] = x_hi


def quantile_invert_pallas(t_mid: jnp.ndarray, t_lo: jnp.ndarray,
                           t_hi: jnp.ndarray, B: jnp.ndarray,
                           seg_lo: jnp.ndarray, seg_hi: jnp.ndarray,
                           coeffs: jnp.ndarray, seg_err: jnp.ndarray,
                           ref_keys: jnp.ndarray, *, h: int, n: int,
                           delta: float, bq: int = DEFAULT_BQ,
                           interpret: Optional[bool] = None,
                           scan: bool = False):
    """(answer, lower, upper) for slack-pre-shifted rank-target blocks.

    ``ref_keys`` is the (padded) sorted exact key grid; ``n`` the live
    key count.  All (H,)/(H, deg+1)/(nk,) tables are resident per block;
    only the three target arrays and outputs are bq-blocked.
    """
    Q = t_mid.shape[0]
    H = seg_lo.shape[0]
    nk = ref_keys.shape[0]
    deg = coeffs.shape[1] - 1
    assert Q % bq == 0, f"Q={Q} not a multiple of bq={bq}"
    kernel = functools.partial(_quantile_invert_kernel, h=h, n=n,
                               delta=delta, scan=scan)
    qspec = pl.BlockSpec((bq,), lambda i: (i,))
    tspec = pl.BlockSpec((H,), lambda i: (0,))
    out = jax.ShapeDtypeStruct((Q,), coeffs.dtype)
    return pl.pallas_call(
        kernel,
        grid=(Q // bq,),
        in_specs=[qspec, qspec, qspec, tspec, tspec, tspec,
                  pl.BlockSpec((H, deg + 1), lambda i: (0, 0)), tspec,
                  pl.BlockSpec((nk,), lambda i: (0,))],
        out_specs=(qspec, qspec, qspec),
        out_shape=(out, out, out),
        interpret=resolve_interpret(interpret),
    )(t_mid, t_lo, t_hi, B, seg_lo, seg_hi, coeffs, seg_err, ref_keys)
