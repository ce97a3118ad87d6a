"""Certified quantile inversion of the fitted cumulative function.

PolyFit's CF index stores, per segment I, a polynomial P_I whose minimax
residual ``err(I) = max_{k in I} |P_I(k) - F(k)|`` is certified **at the
data keys** (the paper's Eq. 10 constraint set; DESIGN.md §16).  F is
monotone non-decreasing (COUNT, or SUM of non-negative measures), so a rank
target t inverts to a key interval using only key-certified facts — the
fitted polynomial is *not* assumed monotone, and nothing is asserted about
P between keys:

* **upper end** — segment endpoints are data keys, so the first segment s
  whose endpoint value satisfies ``P_s(+1) >= t + slack + delta`` has
  ``F(seg_hi[s]) >= t + slack``: every rank-t crossing sits at or below
  ``seg_hi[s]``.  Within s, the suffix ``[u*, 1]`` on which P stays >=
  ``t + slack + err(s)`` (u* = the *largest* root of P = target, a set on
  which no monotonicity is needed) certifies every key it contains, so the
  upper end tightens to the first data key >= u* — a snap through the
  plan's exact key array when present, the segment endpoint otherwise.
* **lower end** — segments 0..s-1 with running-max endpoint value <=
  ``t - slack - delta`` are cleared wholesale (their keys' F values are
  certified below the target); within segment s the prefix ``[-1, u*)`` on
  which P stays <= ``t - slack - err(s)`` (u* = the *smallest* root) clears
  every key it contains.  Any real in the cleared region lower-bounds the
  crossing — no key snap required.

The interval [lower, upper] therefore brackets the exact quantile with the
rank error pushed through the inverse, the same certificate machinery as
Lemmas 5.1-5.4.  Location uses the running max of the per-segment endpoint
values P_i(+1) (``boundary_array``): a cummax is sorted, so the branch-free
``bsearch_count`` applies, and its first crossing of a threshold coincides
with the raw array's.  Root finding inside the located segment is closed
form for deg <= 3 (the degrees the paper recommends) via the shared solvers
in ``core.queries``, and a fixed-iteration safeguarded Newton/bisection
otherwise.

Everything here is plain ``jnp`` on values — it runs inside jitted XLA
paths, inside Pallas kernel bodies (``kernels/quantile_invert.py``), and in
host-side oracles, identically.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .poly import horner
from .queries import _roots_cubic, _roots_linear, _roots_quadratic

__all__ = [
    "boundary_array", "certified_quantile", "certified_quantile_shifted",
    "invert_cf", "rank_slack",
]

#: rank-unit slack for COUNT tables: absorbs every numpy.quantile
#: interpolation convention (linear/lower/higher all live within one rank
#: unit of q*N; the extra unit covers the inclusive-CF off-by-one).
COUNT_RANK_SLACK = 2.0

_NEWTON_ITERS = 40


def rank_slack(agg: str, total) -> jnp.ndarray:
    """Soundness margin added to rank targets before certification.

    COUNT ranks are integers — 2 rank units dominate every interpolation
    convention.  SUM ranks are continuous — a relative margin well above
    the float64 validity tolerance (1e-9 per lane) suffices.
    """
    if agg == "count":
        return jnp.asarray(COUNT_RANK_SLACK)
    return 1e-7 * (jnp.abs(jnp.asarray(total)) + 1.0)


def boundary_array(coeffs: jnp.ndarray) -> jnp.ndarray:
    """``B[i] = max_{j<=i} P_j(+1)`` — running max of segment endpoint CF
    values.  Sorted by construction; zero-coefficient padding rows evaluate
    to 0 and sit at the tail, where the running max has already saturated.
    A log-depth scan: ``lax.cummax`` lowers through ``reduce_window``,
    slow to compile in f64 for a TPU.
    """
    return jax.lax.associative_scan(
        jnp.maximum, horner(coeffs, jnp.ones(coeffs.shape[0], coeffs.dtype)))


def _newton_root(c: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """One root of P(u) = t on [-1, 1], safeguarded Newton + bisection.

    Fixed iteration count (branch-free, kernel-safe); when no sign change
    exists on the interval the result is rejected downstream by the root
    validity mask.
    """
    # derivative weights via scalar multiplies (no materialized constant
    # array — Pallas kernel bodies cannot capture traced-time constants)
    dc = jnp.stack([c[..., j] * float(j) for j in range(1, c.shape[-1])],
                   axis=-1)
    a = jnp.full_like(t, -1.0)
    b = jnp.ones_like(t)
    fa = horner(c, a) - t
    u = 0.5 * (a + b)
    for _ in range(_NEWTON_ITERS):
        fu = horner(c, u) - t
        same = (fu > 0) == (fa > 0)
        a = jnp.where(same, u, a)
        fa = jnp.where(same, fu, fa)
        b = jnp.where(same, b, u)
        du = horner(dc, u)
        step = u - fu / jnp.where(du == 0, 1.0, du)
        lo = jnp.minimum(a, b)
        hi = jnp.maximum(a, b)
        bad = (du == 0) | ~jnp.isfinite(step) | (step <= lo) | (step >= hi)
        u = jnp.where(bad, 0.5 * (a + b), step)
    return u


def _unit_roots(c: jnp.ndarray, t: jnp.ndarray):
    """Real roots of P(u) = t, nan-padded; closed form through deg 3."""
    deg = c.shape[-1] - 1
    if deg <= 1:
        return (_roots_linear(c[..., 0] - t, c[..., 1]),)
    if deg == 2:
        return _roots_quadratic(c[..., 0] - t, c[..., 1], c[..., 2])
    if deg == 3:
        return _roots_cubic(c[..., 0] - t, c[..., 1], c[..., 2], c[..., 3])
    return (_newton_root(c, t),)


def _extreme_root(c: jnp.ndarray, T: jnp.ndarray, which: str):
    """(root, found): largest/smallest real root of P(u) = T inside [-1, 1].

    No root inside the interval means P - T holds one sign throughout —
    the caller resolves which via an endpoint evaluation.
    """
    sign = 1.0 if which == "max" else -1.0
    best = jnp.full_like(T, -jnp.inf)
    for r in _unit_roots(c, T):
        valid = jnp.isfinite(r) & (jnp.abs(r) <= 1.0 + 1e-9)
        best = jnp.where(valid, jnp.maximum(best, sign * jnp.clip(r, -1.0, 1.0)),
                         best)
    found = jnp.isfinite(best)
    return jnp.where(found, sign * best, 0.0), found


def _unscale(u: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``core.poly.scale_unit`` (degenerate span -> lo)."""
    return jnp.where(hi > lo, 0.5 * (u * (hi - lo) + lo + hi), lo)


def _count(keys: jnp.ndarray, q: jnp.ndarray, side: str,
           scan: bool) -> jnp.ndarray:
    """searchsorted(keys, q, side): O(log n) branch-free binary search, or
    the O(Q*n) one-hot comparison sum (``pallas_scan`` A/B twin — the
    summed predicate is exactly the bsearch predicate, so indices match
    bit-for-bit)."""
    if scan:
        cmp = (keys[None, :] <= q[:, None]) if side == "right" else (
            keys[None, :] < q[:, None])
        return jnp.sum(cmp, axis=1, dtype=jnp.int32)
    from ..kernels.locate import bsearch_count  # lazy: kernels import core
    return bsearch_count(keys, q, side=side)


def invert_cf(t: jnp.ndarray, side: str, *, B: jnp.ndarray,
              seg_lo: jnp.ndarray, seg_hi: jnp.ndarray, coeffs: jnp.ndarray,
              seg_err: jnp.ndarray, h: int, delta: float, slack,
              ref_keys: Optional[jnp.ndarray] = None, n: int = 0,
              raw: bool = False, scan: bool = False):
    """Certified one-sided inverse of the fitted CF at rank targets ``t``.

    Locates with the *global* delta (sound: the located segment's endpoint
    key provably clears the global target, hence also the tighter
    per-segment one), then resolves the crossing inside the segment against
    the gathered ``seg_err``.  Returns (x, ok).  side='hi' lanes with
    ok=False have targets above the fitted range and must fall back to the
    domain top.  side='lo' is unconditionally sound against the *static*
    data (its worst case is already the domain floor); there, ok reports
    whether the stronger contract "every data key <= x has F(key) <= t"
    holds — the fact the dynamic executor needs to push the exact buffer
    correction through the inverse (ok=False only on the vacuous
    domain-floor fallback, which dynamic lanes must replace with a
    below-all-live-keys floor).
    """
    pad = slack + delta
    # complete real root sets exist closed-form through deg 3 (the degrees
    # the paper recommends); without them the prefix/suffix sign conditions
    # cannot be certified, so deg > 3 keeps segment-endpoint granularity.
    tight = coeffs.shape[-1] - 1 <= 3
    if side == "hi":
        s = jnp.minimum(_count(B, t + pad, "left", scan), h - 1)
    else:
        s = jnp.clip(_count(B, t - pad, "right", scan), 0, h - 1)
    lo = jnp.take(seg_lo, s)
    hi = jnp.take(seg_hi, s)
    c = jnp.take(coeffs, s, axis=0)
    e = jnp.take(seg_err, s)

    if side == "hi":
        # suffix [u*, 1] on which P >= T: every data key it holds (seg_hi[s]
        # is one) has F >= t + slack, so the first key >= u* caps the rank-t
        # crossing.  u* = largest root, or -1 when P >= T on all of [-1, 1]
        # (no root in the interval means P - T holds the sign it has at +1).
        T = t + (slack + e)
        ok = t + pad <= B[h - 1]
        if raw:                 # uncertified point estimate, no snap
            root, found = _extreme_root(c, T, "max")
            return _unscale(jnp.where(found, root, -1.0), lo, hi), ok
        if tight:
            root, found = _extreme_root(c, T, "max")
            x = _unscale(jnp.where(found, root, -1.0), lo, hi)
        else:
            x = hi
        if ref_keys is not None:
            k = jnp.minimum(_count(ref_keys, x, "left", scan), n - 1)
            x = jnp.take(ref_keys, k)
        else:
            x = hi   # segment endpoint key: coarser, still certified
        return x, ok

    # side == 'lo': prefix [-1, u*) on which P <= T clears every key it
    # holds; segments below s were cleared wholesale by the locate.  When
    # the segment-start value already exceeds T nothing inside s clears,
    # and the certified floor is the previous segment's endpoint key.
    prev = jnp.take(seg_hi, jnp.maximum(s - 1, 0))
    below = jnp.where(s > 0, prev, seg_lo[0])
    if not tight:
        return below, s > 0
    T = t - (slack + e)
    tiny = 1e-9 * (jnp.abs(T) + 1.0)
    root, found = _extreme_root(c, T, "min")
    start_ok = horner(c, jnp.full_like(t, -1.0)) <= T + tiny
    u = jnp.where(found, root, 1.0)
    x = jnp.where(start_ok, _unscale(u, lo, hi), below)
    return x, start_ok | (s > 0)


def certified_quantile_shifted(t_mid: jnp.ndarray, t_lo: jnp.ndarray,
                               t_hi: jnp.ndarray, *, seg_lo: jnp.ndarray,
                               seg_hi: jnp.ndarray, coeffs: jnp.ndarray,
                               seg_err: jnp.ndarray, h: int, delta: float,
                               B: jnp.ndarray,
                               ref_keys: Optional[jnp.ndarray] = None,
                               n: int = 0, scan: bool = False):
    """(answer, lower, upper) for slack-pre-shifted rank targets.

    ``t_lo``/``t_hi`` already carry the soundness slack (``rank_slack``) —
    this is the form the Pallas kernels consume, since the slack is a
    traced value folded into the target arrays before the kernel launch.
    """
    args = dict(seg_lo=seg_lo, seg_hi=seg_hi, coeffs=coeffs, h=h, scan=scan)
    x_hi, ok_hi = invert_cf(t_hi, "hi", B=B, seg_err=seg_err, delta=delta,
                            slack=0.0, ref_keys=ref_keys, n=n, **args)
    x_lo, _ = invert_cf(t_lo, "lo", B=B, seg_err=seg_err, delta=delta,
                        slack=0.0, **args)
    dom_hi = seg_hi[h - 1]
    x_hi = jnp.where(ok_hi, x_hi, dom_hi)
    zeros = jnp.zeros_like(seg_err)
    x_mid, ok_mid = invert_cf(t_mid, "hi", B=B, seg_err=zeros, delta=0.0,
                              slack=0.0, raw=True, **args)
    x_mid = jnp.clip(jnp.where(ok_mid, x_mid, dom_hi), x_lo, x_hi)
    return x_mid, x_lo, x_hi


def certified_quantile(t: jnp.ndarray, *, slack, **kw):
    """(answer, lower, upper) for rank targets ``t`` (already in CF units).

    [lower, upper] brackets every rank-t crossing of the monotone CF; the
    answer is the raw fitted crossing clipped into the certificate.
    Targets above the fitted range fall back to the fitted domain top,
    which brackets unconditionally (the data lives inside the domain).
    """
    return certified_quantile_shifted(t, t - slack, t + slack, **kw)
