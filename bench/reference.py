"""The plain reference that decides ``correct``, and the numbers compared.

``Truth`` answers range aggregates exactly over a table's records with
sorted numpy arrays and slices (copied from ``chip_smoke.py``), independent
of the index, its plans and its refinement structures.  ``LiveTruth``
extends it to a table that takes inserts while it is read: a read must see
every insert acknowledged before it was submitted and may see any insert
issued before it resolved, so it answers each query with an interval
``[lo, hi]`` the served answer has to fall in (up to the error bound).

``compare`` turns served answers and their truth intervals into the
numbers the configuration holds the program to, each against its limit.
``control_answers`` is the reference itself in float32, the nearest
precision below the float64 the program serves in: put in the program's
place it has to come out as not correct.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

__all__ = ["Truth", "LiveTruth", "InsertBatch", "compare", "passed",
           "control_answers", "SLACK"]

# float64 rounding of the program's own arithmetic: CF values reach 1e10
# for HKI SUM, so each check allows SLACK * (|truth| + bound) on top
SLACK = 1e-9

KINDS_1D = ("count", "sum", "max", "min")


class Truth:
    """Exact answers over a table's live records — sorted arrays and
    slices, independent of the index and its refinement structures."""

    def __init__(self, kind: str, data, dtype=np.float64):
        self.kind = kind
        self.dtype = np.dtype(dtype)
        if kind in KINDS_1D:
            keys = data if kind == "count" else data[0]
            meas = np.ones_like(keys) if kind == "count" else data[1]
            order = np.argsort(keys, kind="stable")
            self.k = np.asarray(keys, np.float64)[order].astype(dtype)
            self.m = np.asarray(meas, np.float64)[order].astype(dtype)
            self.cf = np.concatenate([np.zeros(1, dtype),
                                      np.cumsum(self.m, dtype=dtype)])
        else:
            xs, ys = data[0], data[1]
            ws = np.ones_like(xs) if kind == "count2d" else data[2]
            order = np.argsort(xs, kind="stable")
            self.x = np.asarray(xs, np.float64)[order].astype(dtype)
            self.y = np.asarray(ys, np.float64)[order].astype(dtype)
            self.w = np.asarray(ws, np.float64)[order].astype(dtype)

    def __call__(self, *q) -> np.ndarray:
        q = [np.asarray(c, np.float64).astype(self.dtype) for c in q]
        kind = self.kind
        if kind in ("count", "sum"):        # (lq, uq]
            lq, uq = q
            return (self.cf[np.searchsorted(self.k, uq, side="right")]
                    - self.cf[np.searchsorted(self.k, lq, side="right")])
        if kind in ("max", "min"):          # [lq, uq]
            lq, uq = q
            i = np.searchsorted(self.k, lq, side="left")
            j = np.searchsorted(self.k, uq, side="right")
            red = np.max if kind == "max" else np.min
            empty = -np.inf if kind == "max" else np.inf
            return np.array([red(self.m[a:b]) if b > a else empty
                             for a, b in zip(i, j)])
        if kind in ("count2d", "sum2d"):    # (lx, ux] x (ly, uy]
            lx, ux, ly, uy = q
            i = np.searchsorted(self.x, lx, side="right")
            j = np.searchsorted(self.x, ux, side="right")
            out = np.empty(len(lx), self.dtype)
            for t, (a, b) in enumerate(zip(i, j)):
                ys = self.y[a:b]
                out[t] = self.w[a:b][(ys > ly[t]) & (ys <= uy[t])].sum()
            return out
        u, v = q                            # dominance: x <= u, y <= v
        j = np.searchsorted(self.x, u, side="right")
        red = np.max if kind == "max2d" else np.min
        empty = -np.inf if kind == "max2d" else np.inf
        out = np.empty(len(u), self.dtype)
        for t, b in enumerate(j):
            sel = self.w[:b][self.y[:b] <= v[t]]
            out[t] = red(sel) if len(sel) else empty
        return out


class InsertBatch:
    """One acknowledged-or-not insert: its records and when it was issued
    (``engine.insert`` called) and acknowledged (the call returned, so the
    records are query-visible).  ``ack`` is ``inf`` for one never acked."""

    __slots__ = ("keys", "vals", "issued", "acked")

    def __init__(self, keys, vals, issued: float, acked: float):
        self.keys = np.asarray(keys, np.float64)
        self.vals = np.asarray(vals, np.float64)
        self.issued = float(issued)
        self.acked = float(acked)


class LiveTruth:
    """Exact SUM/COUNT over a base table plus insert batches, as the
    interval a read may legitimately answer (see the module docstring)."""

    def __init__(self, kind: str, base, batches: Sequence[InsertBatch] = (),
                 dtype=np.float64):
        if kind not in ("count", "sum"):
            raise ValueError(f"live truth covers COUNT/SUM, not {kind!r}")
        self.kind = kind
        self.dtype = dtype
        self.base = Truth(kind, base, dtype)
        self.batches = list(batches)

    def contributions(self, lq, uq) -> np.ndarray:
        """(queries, batches) exact contribution of each batch."""
        out = np.zeros((len(lq), len(self.batches)), self.dtype)
        for j, b in enumerate(self.batches):
            vals = np.ones_like(b.vals) if self.kind == "count" else b.vals
            out[:, j] = Truth("sum", (b.keys, vals), self.dtype)(lq, uq)
        return out

    def bounds(self, lq, uq, submitted, resolved
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Per query: (lo, hi) over the inserts a read submitted at
        ``submitted`` and resolved at ``resolved`` may have seen."""
        base = self.base(lq, uq)
        if not self.batches:
            return base, base
        c = self.contributions(lq, uq)
        acked = np.array([b.acked for b in self.batches])
        issued = np.array([b.issued for b in self.batches])
        must = acked[None, :] <= np.asarray(submitted, np.float64)[:, None]
        may = issued[None, :] <= np.asarray(resolved, np.float64)[:, None]
        opt = may & ~must
        fixed = base + (c * must).sum(axis=1)
        lo = fixed + (np.minimum(c, 0) * opt).sum(axis=1)
        hi = fixed + (np.maximum(c, 0) * opt).sum(axis=1)
        return lo, hi

    def snapshot(self, submitted: float):
        """The records a read submitted at ``submitted`` must see: base
        plus every batch acknowledged by then (the control's table)."""
        keys = [self.base.k.astype(np.float64)]
        vals = [self.base.m.astype(np.float64)]
        for b in self.batches:
            if b.acked <= submitted:
                keys.append(b.keys)
                vals.append(np.ones_like(b.vals) if self.kind == "count"
                            else b.vals)
        return np.concatenate(keys), np.concatenate(vals)


def compare(answers, lo, hi, refined, bound: float,
            limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """The numbers ``correct`` is decided by, each with its limit.

    ``err_over_bound``: the largest distance of an answer outside its
    truth interval, over the certified bound (Q_abs: at most 1).
    ``rel_err``: that distance over the truth (Q_rel: at most eps_rel; an
    empty range must answer exactly).  ``refined_err_over_bound``: the
    first number over the answers the program refined, which it claims to
    answer exactly.  Each distance first gives up ``SLACK`` float64
    rounding on the operands' scale.
    """
    ans = np.asarray(answers, np.float64)
    lo = np.asarray(lo, np.float64)
    hi = np.asarray(hi, np.float64)
    refined = np.asarray(refined, bool)
    gap = np.maximum(np.maximum(lo - ans, ans - hi), 0.0)
    gap = np.where(np.isfinite(ans), gap, np.inf)
    gap = np.maximum(gap - SLACK * (np.abs(hi) + bound), 0.0)
    scale = np.abs(lo)
    rel = np.divide(gap, scale, out=np.where(gap > 0, np.inf, 0.0),
                    where=scale > 0)
    numbers = {
        "err_over_bound": float(gap.max(initial=0.0) / bound),
        "rel_err": float(rel.max(initial=0.0)),
        "refined_err_over_bound": float(
            gap[refined].max(initial=0.0) / bound),
    }
    return {k: {"value": v, "limit": float(limits[k])}
            for k, v in numbers.items()}


def passed(numbers: Dict[str, Dict[str, float]]) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


def control_answers(truth: LiveTruth, lq, uq, submitted) -> np.ndarray:
    """The reference computed in float32, in the program's place: each
    read answered over the records it must see, every answer exact to
    float32 (so every answer counts as refined)."""
    out = np.empty(len(lq), np.float64)
    order = np.argsort(submitted, kind="stable")
    acked = sorted({b.acked for b in truth.batches
                    if b.acked <= max(submitted, default=-np.inf)})
    # group reads by how many batches they must see: one f32 table each
    cuts = np.searchsorted(np.asarray(acked), np.asarray(submitted)[order],
                           side="right")
    for g in np.unique(cuts):
        idx = order[cuts == g]
        t = acked[g - 1] if g > 0 else -np.inf
        keys, vals = truth.snapshot(t)
        data = keys if truth.kind == "count" else (keys, vals)
        out[idx] = Truth(truth.kind, data, np.float32)(lq[idx], uq[idx])
    return out

