"""Epoch-ring windowed aggregates over the LSM level machinery.

Streaming rows land in an append-only delta buffer (the open epoch);
``advance()`` seals the buffer into an immutable fitted plan wrapped as a
tombstone-free ``LsmLevel`` and pushes it onto a bounded ring.  A window
query ``[t0, t1]`` then *is* an LSM execution over the selected epoch
levels — the existing ``execute_lsm`` fuses the per-epoch evaluations
exactly (every level's correction is exact; only fitted approximation
error composes), plus the open epoch's exact buffer correction when the
window reaches it.  Bounds compose via ``composed_bound`` over the
selected levels' deltas (DESIGN.md §16).

Epoch ids are dense integers starting at 0; the ring retains the last
``ring`` sealed epochs and queries below the oldest retained epoch raise
(the data is gone).  1-D SUM/COUNT only, append-only: a windowed stream
has no deletes — rows leave by epoch eviction.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.index import build_index_1d
from ..core.queries import QueryResult
from ..kernels.poly_eval import DEFAULT_BQ, resolve_interpret
from .dynamic import DeltaBuffer, _append_1d, _pad_batch
from .engine import check_pow2
from .lsm import LsmLevel, LsmPlan, composed_bound, execute_lsm
from .plan import big_sentinel, build_plan

__all__ = ["WindowEngine"]


class WindowEngine:
    """Ring of per-epoch immutable plans answering windowed SUM/COUNT.

    ``keys``/``measures`` (optional) seal immediately as epoch 0; the
    open epoch is always ``self.epoch``.  ``ingest`` appends to the open
    epoch, ``advance`` seals it, ``query(lq, uq, t0, t1)`` evaluates the
    range aggregate restricted to epochs t0..t1 inclusive.
    """

    def __init__(self, keys=None, measures=None, *, agg: str = "count",
                 delta: float = 64.0, deg: int = 2, ring: int = 8,
                 capacity: int = 1024, backend: str = "xla",
                 interpret: Optional[bool] = None, bq: int = DEFAULT_BQ,
                 min_bucket: int = 64):
        if agg not in ("sum", "count"):
            raise ValueError("windowed aggregates support 1-D SUM/COUNT "
                             f"only, got {agg!r}")
        if ring < 1:
            raise ValueError("ring must retain at least one epoch")
        check_pow2("capacity", capacity)
        check_pow2("bq", bq)
        check_pow2("min_bucket", min_bucket)
        self.agg = agg
        self.delta = float(delta)
        self.deg = deg
        self.ring = ring
        self.capacity = capacity
        self.backend = backend
        self.interpret = resolve_interpret(interpret)
        self.bq = bq
        self.min_bucket = min_bucket
        self._lock = threading.RLock()
        self._ring: deque = deque(maxlen=ring)   # (epoch_id, level-or-None)
        self._buf = DeltaBuffer.empty(capacity)
        self._pend: List[Tuple[np.ndarray, np.ndarray]] = []
        self._n_buf = 0
        self.epoch = 0
        if keys is not None and len(np.atleast_1d(keys)):
            self._ring.append((0, self._build_level(
                np.atleast_1d(np.asarray(keys, np.float64)), measures, 0)))
            self.epoch = 1

    def place(self, device) -> None:
        """Commit the sealed epochs and the open epoch's buffer to
        ``device`` (the session builds on the host and places once)."""
        with self._lock:
            self._ring = deque(((eid, jax.device_put(lvl, device))
                                for eid, lvl in self._ring), maxlen=self.ring)
            self._buf = jax.device_put(self._buf, device)

    # -- epoch lifecycle -------------------------------------------------

    def _build_level(self, k: np.ndarray, v, slot: int) -> LsmLevel:
        if self.agg == "count":
            v = np.ones_like(k)
        elif v is None:
            raise ValueError("measures required unless agg='count'")
        else:
            v = np.broadcast_to(np.asarray(v, np.float64), k.shape).copy()
        order = np.argsort(k, kind="stable")
        idx = build_index_1d(k[order], v[order], agg=self.agg,
                             delta=self.delta, deg=self.deg,
                             keep_exact=True)
        return LsmLevel(build_plan(idx), None, None, None, None, slot=slot)

    def ingest(self, keys, measures=None) -> None:
        """Append rows to the open epoch (exact until sealed)."""
        keys = np.atleast_1d(np.array(keys, np.float64))
        if self.agg == "count":
            vals = np.ones_like(keys)
        elif measures is None:
            raise ValueError("measures required unless agg='count'")
        else:
            vals = np.broadcast_to(
                np.asarray(measures, np.float64), keys.shape).copy()
        if not len(keys):
            return
        with self._lock:
            if self._n_buf + len(keys) > self.capacity:
                raise ValueError(
                    f"open epoch holds {self._n_buf} rows; {len(keys)} more "
                    f"exceeds capacity {self.capacity} — call advance()")
            buf = self._buf
            dt = buf.ins_keys.dtype
            pk = _pad_batch(keys, big_sentinel(dt), dt)
            pv = _pad_batch(vals, 0.0, dt)
            ik, iv, icf, _ = _append_1d(buf.ins_keys, buf.ins_vals, pk, pv,
                                        cap=buf.cap, with_st=False)
            self._buf = dataclasses.replace(buf, ins_keys=ik, ins_vals=iv,
                                            ins_cf=icf)
            self._pend.append((keys, vals))
            self._n_buf += len(keys)

    def advance(self) -> int:
        """Seal the open epoch into an immutable level; empty epochs seal
        as holes (no level).  Returns the new open epoch id."""
        with self._lock:
            eid = self.epoch
            if self._n_buf:
                k = np.concatenate([p[0] for p in self._pend])
                v = np.concatenate([p[1] for p in self._pend])
                lvl = self._build_level(k, v, eid)
            else:
                lvl = None
            self._ring.append((eid, lvl))
            self._buf = DeltaBuffer.empty(self.capacity)
            self._pend = []
            self._n_buf = 0
            self.epoch = eid + 1
            return self.epoch

    @property
    def oldest(self) -> int:
        """Oldest retained epoch id (sealed or the open epoch)."""
        return self._ring[0][0] if self._ring else self.epoch

    # -- queries ---------------------------------------------------------

    def _select(self, t0: int, t1: int):
        t0, t1 = int(t0), int(t1)
        if t1 < t0:
            raise ValueError(f"empty window [{t0}, {t1}]")
        if t0 < self.oldest:
            raise ValueError(f"epoch {t0} evicted (oldest retained is "
                             f"{self.oldest}, ring={self.ring})")
        levels = tuple(lvl for eid, lvl in self._ring
                       if t0 <= eid <= t1 and lvl is not None)
        buf = self._buf if (t0 <= self.epoch <= t1 and self._n_buf) else None
        return levels, buf

    def window_plan(self, t0: int, t1: int):
        """Atomic (LsmPlan-or-None, buf-or-None) snapshot of the window —
        the pair external executors (serving) evaluate against."""
        with self._lock:
            levels, buf = self._select(t0, t1)
        plan = LsmPlan(levels=levels, agg=self.agg) if levels else None
        return plan, buf

    def bound(self, t0: int, t1: int) -> float:
        """Certified absolute error of a [t0, t1] window answer: the
        sealed epochs' deltas compose (Lemma 5.1 per level); the open
        epoch's buffer correction is exact and adds nothing."""
        with self._lock:
            levels, _ = self._select(t0, t1)
        return composed_bound(self.agg, [l.plan.delta for l in levels]) \
            if levels else 0.0

    def query(self, lq, uq, t0: int, t1: int,
              eps_rel: Optional[float] = None) -> QueryResult:
        """SUM/COUNT over (lq, uq] restricted to epochs t0..t1."""
        plan, buf = self.window_plan(t0, t1)
        lq, uq = jnp.asarray(lq), jnp.asarray(uq)
        if plan is None:
            if buf is None:        # window covers no rows at all
                z = jnp.zeros(lq.shape, jnp.float64)
                return QueryResult(z, z, jnp.zeros(lq.shape, bool))
            # open epoch only: the exact prefix-sum correction is the answer
            dt = buf.ins_keys.dtype
            lqc, uqc = lq.astype(dt), uq.astype(dt)
            ans = (buf.ins_cf[jnp.searchsorted(buf.ins_keys, uqc, "right")]
                   - buf.ins_cf[jnp.searchsorted(buf.ins_keys, lqc,
                                                 "right")])
            return QueryResult(ans, ans, jnp.zeros(lq.shape, bool))
        return execute_lsm(plan, buf, (lq, uq), backend=self.backend,
                           eps_rel=eps_rel, interpret=self.interpret,
                           bq=self.bq, min_bucket=self.min_bucket)
