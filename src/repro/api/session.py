"""The ``PolyFit`` session facade: one declarative entry point for every
aggregate family, batch shape, dynamism level and device layout.

    from repro.api import ErrorBudget, PolyFit, QueryBatch, QuerySpec, TableSpec

    session = PolyFit.fit(
        {"lat": keys, "price": (ts, vals), "geo": (xs, ys)},
        {"lat":   TableSpec("count",   ErrorBudget(abs=100, rel=0.01)),
         "price": TableSpec("max",     ErrorBudget(abs=50.0)),
         "geo":   TableSpec("count2d", ErrorBudget(abs=200))})
    results = session.query(QueryBatch.of(
        QuerySpec.range("lat", -10.0, 30.0),
        QuerySpec.rect("geo", x0, x1, y0, y1),
        QuerySpec.range("price", t0, t1)))

``fit`` builds one index per named table with the delta its ``ErrorBudget``
derives (Lemma 5.1/5.3/6.3 — see ``budget.py``), lowers each to a canonical
device plan, and wires the execution stack the ``TableSpec`` asks for:
static plans dispatch straight through ``engine.execute_*``, ``dynamic``
tables get a delta-buffered ``DynamicEngine`` (inserts/deletes without
rebuild), ``lsm=True`` tables an ``LsmEngine`` geometric level ladder
(worst-case bounded compactions, never a full refit — DESIGN.md §15),
``shards=N`` partitions the plan across N devices behind the
``shard_map`` executor (``engine/sharded.py``; LSM ladders shard per
level, Q_abs only).  ``query`` groups a mixed
batch by (plan, guarantee), pads each group to its power-of-two bucket,
runs one fused jitted executor per group, and scatters the answers back in
request order — so callers never touch ``Engine``/``DynamicEngine``, which
are now internal machinery behind this facade.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import AGGS_2D, build_index_1d, build_index_2d
from ..core.queries import QueryResult
from ..engine import (DynamicEngine, DynamicEngine2D, LsmEngine,
                      LsmEngine2D, ShardedEngine, ShardedEngine2D,
                      WindowEngine, build_plan, build_plan_2d, execute,
                      execute_quantile, fused_executor,
                      fused_quantile_executor)
from ..kernels.poly_eval import DEFAULT_BQ, resolve_interpret
from .budget import ErrorBudget
from .spec import (DEFAULT_REL, KIND_OF_AGG, QueryBatch, QuerySpec,
                   TableSpec)

__all__ = ["PolyFit", "Answer"]

Request = Union[QuerySpec, QueryBatch, Sequence[QuerySpec]]


@dataclasses.dataclass(frozen=True)
class Answer:
    """One structured query answer, uniform across every query kind.

    ``value`` is the (possibly refined) answer batch; ``approx``/``refined``
    expose the raw index answers and the Q_rel refinement mask exactly as
    :class:`~repro.core.queries.QueryResult` did.  ``bound`` is the
    certified guarantee that travels with the answer: the scalar Q_abs
    bound for range aggregates (composed over selected epochs for window
    queries), or the ``(lo, hi)`` certified key interval for quantiles
    (``value`` is clipped inside it).  ``staleness`` counts how far the
    answer lags a fully-merged view — buffered-but-unmerged rows for
    dynamic/LSM tables, trailing epochs (current minus ``t1``) for window
    queries, 0 for static plans; buffered rows are still folded in
    *exactly*, so staleness is an operational signal, not extra error.

    ``.answer`` aliases ``value`` for drop-in compatibility with
    ``QueryResult`` consumers.
    """

    value: jnp.ndarray
    approx: jnp.ndarray
    refined: jnp.ndarray
    bound: object = None
    staleness: int = 0

    @property
    def answer(self):
        return self.value

    def __iter__(self):   # (value, approx, refined) unpacking compat
        return iter((self.value, self.approx, self.refined))


jax.tree_util.register_pytree_node(
    Answer,
    lambda a: ((a.value, a.approx, a.refined, a.bound), a.staleness),
    lambda staleness, kids: Answer(*kids, staleness=staleness))


class _Table:
    """One fitted table: the spec plus whichever execution stack it needs."""

    build_seconds: float = 0.0   # wall time of this table's construction

    def __init__(self, name: str, spec: TableSpec, data, *, backend: str,
                 interpret: Optional[bool], bq: int, min_bucket: int):
        self.name = name
        self.spec = spec
        self.dyn = None
        self.win = None
        self.sharded = None
        self._static_plan = None
        agg = spec.agg
        if spec.window:
            keys, meas = data
            self.win = WindowEngine(
                keys, meas, agg=agg, delta=spec.budget.delta(agg),
                deg=spec.degree, ring=spec.window, capacity=spec.capacity,
                backend=backend, interpret=interpret, bq=bq,
                min_bucket=min_bucket)
        elif agg in AGGS_2D:
            if agg == "count2d":
                xs, ys = (np.asarray(a, np.float64) for a in data)
                ws = None
            else:
                xs, ys, ws = (np.asarray(a, np.float64) for a in data)
            if spec.lsm:
                self.dyn = LsmEngine2D(
                    xs, ys, ws, agg=agg, deg=spec.degree,
                    delta=spec.budget.delta(agg), backend=backend,
                    interpret=interpret, capacity=spec.capacity,
                    growth=spec.growth, background=spec.background,
                    auto_refit=spec.auto_refit, bq=bq,
                    min_bucket=min_bucket)
            elif spec.dynamic:
                idx = build_index_2d(xs, ys, measures=ws, agg=agg,
                                     deg=spec.degree,
                                     delta=spec.budget.delta(agg))
                self.dyn = DynamicEngine2D(
                    idx, backend=backend, interpret=interpret,
                    capacity=spec.capacity, background=spec.background,
                    auto_refit=spec.auto_refit, bq=bq,
                    min_bucket=min_bucket)
            else:
                idx = build_index_2d(xs, ys, measures=ws, agg=agg,
                                     deg=spec.degree,
                                     delta=spec.budget.delta(agg))
                self._static_plan = build_plan_2d(idx)
            if spec.shards is not None:
                self.sharded = ShardedEngine2D(spec.shards,
                                               min_bucket=min_bucket,
                                               interpret=interpret)
        else:
            keys, meas = data
            keys = np.asarray(keys, np.float64)
            meas = None if meas is None else np.asarray(meas, np.float64)
            if spec.lsm:
                self.dyn = LsmEngine(
                    keys, meas, agg=agg, deg=spec.degree,
                    delta=spec.budget.delta(agg), backend=backend,
                    interpret=interpret, capacity=spec.capacity,
                    growth=spec.growth, background=spec.background,
                    auto_refit=spec.auto_refit, bq=bq,
                    min_bucket=min_bucket)
            elif spec.dynamic:
                idx = build_index_1d(keys, meas, agg, deg=spec.degree,
                                     delta=spec.budget.delta(agg))
                self.dyn = DynamicEngine(
                    idx, backend=backend, interpret=interpret,
                    capacity=spec.capacity, background=spec.background,
                    auto_refit=spec.auto_refit, bq=bq,
                    min_bucket=min_bucket)
            else:
                idx = build_index_1d(keys, meas, agg, deg=spec.degree,
                                     delta=spec.budget.delta(agg))
                self._static_plan = build_plan(idx)
            if spec.shards is not None:
                self.sharded = ShardedEngine(spec.shards,
                                             min_bucket=min_bucket,
                                             interpret=interpret)

    def place(self, device) -> None:
        """Commit the serving state, built on the host, to ``device``; a
        sharded table then partitions the placed plan over its mesh."""
        if self._static_plan is not None:
            self._static_plan = jax.device_put(self._static_plan, device)
        for engine in (self.dyn, self.win):
            if engine is not None:
                engine.place(device)
        if self.sharded is not None:
            self.sharded.shard(self.plan)   # warm the partition cache

    @property
    def plan(self):
        if self.win is not None:
            raise RuntimeError(
                f"table {self.name!r} is windowed — there is no single "
                "plan; take window_plan(t0, t1) snapshots instead")
        return self.dyn.plan if self.dyn is not None else self._static_plan

    def snapshot(self):
        """Immutable (plan, delta-buffer) pair; ``()`` buffer when static."""
        if self.dyn is not None:
            return self.dyn.snapshot()
        return self._static_plan, ()

    def size_bytes(self) -> int:
        if self.win is not None:
            return sum(lvl.plan.size_bytes()
                       for _, lvl in self.win._ring if lvl is not None)
        return self.plan.size_bytes()

    def resolve_rel(self, rel) -> Optional[float]:
        return self.spec.budget.rel if rel is DEFAULT_REL else rel

    @property
    def kind(self) -> str:
        """The range-query kind this table's aggregate answers."""
        return KIND_OF_AGG[self.spec.agg]

    def staleness(self, kind: str, params: Tuple) -> int:
        if kind == "window":
            return max(0, self.win.epoch - params[1])
        if self.dyn is not None:
            return int(getattr(self.dyn, "n_pending",
                               getattr(self.dyn, "_n_pending", 0)))
        return 0


class PolyFit:
    """A fitted PolyFit session — construct with :meth:`fit`."""

    def __init__(self, tables: Dict[str, _Table], *, backend: str,
                 interpret: Optional[bool], bq: int, min_bucket: int):
        self._tables = tables
        self.backend = backend
        self.interpret = interpret
        self.bq = bq
        self.min_bucket = min_bucket

    # -- construction ----------------------------------------------------

    @classmethod
    def fit(cls, datasets: Mapping, specs: Mapping[str, TableSpec], *,
            backend: str = "xla", interpret: Optional[bool] = None,
            bq: int = DEFAULT_BQ, min_bucket: int = 64) -> "PolyFit":
        """Build one index per named table and return the query session.

        ``datasets`` maps table name -> data: a bare key array (COUNT),
        ``(keys, measures)`` for SUM/MAX/MIN, ``(xs, ys)`` for 2-key COUNT.
        ``specs`` maps the same names to ``TableSpec``s; the spec's
        ``ErrorBudget`` is the only source of build deltas.  ``interpret``
        (Pallas interpret mode) defaults to the platform's answer —
        interpret on CPU hosts, compiled kernels on the chip — and the
        resolved value is what every table of the session runs with.
        """
        interpret = resolve_interpret(interpret)
        missing = set(datasets) ^ set(specs)
        if missing:
            raise ValueError(f"datasets and specs disagree on tables: "
                             f"{sorted(missing)}")
        jobs = {}
        for name, spec in specs.items():
            data = datasets[name]
            if spec.agg == "count2d":
                if not (isinstance(data, tuple) and len(data) == 2):
                    raise ValueError(f"table {name!r}: count2d data must be "
                                     "(xs, ys)")
            elif spec.agg in ("sum2d", "max2d", "min2d"):
                if not (isinstance(data, tuple) and len(data) == 3):
                    raise ValueError(f"table {name!r}: {spec.agg} data must "
                                     "be (xs, ys, measures)")
            elif spec.agg == "count":
                if not isinstance(data, tuple):
                    data = (data, None)
                elif len(data) == 1:
                    data = (data[0], None)
            elif not (isinstance(data, tuple) and len(data) == 2):
                raise ValueError(f"table {name!r}: {spec.agg} data must be "
                                 "(keys, measures)")
            jobs[name] = (spec, data)

        # construction is f64 host work wherever the session runs: each
        # table builds with the host CPU as JAX's default device, then its
        # finished serving state is committed to the serving device once
        host = jax.local_devices(backend="cpu")[0]
        serving = jax.devices()[0]

        def build(name: str) -> _Table:
            t0 = time.perf_counter()
            with jax.default_device(host):   # thread-local: set per build
                table = _Table(name, *jobs[name], backend=backend,
                               interpret=interpret, bq=bq,
                               min_bucket=min_bucket)
            table.place(serving)
            table.build_seconds = time.perf_counter() - t0
            return table

        # tables are independent, and their host fitting (HiGHS LPs, numpy
        # sorts) releases the GIL: building them side by side cuts the
        # set-up wall time at deployment sizes
        with ThreadPoolExecutor(max_workers=max(1, len(jobs))) as pool:
            tables = dict(zip(jobs, pool.map(build, jobs)))
        return cls(tables, backend=backend, interpret=interpret, bq=bq,
                   min_bucket=min_bucket)

    # -- introspection ---------------------------------------------------

    @property
    def tables(self) -> Tuple[str, ...]:
        return tuple(self._tables)

    def spec(self, table: str) -> TableSpec:
        return self._table(table).spec

    def budget(self, table: str) -> ErrorBudget:
        return self._table(table).spec.budget

    def plan(self, table: str):
        """The table's current device plan (fresh after dynamic merges)."""
        return self._table(table).plan

    def size_bytes(self) -> Dict[str, int]:
        return {k: t.size_bytes() for k, t in self._tables.items()}

    def build_seconds(self) -> Dict[str, float]:
        """Wall seconds each table took to fit (tables fit concurrently,
        so these overlap; their max bounds the session's set-up time)."""
        return {k: t.build_seconds for k, t in self._tables.items()}

    def _table(self, name: str) -> _Table:
        t = self._tables.get(name)
        if t is None:
            raise KeyError(f"unknown table {name!r}; fitted tables: "
                           f"{sorted(self._tables)}")
        return t

    # -- serving hooks (repro.serve.engine) -------------------------------

    def snapshot(self, table: str):
        """The table's current immutable (plan, delta-buffer) pair.

        Static tables return ``()`` for the buffer so callers can pass the
        pair straight into a :func:`~repro.engine.fused_executor` callable
        regardless of dynamism.  The pair never mutates — merges install a
        *new* plan object — so it is safe to hold across a dispatch.
        """
        return self._table(table).snapshot()

    def resolve_rel(self, table: str,
                    rel=DEFAULT_REL) -> Optional[float]:
        """Concrete eps_rel for ``table``: the budget's default unless a
        per-request override is given."""
        return self._table(table).resolve_rel(rel)

    def is_sharded(self, table: str) -> bool:
        return self._table(table).sharded is not None

    def is_lsm(self, table: str) -> bool:
        """True when the table is a tiered level ladder (``lsm=True``)."""
        return self._table(table).spec.lsm

    def on_plan_swap(self, table: str, fn) -> None:
        """Register ``fn(incoming_plan)`` to run on the merge/compaction
        thread immediately *before* a refit installs the new plan (or
        ladder).  The serving engine uses this to AOT-lower the incoming
        plan's warmed bucket sizes so post-swap dispatches never pay a
        relower; a listener exception aborts the install and surfaces as
        the table's refit error."""
        self._dyn(table).add_install_listener(fn)

    def admission_class(self, table: str) -> Tuple[Optional[float], int]:
        """The table's serving guarantee class ``(deadline, priority)``
        (``TableSpec.deadline``/``priority``) — the serving engine's
        per-request defaults for admission deadlines and load shedding."""
        spec = self._table(table).spec
        return spec.deadline, spec.priority

    def serving_executor(self, table: str, eps_rel: Optional[float], *,
                         bq: Optional[int] = None, kind: str = "range"):
        """An un-jitted ``fn(plan, buf, *padded_ranges)`` for ``table``
        with this session's backend statics closed over — the unit the
        serving engine AOT-lowers per bucket size.  ``bq`` overrides the
        session block size (callers pass ``min(session.bq, bucket)`` to
        match the in-session executors bit for bit).  ``kind='quantile'``
        returns the CF-inversion executor ``fn(plan, buf, padded_qs)``
        instead of the range one."""
        t = self._table(table)
        if kind == "quantile":
            return fused_quantile_executor(t.dyn is not None,
                                           backend=self.backend,
                                           interpret=self.interpret,
                                           bq=self.bq if bq is None else bq,
                                           deg=t.spec.degree)
        return fused_executor(t.spec.agg, t.dyn is not None,
                              backend=self.backend, eps_rel=eps_rel,
                              interpret=self.interpret,
                              bq=self.bq if bq is None else bq,
                              deg=t.spec.degree)

    def resolve_spec(self, spec: QuerySpec):
        """Validated ``(kind, eps_rel, params)`` grouping coordinates for a
        spec — the serving engine's admission-time resolution (quantiles
        force ``eps_rel=None``; legacy kind-less specs resolve from the
        table's aggregate)."""
        return self._resolve(spec)

    def resolve_kind(self, table: str, kind: Optional[str]) -> str:
        """Concrete query kind for ``table``: an explicit spec kind wins,
        a legacy ``None`` resolves from the table's aggregate."""
        return self._table(table).kind if kind is None else kind

    def is_window(self, table: str) -> bool:
        """True when the table is an epoch-ring (``TableSpec.window``)."""
        return self._table(table).win is not None

    def window_bound(self, table: str, t0: int, t1: int) -> float:
        """Certified Q_abs bound of a [t0, t1] window answer."""
        return self._win(table).bound(t0, t1)

    def window_snapshot(self, table: str, t0: int, t1: int):
        """Atomic (LsmPlan-or-None, buf-or-None) snapshot of a window —
        what external executors (serving) evaluate against."""
        return self._win(table).window_plan(t0, t1)

    # -- queries ---------------------------------------------------------

    def query(self, request: Request):
        """Answer a request batch, preserving request order.

        A single ``QuerySpec`` returns its :class:`Answer`; a
        ``QueryBatch`` (or a sequence of specs) returns a list of
        ``Answer``s aligned with the specs.  Specs are grouped by
        (table, kind, guarantee, params); each group enters one fused
        jitted executor.  Legacy kind-less specs resolve their kind from
        the table's aggregate, so pre-redesign call sites group (and
        answer) exactly as before.
        """
        if isinstance(request, QuerySpec):
            kind, rel, params = self._resolve(request)
            res = self._exec_group(request.table, kind, request.ranges,
                                   rel, params)
            return self._wrap(request.table, kind, params, res)
        specs = list(request.specs if isinstance(request, QueryBatch)
                     else request)
        if not specs:
            return []
        groups: Dict[Tuple, List[int]] = {}
        resolved = []
        for i, spec in enumerate(specs):
            if not isinstance(spec, QuerySpec):
                raise TypeError(f"expected QuerySpec, got {type(spec)}")
            kind, rel, params = self._resolve(spec)
            resolved.append((kind, rel, params))
            groups.setdefault((spec.table, kind, rel, params),
                              []).append(i)
        out: List[Optional[Answer]] = [None] * len(specs)
        for (table, kind, rel, params), idxs in groups.items():
            # jnp.concatenate keeps device-resident sub-batches on device
            # (and is a cheap host concat for numpy ranges)
            ranges = tuple(
                jnp.concatenate([jnp.asarray(specs[i].ranges[j])
                                 for i in idxs])
                if len(idxs) > 1 else specs[idxs[0]].ranges[j]
                for j in range(len(specs[idxs[0]].ranges)))
            res = self._exec_group(table, kind, ranges, rel, params)
            off = 0
            for i in idxs:
                m = len(specs[i])
                part = type(res)(*(f[off:off + m] for f in res))
                out[i] = self._wrap(table, kind, params, part)
                off += m
        return out

    def _resolve(self, spec: QuerySpec):
        """Validate a spec against its table and return the concrete
        ``(kind, eps_rel, params)`` grouping coordinates."""
        t = self._table(spec.table)
        kind = spec.kind
        if kind is None:
            kind = t.kind        # legacy spec: the table names the query
        if kind == "quantile":
            if t.spec.agg not in ("sum", "count") or t.spec.window:
                raise ValueError(
                    f"table {spec.table!r} ({t.spec.agg}"
                    f"{', windowed' if t.spec.window else ''}) cannot "
                    "answer quantiles; they invert 1-D SUM/COUNT tables")
            if t.spec.lsm:
                raise ValueError(
                    f"table {spec.table!r} is LSM-tiered; quantile "
                    "inversion needs a single fitted CF (flush to a "
                    "dynamic or static table)")
            return kind, None, ()    # no refinement path: one group per q
        if kind == "window":
            if t.win is None:
                raise ValueError(
                    f"table {spec.table!r} is not windowed; fit it with "
                    "TableSpec(window=<ring>) to take window queries")
            return kind, t.resolve_rel(spec.rel), spec.params
        if t.win is not None:
            raise ValueError(
                f"table {spec.table!r} is windowed; use "
                "QuerySpec.window(..., t0, t1) to name the epoch range")
        if kind != t.kind:
            raise ValueError(
                f"table {spec.table!r} ({t.spec.agg}) answers "
                f"{t.kind!r} queries, spec asks for {kind!r}")
        if len(spec.ranges) != t.spec.n_ranges:
            raise ValueError(
                f"table {spec.table!r} ({t.spec.agg}) takes "
                f"{t.spec.n_ranges} range coordinates, spec has "
                f"{len(spec.ranges)}")
        return kind, t.resolve_rel(spec.rel), ()

    def _exec_group(self, table: str, kind: str, ranges, eps_rel, params):
        t = self._table(table)
        if kind == "quantile":
            (qs,) = ranges
            if t.sharded is not None:
                plan, buf = t.snapshot()
                return t.sharded.quantile(plan, qs, buf=buf or None)
            if t.dyn is not None:
                return t.dyn.quantile(qs)
            return execute_quantile(t.plan, jnp.asarray(qs),
                                    backend=self.backend,
                                    interpret=self.interpret, bq=self.bq,
                                    min_bucket=self.min_bucket)
        if kind == "window":
            return t.win.query(*ranges, *params, eps_rel=eps_rel)
        if t.sharded is not None:
            if t.dyn is not None:
                plan, buf = t.dyn.snapshot()
                return t.sharded.query(plan, *ranges, eps_rel=eps_rel,
                                       buf=buf)
            return t.sharded.query(t.plan, *ranges, eps_rel=eps_rel)
        if t.dyn is not None:
            return t.dyn.query(*ranges, eps_rel=eps_rel)
        return execute(t.plan, tuple(jnp.asarray(r) for r in ranges),
                       backend=self.backend, eps_rel=eps_rel,
                       interpret=self.interpret, bq=self.bq,
                       min_bucket=self.min_bucket)

    def _wrap(self, table: str, kind: str, params, res) -> Answer:
        t = self._table(table)
        stale = t.staleness(kind, params)
        if kind == "quantile":
            return Answer(res.answer, res.answer,
                          jnp.zeros(res.answer.shape, bool),
                          bound=(res.lo, res.hi), staleness=stale)
        bound = (t.win.bound(*params) if kind == "window"
                 else t.spec.budget.bound(t.spec.agg))
        return Answer(res.answer, res.approx, res.refined, bound=bound,
                      staleness=stale)

    # -- updates (dynamic tables) ----------------------------------------

    def _dyn(self, table: str):
        t = self._table(table)
        if t.dyn is None:
            raise RuntimeError(f"table {table!r} is static; fit it with "
                               "TableSpec(dynamic=True) to take updates")
        return t.dyn

    def insert(self, table: str, *args) -> None:
        """Buffer new records: (keys[, measures]) for 1-D tables,
        (xs, ys) for 2-key COUNT.  Queries fold them in exactly."""
        self._dyn(table).insert(*args)

    def delete(self, table: str, *args) -> None:
        """Buffer delete tombstones for existing records."""
        self._dyn(table).delete(*args)

    def flush(self, table: Optional[str] = None) -> None:
        """Merge buffered updates into fresh plans (all tables default)."""
        names = [table] if table is not None else [
            k for k, t in self._tables.items() if t.dyn is not None]
        for name in names:
            self._dyn(name).flush()

    # -- windowed tables --------------------------------------------------

    def _win(self, table: str) -> WindowEngine:
        t = self._table(table)
        if t.win is None:
            raise RuntimeError(f"table {table!r} is not windowed; fit it "
                               "with TableSpec(window=<ring>) to stream "
                               "epochs")
        return t.win

    def ingest(self, table: str, keys, measures=None) -> None:
        """Append rows to a windowed table's open epoch (exact until
        sealed by :meth:`advance_epoch`)."""
        self._win(table).ingest(keys, measures)

    def advance_epoch(self, table: str) -> int:
        """Seal the open epoch into an immutable fitted plan on the ring;
        returns the new open epoch id."""
        return self._win(table).advance()

    def epoch(self, table: str) -> int:
        """The windowed table's current open epoch id."""
        return self._win(table).epoch
