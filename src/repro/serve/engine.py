"""Continuous-batching serving engine over a ``PolyFit`` session
(DESIGN.md §13, fault model §14).

``ServingEngine`` turns the synchronous session facade into a traffic
engine with three moving parts:

* **Bounded request queue + admission batching.**  ``submit`` enqueues a
  read and returns a future; background worker threads drain the queue,
  coalesce whatever is waiting (up to ``max_batch`` queries) into groups
  keyed on (table, guarantee, deadline class), assemble each group's
  ranges on the host as one ``(k, bucket)`` array padded to its
  power-of-two bucket, and answer every caller's future from one device
  dispatch: one transfer in, one host copy out (or the device arrays as
  they are, for a lone request that fills its bucket).  The executors
  are elementwise per query, so coalesced answers are bit-identical to
  serial execution of the same requests.
  Admission is ``'block'`` (default: ``submit`` waits for room) or
  ``'reject'`` (``QueueFull`` when the queue is at capacity).

* **AOT executable cache.**  Each (table, guarantee, bucket) is served by
  a ``jax.jit(fn).lower(plan, buf, q).compile()`` executable, so the
  steady state never re-traces: admission batching maps every batch shape
  onto the cached bucket ladder.  Compiled objects pin the plan's static
  metadata (``delta``/``h``/``n`` change on every merge), so entries are
  keyed by plan identity and recompiled on plan swap — the plan-swap
  protocol is simply "readers snapshot, the cache invalidates on
  mismatch".  ``warmup`` eagerly compiles the full bucket ladder per
  table instead of a single shape.  Two refinements on top of that
  protocol: (a) LSM tables (``TableSpec(lsm=True)``) are served through
  ``execute_lsm`` with one executable *per level*, keyed
  (table, guarantee, bucket, slot) — a compaction invalidates only the
  rebuilt slots' entries, surviving levels keep serving their compiled
  code; (b) the engine registers a ``session.on_plan_swap`` listener per
  dynamic table, so the merge/compaction thread AOT-lowers the incoming
  plan (or preview ladder) for every warmed bucket *before* the atomic
  install — post-swap dispatches promote the staged executable
  (``aot_promotions``) instead of paying a relower.

* **Async insert pipeline with a write-ahead journal.**  ``insert``/
  ``delete`` append to a host-side journal and return immediately
  (``wait=False``); a background updater thread drains the *un-applied
  suffix*, coalescing consecutive same-(table, op) runs into few engine
  calls — one fused jitted append per capacity-sized, item-aligned chunk
  — and marks each item applied only after its chunk lands.  A crashed
  updater therefore replays exactly the un-applied suffix on restart,
  preserving the whole-chunk-prefix visibility order readers rely on.
  Per-table submission order is preserved; ``wait=True`` blocks until
  the caller's records are query-visible.

Fault-tolerance hardening (``repro.dist.fault_tolerance``):

* **Deadlines.**  ``submit(spec, deadline=...)`` (or a per-table default
  from ``TableSpec.deadline``) rejects requests whose deadline expires
  while queued with ``DeadlineExceeded`` *before* wasting a dispatch;
  the deadline class joins the coalescing key, so a tight-deadline
  request is never padded into — or dispatched behind — a slack batch
  (groups dispatch earliest-deadline-first).

* **Supervised threads.**  Workers and the updater heartbeat into a
  ``HeartbeatMonitor``; a supervisor thread restarts crashed threads, a
  crash fails only the in-flight group's futures (never the whole
  queue), and crash/restart counts surface in ``EngineStats``.

* **Graceful degradation.**  ``shed_watermark`` arms a load-shedding
  ladder: the queue capacity beyond the watermark is reserved for
  higher-priority guarantee classes (class p may fill a
  ``w + (1-w)(1 - 2^-p)`` fraction), so the lowest class sheds first
  (``Overloaded``).  While the updater is down, reads keep serving from
  the last installed plan snapshot; each answered future carries
  ``.staleness`` — the acknowledged-but-unapplied record count for its
  table at dispatch time.  An optional ``RetryPolicy`` retries transient
  dispatch failures with backoff before failing the group.

* **Failure injection.**  An optional ``FailureInjector`` is consulted at
  three sites — ``serve.worker`` (thread crash with requests in flight),
  ``serve.dispatch`` (transient dispatch failure, retried), and
  ``serve.updater`` (updater crash between fused applies) — which is how
  the chaos harness (tests/chaos_serve.py, bench_serve --chaos) drives
  crash storms through the real code paths.

Sharded tables (``TableSpec(shards=N)``) fall back to the session's
shard_map executors, which carry their own cache; everything else goes
through the AOT path.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from .. import spans
from ..api.session import Answer
from ..api.spec import DEFAULT_REL, QueryBatch, QuerySpec
from ..core.queries import QueryResult
from ..dist.fault_tolerance import HeartbeatMonitor
from ..engine import execute_lsm, level_executor, pad_fills, refine_slots
from ..engine.engine import _bucket_size

__all__ = ["ServingEngine", "QueueFull", "Overloaded", "DeadlineExceeded",
           "EngineStats"]


class QueueFull(RuntimeError):
    """``admission='reject'`` and the bounded request queue is at capacity."""


class Overloaded(QueueFull):
    """Shed by the degradation ladder: the queue is past the watermark and
    this request's priority class has no reserved headroom left."""


class DeadlineExceeded(TimeoutError):
    """The request's admission deadline expired while it was queued."""


@dataclasses.dataclass
class EngineStats:
    """Monotonic counters; read a consistent copy via ``engine.stats``."""

    submitted: int = 0        # read requests accepted into the queue
    rejected: int = 0         # read requests refused by admission='reject'
    shed: int = 0             # read requests shed by the priority ladder
    answered: int = 0         # read requests resolved by a dispatch
    deadline_expired: int = 0  # queued requests expired before dispatch
    dispatches: int = 0       # device dispatches serving reads
    coalesced: int = 0        # requests that shared a dispatch with others
    stale_reads: int = 0      # answers served with unapplied updates pending
    aot_compiles: int = 0     # executables lowered+compiled on dispatch
    aot_hits: int = 0         # dispatches served from the cache
    aot_invalidations: int = 0  # cache entries dropped on plan swap
    aot_precompiles: int = 0  # executables staged on the merge thread
    aot_promotions: int = 0   # staged executables promoted at dispatch
    aot_precompile_failures: int = 0  # merge-thread pre-compiles that raised
    staged_records: int = 0   # update records accepted into the journal
    fused_applies: int = 0    # engine insert/delete calls made by drains
    worker_crashes: int = 0   # worker threads that died mid-batch
    updater_crashes: int = 0  # updater threads that died mid-drain
    restarts: int = 0         # threads respawned by the supervisor
    journal_replayed: int = 0  # items a restarted updater found un-applied
    scatter_host_copies: int = 0  # dispatches answered from one host copy
    scatter_passthrough: int = 0  # lone full-bucket requests, no copy
    refine_overflows: int = 0  # static SUM/COUNT dispatches that refined
    #                            every row (pass-through: with spans on)


class _ReadRequest:
    __slots__ = ("table", "kind", "rel", "ranges", "params", "n", "future",
                 "deadline", "dclass", "priority", "rid", "t_put", "t_taken")

    def __init__(self, table: str, rel, ranges: Tuple, n: int,
                 deadline: Optional[float] = None,
                 dclass: Optional[int] = None, priority: int = 0,
                 kind: str = "count", params: Tuple = ()):
        self.table = table
        self.kind = kind            # resolved query kind (never None)
        self.rel = rel
        self.ranges = ranges
        self.params = params        # static kind params ((t0, t1) windows)
        self.n = n
        self.deadline = deadline    # absolute monotonic, or None
        self.dclass = dclass        # pow-2 bucket of the deadline duration
        self.priority = priority
        self.future: Future = Future()
        # the ``polyfit.serve.queued`` span, stamped only while spans are on
        self.rid = -1
        self.t_put = -1
        self.t_taken = -1

    def taken(self) -> None:
        if self.t_put >= 0:
            self.t_taken = time.perf_counter_ns()


class _WriteItem:
    __slots__ = ("table", "kind", "args", "n", "future", "seq")

    def __init__(self, table: Optional[str], kind: str, args: Tuple,
                 n: int):
        self.table = table
        self.kind = kind            # 'insert' | 'delete' | 'barrier'
        self.args = args
        self.n = n
        self.seq = -1               # assigned by the journal
        self.future: Future = Future()


class _UpdateJournal:
    """Write-ahead staging log with an applied watermark.

    ``append`` assigns a monotone sequence number; ``pending`` returns the
    un-applied suffix (items above the watermark, in order); the updater
    calls ``mark_applied`` only after an item's fused chunk has landed on
    the engine, so whatever the updater was holding when it crashed is
    exactly what ``pending`` hands its replacement.  All methods run under
    the engine's staging condition variable.
    """

    __slots__ = ("_items", "_next_seq", "_applied")

    def __init__(self):
        self._items: deque = deque()
        self._next_seq = 0
        self._applied = -1          # every seq <= this has been applied

    def append(self, item: _WriteItem) -> int:
        item.seq = self._next_seq
        self._next_seq += 1
        self._items.append(item)
        return item.seq

    def pending(self) -> List[_WriteItem]:
        return [it for it in self._items if it.seq > self._applied]

    def mark_applied(self, seq: int) -> None:
        self._applied = max(self._applied, seq)
        while self._items and self._items[0].seq <= self._applied:
            self._items.popleft()

    def depth(self, table: Optional[str] = None) -> int:
        return sum(it.n for it in self._items
                   if it.seq > self._applied
                   and (table is None or it.table == table))


class _ExecEntry:
    """One cached AOT executable plus its staged successor.

    ``plan_ref`` keys validity by identity (plan/level meta changes on
    every swap); ``sig`` guards the pytree *structure* of the non-plan
    operands (a delta buffer growing a victim mask, a level growing a
    tombstone array — an AOT executable pins those shapes).
    ``next_*`` hold the successor staged by the merge-thread
    pre-compilation listener; ``promote`` installs it at dispatch when
    the incoming plan matches, so a swap costs zero relowers.
    ``fills`` (range and quantile entries) are the plan's padding values
    on the host, read from the device once per plan."""

    __slots__ = ("plan_ref", "compiled", "sig", "buf_tmpl", "fills",
                 "next_ref", "next_compiled", "next_sig", "next_fills")

    def __init__(self, plan_ref, compiled, sig=None, buf_tmpl=None,
                 fills=None):
        self.plan_ref = plan_ref    # identity-keyed: meta changes per swap
        self.compiled = compiled
        self.sig = sig
        self.buf_tmpl = buf_tmpl    # ShapeDtypeStruct pytree for relowers
        self.fills = fills
        self.next_ref = None
        self.next_compiled = None
        self.next_sig = None
        self.next_fills = None

    def matches(self, plan_ref, sig) -> bool:
        return self.plan_ref is plan_ref and self.sig == sig

    def stage(self, plan_ref, compiled, sig, fills=None) -> None:
        self.next_ref = plan_ref
        self.next_compiled = compiled
        self.next_sig = sig
        self.next_fills = fills

    def promote(self, plan_ref, sig) -> bool:
        if self.next_ref is plan_ref and self.next_sig == sig:
            self.plan_ref = self.next_ref
            self.compiled = self.next_compiled
            self.sig = self.next_sig
            self.fills = self.next_fills
            self.next_ref = self.next_compiled = self.next_sig = None
            self.next_fills = None
            return True
        return False


def _tree_sig(x) -> Tuple:
    """Hashable (structure, shapes, dtypes) signature of a pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(x)
    return treedef, tuple((l.shape, str(l.dtype)) for l in leaves)


def _refine_overflowed(q: np.ndarray, refined: np.ndarray) -> bool:
    """Whether a static SUM/COUNT dispatch of stacked ranges ``q`` (its
    ``(lq, uq)`` rows) refined every row: more rows were flagged with
    ``lq != uq`` than ``refine_slots`` holds, the executor's own test."""
    need = np.count_nonzero(refined & (q[0] != q[1]))
    return need > refine_slots(q.shape[1])


def _aot_compile(fn, *args):
    """``jax.jit(fn)`` lowered for ``args`` (arrays or shapes) and compiled:
    the one place the engine compiles, on dispatch, warm-up or a plan
    swap."""
    with spans.span("polyfit.aot.compile"):
        return jax.jit(fn).lower(*args).compile()


def _stacked(executor, k: int):
    """``executor(plan, buf, *qs)`` taking its ``k`` query coordinates as
    the rows of one ``(k, bucket)`` operand, so a dispatch makes one
    host-to-device transfer."""
    def fn(plan, buf, q):   # a profile names the module ``jit_fn``
        return executor(plan, buf, *(q[j] for j in range(k)))
    return fn


def _host_fills(plan, kind: str) -> np.ndarray:
    """Each query coordinate's padding value as a host array in the plan's
    dtype: the values ``execute_*`` pad with (quantiles pad with 0.5)."""
    if kind == "quantile":
        return np.full(1, 0.5, plan.dtype)
    return np.asarray(jax.device_get(pad_fills(plan)), plan.dtype)


def _tree_tmpl(x):
    """The pytree with every array leaf abstracted to ShapeDtypeStruct
    (``jax.jit(...).lower`` accepts these in place of concrete arrays)."""
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), x)


class ServingEngine:
    """Queue -> admission batcher -> AOT executable cache over one session.

    ``max_queue`` bounds the read queue (backpressure), ``max_batch`` caps
    the queries coalesced into one dispatch, ``workers`` is the number of
    drain threads (1 keeps dispatch order deterministic).  ``start=False``
    builds the engine without threads — ``submit`` still queues, nothing
    drains — which makes backpressure deterministic to test; call
    ``start()`` to begin serving.

    Fault-tolerance knobs: ``injector`` (a ``FailureInjector`` consulted
    at the serve.worker / serve.dispatch / serve.updater sites),
    ``retry`` (a ``RetryPolicy`` wrapped around dispatches — filter its
    ``retry_on`` to the transient exception classes), ``supervise``
    (restart crashed worker/updater threads; on by default),
    ``heartbeat_deadline`` (seconds without a beat before a thread counts
    as stalled), ``shed_watermark`` (queue fraction where the priority
    ladder starts shedding; ``None`` disables shedding), and
    ``default_deadline`` (admission deadline for requests whose table
    declares none).
    """

    def __init__(self, session, *, max_queue: int = 1024,
                 max_batch: int = 4096, workers: int = 1,
                 admission: str = "block", start: bool = True,
                 injector=None, retry=None, supervise: bool = True,
                 heartbeat_deadline: float = 5.0,
                 shed_watermark: Optional[float] = None,
                 default_deadline: Optional[float] = None):
        if admission not in ("block", "reject"):
            raise ValueError(f"admission must be 'block' or 'reject', "
                             f"got {admission!r}")
        if workers < 1:
            raise ValueError("workers must be >= 1")
        if shed_watermark is not None and not 0.0 < shed_watermark <= 1.0:
            raise ValueError("shed_watermark must be in (0, 1]")
        self.session = session
        self.max_batch = int(max_batch)
        self.admission = admission
        self.supervise = bool(supervise)
        self.shed_watermark = shed_watermark
        self.default_deadline = default_deadline
        self._injector = injector
        self._retry = retry
        self._crash_exc = injector.exc if injector is not None else ()
        self.monitor = HeartbeatMonitor(deadline=heartbeat_deadline)
        self._queue: "queue.Queue[_ReadRequest]" = queue.Queue(max_queue)
        self._cache: Dict[Tuple, _ExecEntry] = {}
        self._compile_lock = threading.Lock()
        self._journal = _UpdateJournal()
        self._staging_cv = threading.Condition()
        self._drain_lock = threading.Lock()
        self._stats = EngineStats()
        self._stats_lock = threading.Lock()
        self._request_ids = itertools.count()
        self._update_errors: List[BaseException] = []
        self._stop = threading.Event()
        self._shut_down = False
        self._closing = False       # shutdown began: refuse new reads
        self._n_workers = int(workers)
        self._thread_lock = threading.Lock()
        self._workers: List[Optional[threading.Thread]] = []
        self._updater: Optional[threading.Thread] = None
        self._supervisor: Optional[threading.Thread] = None
        self._register_swap_listeners()
        if start:
            self.start()

    # -- lifecycle --------------------------------------------------------

    def _spawn_worker(self, i: int) -> threading.Thread:
        t = threading.Thread(target=self._worker_run, args=(i,),
                             daemon=True, name=f"polyfit-serve-{i}")
        t.start()
        return t

    def _spawn_updater(self, replaying: bool) -> threading.Thread:
        t = threading.Thread(target=self._updater_run, args=(replaying,),
                             daemon=True, name="polyfit-update")
        t.start()
        return t

    def start(self) -> None:
        """Spawn the worker + updater (+ supervisor) threads (idempotent)."""
        if self._shut_down:
            raise RuntimeError("engine was shut down")
        with self._thread_lock:
            if self._workers:
                return
            self._workers = [self._spawn_worker(i)
                             for i in range(self._n_workers)]
            self._updater = self._spawn_updater(replaying=False)
            if self.supervise:
                self._supervisor = threading.Thread(
                    target=self._supervisor_loop, daemon=True,
                    name="polyfit-supervise")
                self._supervisor.start()

    @property
    def _threads(self) -> List[threading.Thread]:
        with self._thread_lock:
            out = [t for t in self._workers if t is not None]
            if self._updater is not None:
                out.append(self._updater)
            if self._supervisor is not None:
                out.append(self._supervisor)
            return out

    @property
    def running(self) -> bool:
        return bool(self._threads) and not self._shut_down

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None
                 ) -> None:
        """Stop the engine.  ``drain=True`` answers everything already
        queued (reads) and applies everything staged (writes) first;
        ``drain=False`` cancels queued reads and staged writes with a
        ``RuntimeError``.  Idempotent; a ``submit`` racing shutdown either
        gets served (drain) or resolves with the same error — never
        hangs."""
        if self._shut_down:
            return
        # reads submitted from here on are refused, so the drain below
        # ends once what is queued now is answered
        self._closing = True
        threads = self._threads
        if drain and threads:
            self._queue.join()
            # apply staged writes but never raise deferred errors out of a
            # cleanup path — they stay queued for explicit drain_updates()
            self._drain_updates(raise_errors=False)
        self._shut_down = True
        self._stop.set()
        with self._staging_cv:
            self._staging_cv.notify_all()
        if not drain:
            self._cancel_queued("serving engine shut down")
            self._cancel_staged("serving engine shut down")
        for t in threads:
            t.join(timeout)
        with self._thread_lock:
            self._workers = []
            self._updater = None
            self._supervisor = None
        # a submit may have slipped in between the drain/cancel above and
        # the _shut_down flag landing; nothing serves it now, so sweep —
        # submit() re-checks the flag after its put for the same reason
        self._cancel_queued("serving engine shut down")

    def _cancel_queued(self, msg: str) -> None:
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            if not req.future.done():
                req.future.set_exception(RuntimeError(msg))
            self._queue.task_done()

    def _cancel_staged(self, msg: str) -> None:
        with self._staging_cv:
            items = self._journal.pending()
            for it in items:
                self._journal.mark_applied(it.seq)
        for it in items:
            if not it.future.done():
                if it.kind == "barrier":
                    it.future.set_result(None)
                else:
                    it.future.set_exception(RuntimeError(msg))

    # -- supervision ------------------------------------------------------

    def _supervisor_loop(self) -> None:
        """Restart crashed worker/updater threads until shutdown."""
        while not self._stop.wait(0.02):
            with self._thread_lock:
                if self._stop.is_set() or not self._workers:
                    continue
                restarted = 0
                for i, t in enumerate(self._workers):
                    if t is not None and not t.is_alive():
                        self._workers[i] = self._spawn_worker(i)
                        restarted += 1
                if self._updater is not None and not self._updater.is_alive():
                    self._updater = self._spawn_updater(replaying=True)
                    restarted += 1
            if restarted:
                with self._stats_lock:
                    self._stats.restarts += restarted

    def health(self) -> Dict:
        """Liveness snapshot: thread states, stall list, crash counters,
        journal depth — the supervisor's view, for operators."""
        with self._thread_lock:
            workers_alive = sum(1 for t in self._workers
                                if t is not None and t.is_alive())
            updater_alive = (self._updater is not None
                             and self._updater.is_alive())
        st = self.stats
        out = {
            "running": self.running,
            "workers_alive": workers_alive,
            "updater_alive": updater_alive,
            "stalled": self.monitor.stalled(),
            "queue_depth": self.queue_depth,
            "staged_depth": self.staged_depth,
            "worker_crashes": st.worker_crashes,
            "updater_crashes": st.updater_crashes,
            "restarts": st.restarts,
        }
        if self._retry is not None:
            out["retry"] = {"retries": self._retry.retries,
                            "giveups": self._retry.giveups,
                            "slept": self._retry.slept}
        return out

    def _maybe_fail(self, site: str) -> None:
        if self._injector is not None:
            self._injector.maybe_fail(site)

    # -- reads ------------------------------------------------------------

    def _admission_class(self, table: str) -> Tuple[Optional[float], int]:
        deadline, priority = self.session.admission_class(table)
        if deadline is None:
            deadline = self.default_deadline
        return deadline, int(priority)

    def _shed(self, priority: int) -> bool:
        w = self.shed_watermark
        cap = self._queue.maxsize
        if w is None or cap <= 0:
            return False
        # the (1-w) tail of the queue is reserved in geometric slices for
        # higher priority classes: class p may fill w + (1-w)(1 - 2^-p)
        limit = cap * (w + (1.0 - w) * (1.0 - 2.0 ** (-max(priority, 0))))
        return self._queue.qsize() >= limit

    def submit(self, spec: QuerySpec, *, deadline: Optional[float] = None,
               priority: Optional[int] = None,
               timeout: Optional[float] = None) -> Future:
        """Enqueue one read; the future resolves to its structured
        ``Answer`` (value + certified bound + staleness; ``.staleness`` is
        also set on the future itself for pre-Answer consumers).

        ``deadline`` (seconds from now; default the table's class) bounds
        the *queue wait*: a request still queued when it expires resolves
        with ``DeadlineExceeded`` instead of dispatching.  ``priority``
        picks the shedding rung when the ladder is armed.
        ``admission='block'`` waits up to ``timeout`` for queue room (then
        raises ``QueueFull``); ``'reject'`` raises immediately when full.
        """
        if self._shut_down or self._closing:
            raise RuntimeError("serving engine shut down")
        kind, rel, params = self.session.resolve_spec(spec)
        d_default, p_default = self._admission_class(spec.table)
        if deadline is None:
            deadline = d_default
        if priority is None:
            priority = p_default
        if self._shed(priority):
            with self._stats_lock:
                self._stats.shed += 1
            raise Overloaded(
                f"load shed: queue past watermark "
                f"{self.shed_watermark:.2f} for priority {priority}")
        dclass = (None if deadline is None
                  else max(math.ceil(math.log2(max(deadline, 1e-3))), -10))
        abs_deadline = (None if deadline is None
                        else time.monotonic() + deadline)
        req = _ReadRequest(spec.table, rel, spec.ranges, len(spec),
                           abs_deadline, dclass, priority, kind=kind,
                           params=params)
        if spans.enabled():
            req.rid = next(self._request_ids)
            req.t_put = time.perf_counter_ns()
        try:
            if self.admission == "reject":
                self._queue.put_nowait(req)
            else:
                self._queue.put(req, timeout=timeout)
        except queue.Full:
            with self._stats_lock:
                self._stats.rejected += 1
            raise QueueFull(f"request queue at capacity "
                            f"({self._queue.maxsize})") from None
        with self._stats_lock:
            self._stats.submitted += 1
        if self._shut_down:
            # raced shutdown's final sweep: make sure this future resolves
            self._cancel_queued("serving engine shut down")
        return req.future

    def query(self, request: Union[QuerySpec, QueryBatch,
                                   Sequence[QuerySpec]],
              *, timeout: Optional[float] = None):
        """Blocking convenience mirroring ``session.query``: one spec
        returns its ``Answer``, a batch returns the aligned list."""
        if isinstance(request, QuerySpec):
            return self.submit(request).result(timeout)
        specs = list(request.specs if isinstance(request, QueryBatch)
                     else request)
        futures = [self.submit(s) for s in specs]
        return [f.result(timeout) for f in futures]

    def serve(self, table: str, *ranges, rel=DEFAULT_REL,
              timeout: Optional[float] = None):
        """Blocking single-request endpoint: ``serve('count', lq, uq)``."""
        res = self.submit(QuerySpec(table, ranges, rel)).result(timeout)
        jax.block_until_ready(res.answer)
        return res

    # -- worker: drain, coalesce, dispatch --------------------------------

    def _worker_run(self, wid: int) -> None:
        """Thread body: loop until stop; on crash, die quietly (the
        supervisor restarts; the crash already failed only the in-flight
        batch inside ``_worker_loop``)."""
        name = f"worker-{wid}"
        try:
            self._worker_loop(name)
        except BaseException:
            with self._stats_lock:
                self._stats.worker_crashes += 1
        finally:
            self.monitor.forget(name)

    def _worker_loop(self, name: str) -> None:
        q = self._queue
        while True:
            self.monitor.beat(name)
            try:
                req = q.get(timeout=0.05)
            except queue.Empty:
                if self._stop.is_set():
                    return
                continue
            req.taken()
            batch = [req]
            try:
                with spans.span("polyfit.serve.batch"):
                    # chaos site: a crash here has requests in flight — fail
                    # exactly those futures, account the queue, then die
                    self._maybe_fail("serve.worker")
                    budget = self.max_batch - req.n
                    while budget > 0:
                        # peek so the admission batch never overshoots
                        # max_batch — overshoot would hit a bucket above the
                        # warmed ladder
                        with q.mutex:
                            if not q.queue or q.queue[0].n > budget:
                                break
                        try:
                            nxt = q.get_nowait()
                        except queue.Empty:
                            break
                        nxt.taken()
                        batch.append(nxt)
                        budget -= nxt.n
                    self._process_batch(batch)
            except BaseException as e:
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(e)
                raise
            finally:
                for _ in batch:
                    q.task_done()

    def _process_batch(self, batch: List[_ReadRequest]) -> None:
        # admission deadlines: expire pre-dispatch, never waste the device
        now = time.monotonic()
        live: List[_ReadRequest] = []
        expired = 0
        for r in batch:
            if r.deadline is not None and now > r.deadline:
                if not r.future.done():
                    r.future.set_exception(DeadlineExceeded(
                        f"deadline expired after "
                        f"{now - r.deadline:.3f}s in queue"))
                expired += 1
            else:
                live.append(r)
        if expired:
            with self._stats_lock:
                self._stats.deadline_expired += expired
        groups: Dict[Tuple, List[_ReadRequest]] = {}
        for r in live:
            # the deadline class keys the group: tight requests are never
            # padded into (or billed for) a slack batch's bucket; kind and
            # its static params key it too — a quantile never coalesces
            # into a range bucket, nor one window into another's epochs
            groups.setdefault((r.table, r.kind, r.rel, r.dclass, r.params),
                              []).append(r)
        # earliest-deadline-first across the batch's groups
        ordered = sorted(
            groups.items(),
            key=lambda kv: min((r.deadline for r in kv[1]
                                if r.deadline is not None),
                               default=float("inf")))
        for (table, kind, rel, _, params), grp in ordered:
            # count before resolving: a caller that saw its future
            # complete must also see it reflected in ``stats``
            with self._stats_lock:
                self._stats.dispatches += 1
                did = self._stats.dispatches
                self._stats.answered += len(grp)
                if len(grp) > 1:
                    self._stats.coalesced += len(grp)
            for r in grp:
                if r.t_put >= 0:
                    spans.record("polyfit.serve.queued", r.t_put, r.t_taken,
                                 request=r.rid, dispatch=did)
            try:
                with spans.span("polyfit.serve.dispatch", dispatch=did):
                    if self._retry is not None:
                        self._retry.call(self._dispatch, table, kind, rel,
                                         params, grp)
                    else:
                        self._dispatch(table, kind, rel, params, grp)
            except BaseException as e:   # surface on the callers
                for r in grp:
                    if not r.future.done():
                        r.future.set_exception(e)

    def _dispatch(self, table: str, kind: str, rel, params: Tuple,
                  grp: List[_ReadRequest]) -> None:
        self._maybe_fail("serve.dispatch")
        staleness = self.staleness(table)
        if staleness:
            with self._stats_lock:
                self._stats.stale_reads += len(grp)
        res, q = self._execute(table, kind, rel, params, grp, staleness)
        if not isinstance(res, Answer):  # degenerate paths (QueryResult)
            res = Answer(res.answer, res.approx, res.refined,
                         staleness=staleness)
        # a lone request that fills the result takes the device arrays as
        # they are; any other group is sliced from one host copy, so no
        # eager device op (nor its compile) runs per request
        whole = len(grp) == 1 and res.value.shape[0] == grp[0].n
        with spans.span("polyfit.serve.device_wait"):
            if whole:
                jax.block_until_ready(res.value)  # resolve device-ready
            else:
                res = self._host_copy(res)
        overflowed = False
        if q is not None and not whole:
            overflowed = _refine_overflowed(q, res.refined)
        elif q is not None and spans.enabled():
            with spans.span("polyfit.serve.refine_count"):
                overflowed = _refine_overflowed(q, np.asarray(res.refined))
        with self._stats_lock:
            if whole:
                self._stats.scatter_passthrough += 1
            else:
                self._stats.scatter_host_copies += 1
            self._stats.refine_overflows += overflowed
        with spans.span("polyfit.serve.scatter"):
            self._scatter(grp, res, whole)

    def _execute(self, table: str, kind: str, rel, params: Tuple,
                 grp: List[_ReadRequest], staleness: int):
        """Start one group's device work; returns its answers, which may
        not be ready yet, and the stacked ranges where the executable
        compacts its refinement (static SUM/COUNT under Q_rel), else
        None."""
        sess = self.session
        nq = sum(r.n for r in grp)
        size = _bucket_size(nq, sess.min_bucket)
        if kind == "window":
            # epoch-ring tables: the window snapshot *is* a small LSM plan
            # of immutable per-epoch levels — served by the same per-level
            # AOT machinery (sealed epochs never invalidate their entries)
            plan, buf = sess.window_snapshot(table, *params)
            bound = sess.window_bound(table, *params)
            with spans.span("polyfit.serve.prepare"):
                ranges = self._concat_ranges(grp)
            with spans.span("polyfit.serve.execute"):
                if plan is None:
                    return sess.query(QuerySpec(table, ranges, rel,
                                                kind="window",
                                                params=params)), None
                res = execute_lsm(plan, buf, ranges, backend=sess.backend,
                                  eps_rel=rel, interpret=sess.interpret,
                                  bq=sess.bq, min_bucket=sess.min_bucket,
                                  level_runner=self._lsm_runner(
                                      table, rel, size, plan))
            return Answer(res.answer, res.approx, res.refined, bound=bound,
                          staleness=staleness), None
        if sess.is_sharded(table):
            # shard_map executors keep their own cache; no AOT ladder here
            with spans.span("polyfit.serve.prepare"):
                ranges = self._concat_ranges(grp)
            with spans.span("polyfit.serve.execute"):
                return sess.query(QuerySpec(table, ranges, rel, kind=kind,
                                            params=params)), None
        plan, buf = sess.snapshot(table)
        if kind == "quantile":
            with spans.span("polyfit.aot.lookup"):
                compiled, fills = self._executable(table, rel, size, plan,
                                                   buf, kind="quantile")
            with spans.span("polyfit.serve.prepare"):
                q = self._stack_ranges(grp, size, fills)
            with spans.span("polyfit.serve.execute"):
                ans, lo, hi = compiled(plan, buf, q)
            return Answer(ans, ans, np.zeros(size, bool),
                          bound=(lo, hi), staleness=staleness), None
        spec = sess.spec(table)
        bound = sess.budget(table).bound(spec.agg)
        if hasattr(plan, "levels"):
            # LSM ladder: one AOT executable *per level*, fused exactly by
            # execute_lsm's combiner — a compaction only invalidates the
            # rebuilt slots' entries
            with spans.span("polyfit.serve.prepare"):
                ranges = self._concat_ranges(grp)
            with spans.span("polyfit.serve.execute"):
                res = execute_lsm(plan, buf, ranges, backend=sess.backend,
                                  eps_rel=rel, interpret=sess.interpret,
                                  bq=sess.bq, min_bucket=sess.min_bucket,
                                  level_runner=self._lsm_runner(
                                      table, rel, size, plan))
            return Answer(res.answer, res.approx, res.refined, bound=bound,
                          staleness=staleness), None
        with spans.span("polyfit.aot.lookup"):
            compiled, fills = self._executable(table, rel, size, plan, buf)
        with spans.span("polyfit.serve.prepare"):
            q = self._stack_ranges(grp, size, fills)
        with spans.span("polyfit.serve.execute"):
            ans, approx, refined = compiled(plan, buf, q)
        compacts = (rel is not None and not spec.dynamic
                    and spec.agg in ("sum", "count"))
        return (Answer(ans, approx, refined, bound=bound,
                       staleness=staleness), q if compacts else None)

    @staticmethod
    def _concat_ranges(grp: List[_ReadRequest]) -> Tuple:
        """The group's ranges per coordinate, as host arrays."""
        if len(grp) == 1:
            return tuple(grp[0].ranges)
        return tuple(
            np.concatenate([r.ranges[j] for r in grp])
            for j in range(len(grp[0].ranges)))

    @classmethod
    def _stack_ranges(cls, grp: List[_ReadRequest], size: int,
                      fills: np.ndarray) -> np.ndarray:
        """The group's query coordinates as the rows of one host
        ``(k, size)`` array in the plan's dtype, each row's tail padded
        with its coordinate's fill: the executable's one query operand."""
        cols = cls._concat_ranges(grp)
        n = len(cols[0])
        q = np.empty((len(fills), size), fills.dtype)
        q[:, n:] = fills[:, None]
        for j, c in enumerate(cols):
            q[j, :n] = c
        return q

    @staticmethod
    def _slice_answer(a, off: int, m: int) -> "Answer":
        bound = a.bound
        if isinstance(bound, tuple):     # quantile (lo, hi) certificates
            bound = tuple(b[off:off + m] for b in bound)
        return Answer(a.value[off:off + m], a.approx[off:off + m],
                      a.refined[off:off + m], bound=bound,
                      staleness=a.staleness)

    @staticmethod
    def _host_copy(a: "Answer") -> "Answer":
        """The answers (and quantile certificates) as host arrays, from one
        ``device_get`` that starts every copy before it waits."""
        quantile = isinstance(a.bound, tuple)
        arrays = (a.value, a.approx, a.refined) + (a.bound if quantile
                                                   else ())
        got = jax.device_get(arrays)
        return Answer(*got[:3], bound=tuple(got[3:]) if quantile else a.bound,
                      staleness=a.staleness)

    @staticmethod
    def _scatter(grp: List[_ReadRequest], res: "Answer",
                 whole: bool) -> None:
        off = 0
        for r in grp:
            m = r.n
            # per-answer degradation signal: how many acknowledged update
            # records were not yet applied when this answer was computed
            r.future.staleness = res.staleness
            if not r.future.done():
                r.future.set_result(
                    res if whole else ServingEngine._slice_answer(res, off, m))
            off += m

    # -- AOT executable cache ---------------------------------------------

    def _executable(self, table: str, rel, size: int, plan, buf,
                    kind: str = "range"):
        # quantile executables live under their own 4-tuple keys so the
        # range ladder and the inversion ladder never collide (LSM level
        # entries are 4-tuples too, distinguished by an int slot)
        key = ((table, rel, size) if kind == "range"
               else (table, rel, size, "quantile"))
        sig = _tree_sig(buf)
        entry = self._cache.get(key)
        if entry is not None and entry.matches(plan, sig):
            with self._stats_lock:
                self._stats.aot_hits += 1
            return entry.compiled, entry.fills
        with self._compile_lock:
            entry = self._cache.get(key)
            if entry is not None:
                if entry.matches(plan, sig):
                    with self._stats_lock:
                        self._stats.aot_hits += 1
                    return entry.compiled, entry.fills
                if entry.promote(plan, sig):
                    with self._stats_lock:
                        self._stats.aot_promotions += 1
                    return entry.compiled, entry.fills
                with self._stats_lock:
                    self._stats.aot_invalidations += 1
            compiled = self._lower_stacked(table, rel, size, plan, buf, kind)
            fills = _host_fills(plan, kind)
            self._cache[key] = _ExecEntry(plan, compiled, sig=sig,
                                          buf_tmpl=_tree_tmpl(buf),
                                          fills=fills)
            with self._stats_lock:
                self._stats.aot_compiles += 1
            return compiled, fills

    def _lower_stacked(self, table: str, rel, size: int, plan, buf,
                       kind: str):
        """The table's serving executor for one bucket, lowered with the
        stacked query signature ``(plan, buf, q[k, size])``."""
        sess = self.session
        fn = sess.serving_executor(table, rel, bq=min(sess.bq, size),
                                   kind=kind)
        k = sess.spec(table).n_ranges if kind == "range" else 1
        q = jax.ShapeDtypeStruct((k, size), plan.dtype)
        return _aot_compile(_stacked(fn, k), plan, buf, q)

    # -- LSM tables: per-level executables ---------------------------------

    def _lsm_statics(self, rel, size: int, lsm) -> dict:
        """The statics ``execute_lsm`` resolves for this dispatch — the
        per-level executable must be lowered with exactly these so the
        cached call computes the same floats as the default jitted core."""
        sess = self.session
        backend = sess.backend
        if lsm.agg in ("max", "min") \
                and backend in ("pallas", "pallas_scan", "ref") \
                and any(l.plan.deg > 3 for l in lsm.levels):
            backend = "xla"   # mirrors execute_lsm's extremal downgrade
        return dict(backend=backend, interpret=sess.interpret,
                    bq=min(sess.bq, size), with_truth=rel is not None)

    @staticmethod
    def _lower_level(lvl, agg: str, statics: dict, size: int, k: int):
        fn = level_executor(agg, **statics)
        qs = [jax.ShapeDtypeStruct((size,), lvl.plan.dtype)] * k
        return _aot_compile(fn, lvl, *qs)

    def _level_executable(self, table: str, rel, size: int, lvl, agg: str,
                          statics: dict, k: int):
        key = (table, rel, size, lvl.slot)
        sig = _tree_sig(lvl)
        entry = self._cache.get(key)
        if entry is not None and entry.matches(lvl.plan, sig):
            with self._stats_lock:
                self._stats.aot_hits += 1
            return entry.compiled
        with self._compile_lock:
            entry = self._cache.get(key)
            if entry is not None:
                if entry.matches(lvl.plan, sig):
                    with self._stats_lock:
                        self._stats.aot_hits += 1
                    return entry.compiled
                if entry.promote(lvl.plan, sig):
                    with self._stats_lock:
                        self._stats.aot_promotions += 1
                    return entry.compiled
                with self._stats_lock:
                    self._stats.aot_invalidations += 1
            compiled = self._lower_level(lvl, agg, statics, size, k)
            self._cache[key] = _ExecEntry(lvl.plan, compiled, sig=sig)
            with self._stats_lock:
                self._stats.aot_compiles += 1
            return compiled

    def _lsm_runner(self, table: str, rel, size: int, lsm):
        """A ``level_runner`` for ``execute_lsm`` that serves each level
        from the AOT cache (keyed by slot, validated by level identity)."""
        statics = self._lsm_statics(rel, size, lsm)
        k = self.session.spec(table).n_ranges
        agg = lsm.agg

        def runner(i, lvl, *qs):
            with spans.span("polyfit.aot.lookup"):
                compiled = self._level_executable(table, rel, size, lvl, agg,
                                                  statics, k)
            return compiled(lvl, *qs)
        return runner

    # -- plan-swap pre-compilation (merge-thread listener) -----------------

    def _register_swap_listeners(self) -> None:
        """Hook ``session.on_plan_swap`` for every dynamic, unsharded
        table: the merge/compaction thread hands the incoming plan (or
        preview ladder) to ``_precompile`` *before* the atomic install,
        so post-swap dispatches promote staged executables instead of
        relowering."""
        sess = self.session
        hook = getattr(sess, "on_plan_swap", None)
        if hook is None:
            return
        for table in sess.tables:
            if sess.spec(table).dynamic and not sess.is_sharded(table):
                hook(table, self._precompile_listener(table))

    def _precompile_listener(self, table: str):
        def listener(incoming) -> None:
            if self._shut_down:
                return   # a dead engine's cache needs no staged successors
            try:
                self._precompile(table, incoming)
            except Exception:
                # never abort an install: the first post-swap dispatch
                # relowers lazily, and the failure stays visible here
                with self._stats_lock:
                    self._stats.aot_precompile_failures += 1
        return listener

    def _precompile(self, table: str, incoming) -> None:
        sess = self.session
        with self._compile_lock:
            # range keys (table, rel, size), then quantile keys
            # (table, None, size, "quantile")
            keys = sorted((key for key in self._cache if key[0] == table
                           and (len(key) == 3 or key[3] == "quantile")),
                          key=lambda c: (len(c), repr(c[1]), c[2]))
            lsm_combos = sorted({(key[1], key[2]) for key in self._cache
                                 if key[0] == table and len(key) == 4
                                 and key[3] != "quantile"},
                                key=lambda c: (repr(c[0]), c[1]))
        k = sess.spec(table).n_ranges
        if hasattr(incoming, "levels"):
            for rel, size in lsm_combos:
                statics = self._lsm_statics(rel, size, incoming)
                for lvl in incoming.levels:
                    key = (table, rel, size, lvl.slot)
                    sig = _tree_sig(lvl)
                    with self._compile_lock:
                        entry = self._cache.get(key)
                        if entry is not None and (
                                entry.matches(lvl.plan, sig)
                                or (entry.next_ref is lvl.plan
                                    and entry.next_sig == sig)):
                            continue   # surviving level: still valid
                    compiled = self._lower_level(lvl, incoming.agg,
                                                 statics, size, k)
                    with self._compile_lock:
                        entry = self._cache.get(key)
                        if entry is None:
                            entry = self._cache[key] = _ExecEntry(None, None)
                        entry.stage(lvl.plan, compiled, sig)
                    with self._stats_lock:
                        self._stats.aot_precompiles += 1
            return
        for key in keys:
            kind = "range" if len(key) == 3 else "quantile"
            with self._compile_lock:
                entry = self._cache.get(key)
                if entry is None or entry.buf_tmpl is None \
                        or entry.plan_ref is incoming \
                        or entry.next_ref is incoming:
                    continue
                tmpl = entry.buf_tmpl
            compiled = self._lower_stacked(table, key[1], key[2], incoming,
                                           tmpl, kind)
            fills = _host_fills(incoming, kind)
            with self._compile_lock:
                entry = self._cache.get(key)
                if entry is not None:
                    entry.stage(incoming, compiled, _tree_sig(tmpl), fills)
            with self._stats_lock:
                self._stats.aot_precompiles += 1

    def warmup(self, max_bucket: int = 1024,
               tables: Optional[Sequence[str]] = None,
               kinds: Sequence[str] = ("range",)) -> int:
        """Eagerly AOT-compile the full power-of-two bucket ladder
        (``min_bucket`` .. ``max_bucket``) for every (table, default
        guarantee); returns the number of executables compiled.  After
        this, any admitted batch up to ``max_bucket`` queries serves
        without tracing or compiling.  ``kinds`` picks the executor
        ladders: ``'range'`` (the aggregate family) and/or ``'quantile'``
        (CF inversion; skipped on tables that cannot answer quantiles).
        Windowed tables warm lazily — their per-epoch levels compile on
        first touch and sealed epochs never invalidate."""
        sess = self.session
        before = self.stats.aot_compiles
        for table in (tables if tables is not None else sess.tables):
            if sess.is_sharded(table) or sess.is_window(table):
                continue
            spec = sess.spec(table)
            rel = sess.resolve_rel(table)
            plan, buf = sess.snapshot(table)
            size = sess.min_bucket
            while size <= max_bucket:
                if hasattr(plan, "levels"):
                    if "range" in kinds:
                        statics = self._lsm_statics(rel, size, plan)
                        k = spec.n_ranges
                        for lvl in plan.levels:
                            self._level_executable(table, rel, size, lvl,
                                                   plan.agg, statics, k)
                else:
                    if "range" in kinds:
                        self._executable(table, rel, size, plan, buf)
                    if "quantile" in kinds \
                            and spec.agg in ("sum", "count") \
                            and not spec.lsm:
                        self._executable(table, None, size, plan, buf,
                                         kind="quantile")
                size *= 2
        return self.stats.aot_compiles - before

    # -- writes: journal + background drain -------------------------------

    def insert(self, table: str, *args, wait: bool = False) -> None:
        """Stage new records; ``wait=True`` blocks until they are
        query-visible (folded into the table's delta buffer)."""
        self._stage(table, "insert", args, wait)

    def delete(self, table: str, *args, wait: bool = True) -> None:
        """Stage delete tombstones.  Default ``wait=True`` so a bad key
        (``KeyError``: no live occurrence) surfaces to the caller;
        ``wait=False`` defers the error to the next ``flush``."""
        self._stage(table, "delete", args, wait)

    def _stage(self, table: str, kind: str, args: Tuple, wait: bool) -> None:
        if self._shut_down:
            raise RuntimeError("serving engine shut down")
        cols = self._norm_update(table, kind, args)
        item = _WriteItem(table, kind, cols, len(cols[0]))
        with self._staging_cv:
            self._journal.append(item)
            self._staging_cv.notify()
        with self._stats_lock:
            self._stats.staged_records += item.n
        if wait:
            if self._updater is None:   # no updater running: apply inline
                self._drain_once()
            item.future.result()

    def _norm_update(self, table: str, kind: str, args: Tuple) -> Tuple:
        """Host-normalize update args so same-(table, op) runs concat
        columnwise: every column rank-1 float64 of equal length."""
        spec = self.session.spec(table)
        if not spec.dynamic:
            raise RuntimeError(f"table {table!r} is static; fit it with "
                               "TableSpec(dynamic=True) to take updates")
        want = (1 if spec.agg in ("sum", "count", "max", "min")
                else 2) if kind == "delete" else (
            1 if spec.agg == "count" else
            2 if spec.agg in ("sum", "max", "min", "count2d") else 3)
        arrs = [np.atleast_1d(np.asarray(a, np.float64)) for a in args]
        if spec.agg == "count" and kind == "insert" and len(arrs) == 2:
            arrs = arrs[:1]          # engine forces unit measures anyway
        if len(arrs) != want:
            raise ValueError(f"{kind} on {table!r} ({spec.agg}) takes "
                             f"{want} array argument(s), got {len(args)}")
        base = arrs[0].shape
        return tuple(np.broadcast_to(a, base).astype(np.float64, copy=True)
                     for a in arrs)

    def drain_updates(self) -> None:
        """Block until every staged update is applied, then surface the
        oldest deferred write error (one per call, submission order).
        After shutdown this only surfaces deferred errors."""
        self._drain_updates(raise_errors=True)

    def _drain_updates(self, *, raise_errors: bool) -> None:
        if self._shut_down:
            if raise_errors:
                self._raise_update_error()
            return
        barrier = _WriteItem(None, "barrier", (), 0)
        with self._staging_cv:
            self._journal.append(barrier)
            self._staging_cv.notify()
        if self._updater is None or (not self._updater.is_alive()
                                     and self._supervisor is None):
            self._drain_once()
        barrier.future.result()
        if raise_errors:
            self._raise_update_error()

    def flush(self, table: Optional[str] = None) -> None:
        """Drain staging, then merge the tables' delta buffers into fresh
        plans (the AOT cache invalidates itself on the swap)."""
        self.drain_updates()
        self.session.flush(table)

    def _raise_update_error(self) -> None:
        if self._update_errors:
            raise self._update_errors.pop(0)

    def _updater_run(self, replaying: bool) -> None:
        if replaying:
            with self._staging_cv:
                n = len([it for it in self._journal.pending()
                         if it.kind != "barrier"])
            if n:
                with self._stats_lock:
                    self._stats.journal_replayed += n
        try:
            self._updater_loop()
        except BaseException:
            # un-applied suffix stays in the journal; the supervisor's
            # replacement updater replays exactly that
            with self._stats_lock:
                self._stats.updater_crashes += 1
        finally:
            self.monitor.forget("updater")

    def _updater_loop(self) -> None:
        while True:
            self.monitor.beat("updater")
            with self._staging_cv:
                while not self._journal.pending() and not self._stop.is_set():
                    self._staging_cv.wait(timeout=0.1)
            if not self._drain_once() and self._stop.is_set():
                return

    def _drain_once(self) -> bool:
        """Apply the journal's current un-applied suffix; True if any.

        Serialized by ``_drain_lock`` (an inline drain must not race a
        restarting updater into double-applying).  Items are applied in
        sequence order and marked applied chunk by chunk, so an injected
        crash between fused applies leaves exactly the un-applied suffix
        for replay.
        """
        with self._drain_lock:
            with self._staging_cv:
                items = self._journal.pending()
            if not items:
                return False
            # coalesce consecutive same-(table, op) runs; per-table order
            # is global order restricted to the table, so victim
            # resolution and read-your-writes see writes in submission
            # order
            runs: List[List[_WriteItem]] = []
            for it in items:
                if (runs and it.kind != "barrier"
                        and runs[-1][0].kind == it.kind
                        and runs[-1][0].table == it.table):
                    runs[-1].append(it)
                else:
                    runs.append([it])
            applies = 0
            for run in runs:
                head = run[0]
                if head.kind == "barrier":
                    with self._staging_cv:
                        self._journal.mark_applied(head.seq)
                    head.future.set_result(None)
                    continue
                try:
                    applies += self._apply_run(head.table, head.kind, run)
                except self._crash_exc:
                    # injected crash: leave the un-applied suffix in the
                    # journal and die through _updater_run
                    with self._stats_lock:
                        self._stats.fused_applies += applies
                    raise
                except BaseException as e:
                    # permanent engine error: consume the run, defer the
                    # error (submission order) and fail its futures
                    self._update_errors.append(e)
                    with self._staging_cv:
                        for it in run:
                            self._journal.mark_applied(it.seq)
                    for it in run:
                        if not it.future.done():
                            it.future.set_exception(e)
                    continue
            with self._stats_lock:
                self._stats.fused_applies += applies
            return True

    def _apply_run(self, table: str, kind: str,
                   run: List[_WriteItem]) -> int:
        """Apply one same-(table, op) run in capacity-sized, item-aligned
        chunks; each item is marked applied (and its future resolved)
        only after the fused call covering it lands."""
        cap = self.session.spec(table).capacity
        op = self.session.insert if kind == "insert" else self.session.delete
        applies = 0
        pack: List[_WriteItem] = []
        pack_n = 0

        def flush_pack() -> int:
            nonlocal pack, pack_n
            if not pack:
                return 0
            # chaos site: a crash here is *between* fused applies — the
            # journal watermark sits exactly at the last applied item
            self._maybe_fail("serve.updater")
            cols = (pack[0].args if len(pack) == 1 else
                    tuple(np.concatenate([it.args[j] for it in pack])
                          for j in range(len(pack[0].args))))
            n = len(cols[0])
            calls = 0
            for lo in range(0, n, cap):
                op(table, *(c[lo:lo + cap] for c in cols))
                calls += 1
            with self._staging_cv:
                for it in pack:
                    self._journal.mark_applied(it.seq)
            for it in pack:
                if not it.future.done():
                    it.future.set_result(None)
            pack, pack_n = [], 0
            return calls

        for it in run:
            if pack and pack_n + it.n > cap:
                applies += flush_pack()
            pack.append(it)
            pack_n += it.n
        applies += flush_pack()
        return applies

    # -- introspection ----------------------------------------------------

    @property
    def stats(self) -> EngineStats:
        with self._stats_lock:
            return dataclasses.replace(self._stats)

    @property
    def queue_depth(self) -> int:
        return self._queue.qsize()

    @property
    def staged_depth(self) -> int:
        with self._staging_cv:
            return self._journal.depth()

    def staleness(self, table: str) -> int:
        """Acknowledged-but-unapplied update records for ``table`` —
        the per-answer degradation signal while the updater is down."""
        with self._staging_cv:
            return self._journal.depth(table)

    def cache_keys(self) -> Tuple[Tuple, ...]:
        return tuple(sorted(self._cache, key=repr))
