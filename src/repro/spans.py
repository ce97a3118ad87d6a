"""Spans of the serving path, kept in memory for whoever reads them.

Off by default.  Off, ``span()`` checks one module-level flag and hands
back a shared no-op context manager: nothing is allocated, no clock is
read and no profiler call is made.

``enable()`` turns them on until ``disable()``.  While on:

* each span records its name, its start and end (``time.perf_counter_ns``),
  its thread, the index of its parent (the innermost span open on the same
  thread when it started) and the ids it carries, ``request`` and
  ``dispatch`` (-1 when it carries none);
* each ``span()`` also enters ``jax.profiler.TraceAnnotation(name)``, so a
  profiler trace taken meanwhile shows every span on its host plane, on
  the same clock as the device planes;
* one ``jax.monitoring`` listener counts every XLA backend compile against
  the innermost span open on the compiling thread (``none_compiles`` when
  none is open): which step compiled.

Spans are kept in preallocated arrays used as a ring; a span that
overwrites an older one counts the older as ``dropped``, and memory never
grows.  Nothing is written out: ``snapshot()`` hands the arrays, and the
totals per span name, to the reader.

``record(name, t0_ns, t1_ns)`` adds a span timed elsewhere, such as the
wait of a request from the thread that queued it to the one that takes
it; it has no parent and enters no annotation.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import jax
import numpy as np

__all__ = ["COMPILE_EVENT", "enable", "disable", "enabled", "span",
           "record", "snapshot"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _Noop()
_rec: Optional["_Recorder"] = None     # the live recorder while enabled


_FIELDS = np.dtype([("seq", np.int64), ("name", np.int32),
                    ("t0", np.int64), ("t1", np.int64),
                    ("thread", np.int64), ("parent", np.int64),
                    ("request", np.int64), ("dispatch", np.int64),
                    ("compiles", np.int32)])


class _Recorder:
    """The ring: slot ``seq % cap`` holds span number ``seq``; ``t1`` is -1
    while the span is open."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.cap = int(capacity)
        self.ring = np.zeros(self.cap, _FIELDS)
        self.ring["seq"] = -1
        self.n = 0                     # spans ever started
        self.none_compiles = 0
        self.names: list = []
        self.name_ids: dict = {}
        self.threads: dict = {}        # thread ident -> thread name
        self.lock = threading.Lock()
        self.local = threading.local()

    def stack(self) -> list:
        """The open spans of the calling thread, innermost last."""
        st = getattr(self.local, "stack", None)
        if st is None:
            st = self.local.stack = []
            t = threading.current_thread()
            with self.lock:
                self.threads[t.ident] = t.name
        return st

    def _put(self, name: str, t0: int, t1: int, parent: int, request: int,
             dispatch: int) -> int:
        tid = threading.get_ident()
        with self.lock:
            seq = self.n
            self.n += 1
            nid = self.name_ids.get(name)
            if nid is None:
                nid = self.name_ids[name] = len(self.names)
                self.names.append(name)
            self.ring[seq % self.cap] = (seq, nid, t0, t1, tid, parent,
                                         request, dispatch, 0)
        return seq

    def open(self, name: str, request: int, dispatch: int) -> int:
        st = self.stack()
        seq = self._put(name, time.perf_counter_ns(), -1,
                        st[-1] if st else -1, request, dispatch)
        st.append(seq)
        return seq

    def close(self, seq: int) -> None:
        t = time.perf_counter_ns()
        self.stack().pop()
        i = seq % self.cap
        with self.lock:
            if self.ring["seq"][i] == seq:     # not overwritten meanwhile
                self.ring["t1"][i] = t

    def on_event(self, event: str, duration: float, **_) -> None:
        if event != COMPILE_EVENT:
            return
        st = getattr(self.local, "stack", None)
        i = st[-1] % self.cap if st else 0
        with self.lock:
            if st and self.ring["seq"][i] == st[-1]:
                self.ring["compiles"][i] += 1
            else:
                self.none_compiles += 1


class _Span:
    __slots__ = ("rec", "name", "request", "dispatch", "seq", "ann")

    def __init__(self, rec: _Recorder, name: str, request: int,
                 dispatch: int):
        self.rec = rec
        self.name = name
        self.request = request
        self.dispatch = dispatch

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.seq = self.rec.open(self.name, self.request, self.dispatch)
        return self

    def __exit__(self, *exc) -> bool:
        self.rec.close(self.seq)
        self.ann.__exit__(*exc)
        return False


def enabled() -> bool:
    return _rec is not None


def enable(capacity: int = 1 << 18) -> None:
    """Start recording into a fresh ring of ``capacity`` spans (what an
    earlier ``enable`` recorded is dropped)."""
    global _rec
    disable()
    rec = _Recorder(capacity)
    jax.monitoring.register_event_duration_secs_listener(rec.on_event)
    _rec = rec


def disable() -> None:
    """Stop recording and drop what was recorded."""
    global _rec
    rec, _rec = _rec, None
    if rec is not None:
        jax.monitoring.unregister_event_duration_listener(rec.on_event)


def span(name: str, request: int = -1, dispatch: int = -1):
    """A context manager timing one step as the span ``name``."""
    rec = _rec
    if rec is None:
        return _NOOP
    return _Span(rec, name, request, dispatch)


def record(name: str, t0_ns: int, t1_ns: int, request: int = -1,
           dispatch: int = -1) -> None:
    """Add a finished span timed elsewhere (``perf_counter_ns`` times)."""
    rec = _rec
    if rec is not None:
        rec._put(name, int(t0_ns), int(t1_ns), -1, request, dispatch)


def snapshot() -> dict:
    """The finished spans still in the ring, ordered by start of recording,
    and totals per span name.

    Arrays (one entry per span): ``seq`` (its number; ``parent`` holds the
    parent's, or -1), ``name``, ``t0``, ``t1`` (ns), ``thread`` (ident),
    ``request``, ``dispatch``, ``compiles`` (XLA compiles while it was the
    innermost open span of its thread).  ``by_name`` maps each name to its
    ``count``, ``total_ns``, ``self_ns`` (durations less the part their
    child spans cover) and ``compiles``.  Also ``threads`` (ident -> thread
    name), ``none_compiles`` (compiles outside any span) and ``dropped``
    (spans overwritten in the ring)."""
    rec = _rec
    if rec is None:
        raise RuntimeError("spans are off: call repro.spans.enable() first")
    with rec.lock:
        ring = rec.ring[(rec.ring["seq"] >= 0) & (rec.ring["t1"] >= 0)]
        names = np.asarray(rec.names if rec.names else [""])
        out = {"threads": dict(rec.threads),
               "none_compiles": int(rec.none_compiles),
               "dropped": max(0, rec.n - rec.cap)}
    ring = ring[np.argsort(ring["seq"], kind="stable")]
    cols = {k: ring[k].copy() for k in _FIELDS.names}
    cols["name"] = names[cols["name"]]
    dur = cols["t1"] - cols["t0"]
    # self time: each span's duration less its children's (a thread's
    # spans nest, so its children never overlap one another)
    n = len(dur)
    covered = np.zeros(n, np.int64)
    if n:
        pos = np.minimum(np.searchsorted(cols["seq"], cols["parent"]), n - 1)
        has = (cols["parent"] >= 0) & (cols["seq"][pos] == cols["parent"])
        np.add.at(covered, pos[has], dur[has])
    own = dur - covered
    by_name = {}
    for nm in np.unique(cols["name"]):
        m = cols["name"] == nm
        by_name[str(nm)] = {"count": int(m.sum()),
                            "total_ns": int(dur[m].sum()),
                            "self_ns": int(own[m].sum()),
                            "compiles": int(cols["compiles"][m].sum())}
    out.update(cols)
    out["by_name"] = by_name
    return out
