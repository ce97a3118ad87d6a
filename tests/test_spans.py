"""The span recorder (``repro.spans``) and the spans of the serving path:
off costs nothing and records nothing; on, spans nest, carry request and
dispatch ids, count the compiles made under them, land on the profiler's
host plane, and the executors' named scopes reach the compiled HLO."""
from __future__ import annotations

import glob
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.api import ErrorBudget, PolyFit, QuerySpec, TableSpec
from repro.serve import ServingEngine

SERVE_CHILDREN = ("polyfit.aot.lookup", "polyfit.serve.prepare",
                  "polyfit.serve.execute", "polyfit.serve.device_wait",
                  "polyfit.serve.scatter")


@pytest.fixture
def recording():
    spans.enable()
    try:
        yield
    finally:
        spans.disable()


@pytest.fixture(scope="module")
def count_session():
    keys = np.sort(np.random.default_rng(0x5A).normal(size=3000))
    spec = TableSpec("count", ErrorBudget(abs=20.0, rel=0.01), deg=2)
    return PolyFit.fit({"c": keys}, {"c": spec}, backend="xla",
                       min_bucket=64), keys


def _by_name(snap, name):
    return np.flatnonzero(snap["name"] == name)


def test_off_records_nothing_and_calls_no_profiler(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: calls.append(a))
    spans.disable()
    assert not spans.enabled()
    a, b = spans.span("x"), spans.span("y", request=1, dispatch=2)
    assert a is b                      # the one shared no-op
    with a:
        spans.record("z", 0, 1)
    assert calls == []
    with pytest.raises(RuntimeError):
        spans.snapshot()


def test_nesting_parents_and_ids(recording):
    with spans.span("outer", request=7):
        with spans.span("mid", dispatch=3):
            with spans.span("inner"):
                pass
        with spans.span("sibling"):
            pass
    spans.record("remote", 5, 9, request=7, dispatch=3)
    snap = spans.snapshot()
    seq = dict(zip(snap["name"].tolist(), snap["seq"].tolist()))
    parent = dict(zip(snap["name"].tolist(), snap["parent"].tolist()))
    assert parent == {"outer": -1, "mid": seq["outer"],
                      "inner": seq["mid"], "sibling": seq["outer"],
                      "remote": -1}
    i = dict(zip(snap["name"].tolist(), range(len(snap["seq"]))))
    assert snap["request"][i["outer"]] == 7
    assert snap["dispatch"][i["mid"]] == 3
    assert snap["request"][i["inner"]] == -1
    assert (snap["t0"][i["remote"]], snap["t1"][i["remote"]]) == (5, 9)
    assert set(snap["thread"].tolist()) == {threading.get_ident()}
    assert snap["dropped"] == 0


@pytest.mark.parametrize("capacity,spans_made", [(4, 10), (8, 8), (1, 3)])
def test_ring_overwrite_counts_dropped(capacity, spans_made):
    spans.enable(capacity=capacity)
    try:
        for k in range(spans_made):
            with spans.span(f"s{k}"):
                pass
        snap = spans.snapshot()
    finally:
        spans.disable()
    kept = min(capacity, spans_made)
    assert snap["dropped"] == spans_made - kept
    assert snap["seq"].tolist() == list(range(spans_made - kept, spans_made))
    assert snap["name"].tolist() == [f"s{k}" for k in
                                     range(spans_made - kept, spans_made)]


def test_self_time_is_duration_less_children(recording):
    with spans.span("outer"):
        time.sleep(0.02)
        for _ in range(2):
            with spans.span("child"):
                time.sleep(0.01)
    snap = spans.snapshot()
    outer, child = snap["by_name"]["outer"], snap["by_name"]["child"]
    assert child["count"] == 2 and child["self_ns"] == child["total_ns"]
    assert outer["self_ns"] == outer["total_ns"] - child["total_ns"]
    assert outer["self_ns"] >= 20_000_000
    assert child["total_ns"] >= 20_000_000


@pytest.mark.parametrize("inside", [True, False])
def test_compile_counted_against_innermost_span(recording, inside):
    def work():
        # a shape no other test of this file compiles
        (jnp.zeros((131 + inside, 7)) * 3 + 1).block_until_ready()
    if inside:
        with spans.span("outer"):
            with spans.span("compiling"):
                work()
    else:
        work()
    snap = spans.snapshot()
    if inside:
        assert snap["by_name"]["compiling"]["compiles"] >= 1
        assert snap["by_name"]["outer"]["compiles"] == 0
        assert snap["none_compiles"] == 0
    else:
        assert snap["none_compiles"] >= 1


def test_threads_keep_their_own_stacks(recording):
    threads, per = 12, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with spans.span("a"):
                    with spans.span("b"):
                        pass
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    snap = spans.snapshot()
    assert len(snap["seq"]) == 2 * threads * per
    assert len(set(snap["seq"].tolist())) == len(snap["seq"])
    pos = {s: k for k, s in enumerate(snap["seq"].tolist())}
    for k in _by_name(snap, "b"):
        p = pos[int(snap["parent"][k])]
        assert snap["name"][p] == "a"
        assert snap["thread"][p] == snap["thread"][k]
        assert snap["t0"][p] <= snap["t0"][k] <= snap["t1"][k] \
            <= snap["t1"][p]


def test_serving_engine_spans(count_session, recording):
    sess, keys = count_session
    engine = ServingEngine(sess, max_batch=256, workers=1)
    try:
        assert engine.warmup(max_bucket=128) == 2
        futs = [engine.submit(QuerySpec("c", (keys[:n], keys[-n:])))
                for n in (1, 3, 5, 60, 2)]
        for f in futs:
            f.result(timeout=60)
    finally:
        engine.shutdown()
    snap = spans.snapshot()
    by = snap["by_name"]
    assert by["polyfit.aot.compile"]["count"] == 2
    assert by["polyfit.aot.compile"]["compiles"] >= 2
    queued = _by_name(snap, "polyfit.serve.queued")
    assert sorted(snap["request"][queued].tolist()) == list(range(5))
    dispatch = _by_name(snap, "polyfit.serve.dispatch")
    ids = snap["dispatch"][dispatch]
    assert set(snap["dispatch"][queued].tolist()) == set(ids.tolist())
    assert len(set(ids.tolist())) == len(ids) >= 1
    batches = set(snap["seq"][_by_name(snap, "polyfit.serve.batch")])
    for k in dispatch:
        assert snap["parent"][k] in batches
        kids = snap["name"][snap["parent"] == snap["seq"][k]].tolist()
        assert kids == list(SERVE_CHILDREN)
    # a request waits from submit until the worker takes it
    for k in queued:
        assert snap["t0"][k] <= snap["t1"][k]
    assert snap["dropped"] == 0


def test_spans_land_on_the_profilers_host_plane(count_session, tmp_path,
                                                recording):
    from jax.profiler import ProfileData
    sess, keys = count_session
    engine = ServingEngine(sess, max_batch=256, workers=1)
    engine.warmup(max_bucket=64)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        engine.submit(QuerySpec("c", (keys[:4], keys[-4:]))).result(60)
    finally:
        engine.shutdown()        # every span closes inside the trace
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path[0]).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name.startswith("polyfit.")}
    assert {"polyfit.serve.batch", "polyfit.serve.dispatch",
            *SERVE_CHILDREN} <= names


@pytest.mark.parametrize("agg,dynamic", [("count", False), ("max", False),
                                         ("sum", True)])
def test_executor_scopes_reach_the_hlo(agg, dynamic):
    rng = np.random.default_rng(3)
    keys = np.sort(rng.uniform(0.0, 100.0, 2000))
    data = keys if agg == "count" else (keys, rng.uniform(1.0, 5.0, 2000))
    spec = TableSpec(agg, ErrorBudget(abs=5.0, rel=0.01), dynamic=dynamic)
    sess = PolyFit.fit({"t": data}, {"t": spec}, backend="xla")
    plan, buf = sess.snapshot("t")
    fn = sess.serving_executor("t", 0.01, bq=64)
    q = jax.ShapeDtypeStruct((64,), plan.dtype)
    text = jax.jit(fn).lower(plan, buf, q, q).compile().as_text()
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("approx", "refine"):
        assert any(f"/{scope}/" in o for o in ops), scope
