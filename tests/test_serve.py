"""Serving-engine tests (serve/engine.py): coalesced answers bit-identical
to serial execution, concurrent reader/writer pools, queue backpressure,
AOT-cache plan-swap invalidation, shutdown/drain semantics, and the
host-assembled admission path (no compile after warm-up, one host copy
or a pass-through per dispatch).

Everything runs backend='ref' on small synthetic tables so the suite
stays CPU-cheap; the bit-identity assertions compare against the plain
``session.query`` path, which the engine must reproduce exactly (the
executors are elementwise per query, so admission batching may not
change a single bit).
"""
from __future__ import annotations

import re
import threading
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.api import ErrorBudget, PolyFit, QuerySpec, TableSpec
from repro.engine import refine_slots
from repro.serve import QueueFull, ServingEngine

N1 = 4000
N2 = 2000


@pytest.fixture(scope="module")
def session():
    rng = np.random.default_rng(0xE17)
    keys = np.sort(rng.uniform(0.0, 100.0, N1))
    vals = rng.uniform(0.0, 10.0, N1)
    xs = rng.uniform(0.0, 50.0, N2)
    ys = rng.uniform(0.0, 50.0, N2)
    ws = rng.uniform(1.0, 5.0, N2)
    b = ErrorBudget(abs=50.0, rel=0.01)
    m = ErrorBudget(abs=0.5, rel=0.01)
    return PolyFit.fit(
        {"sum": (keys, vals), "min": (keys, vals), "c2": (xs, ys),
         "mn2": (xs, ys, ws)},
        {"sum": TableSpec("sum", b, dynamic=True, capacity=256,
                          auto_refit=False),
         "min": TableSpec("min", m),
         "c2": TableSpec("count2d", b, dynamic=True, capacity=256,
                         auto_refit=False),
         "mn2": TableSpec("min2d", m)},
        backend="ref")


def _mixed_specs(rng, n):
    specs = []
    for _ in range(n):
        m = int(rng.integers(1, 5))
        kind = int(rng.integers(4))
        if kind == 0:
            lq = rng.uniform(0, 80, m)
            specs.append(QuerySpec.range("sum", lq, lq + 10.0))
        elif kind == 1:
            lq = rng.uniform(0, 80, m)
            specs.append(QuerySpec.range("min", lq, lq + 15.0))
        elif kind == 2:
            lx, ly = rng.uniform(0, 40, m), rng.uniform(0, 40, m)
            specs.append(QuerySpec.rect("c2", lx, lx + 8, ly, ly + 8))
        else:
            specs.append(QuerySpec.corner("mn2", rng.uniform(10, 50, m),
                                          rng.uniform(10, 50, m)))
    return specs


def _assert_identical(got, want):
    assert np.array_equal(np.asarray(got.answer), np.asarray(want.answer))
    assert np.array_equal(np.asarray(got.approx), np.asarray(want.approx))


def test_coalesced_bit_identical_to_serial(session):
    """A stream submitted through the queue (admission batching on) gives
    exactly the serial per-spec answers, across all four kinds including
    the newly exposed 1-D sum/min and 2-D min2d."""
    rng = np.random.default_rng(1)
    specs = _mixed_specs(rng, 40)
    serial = [session.query(s) for s in specs]
    eng = ServingEngine(session, start=False)
    futures = [eng.submit(s) for s in specs]   # all queued before serving
    eng.start()
    try:
        for fut, want in zip(futures, serial):
            _assert_identical(fut.result(timeout=120), want)
        st = eng.stats
        assert st.answered == len(specs)
        assert st.coalesced > 0          # batching actually kicked in
        assert st.dispatches < len(specs)
    finally:
        eng.shutdown()


def test_aot_cache_reuse_and_warmup(session):
    eng = ServingEngine(session)
    try:
        n = eng.warmup(max_bucket=128)
        assert n == 8                    # 4 tables x ladder {64, 128}
        assert eng.warmup(max_bucket=128) == 0   # idempotent
        c0 = eng.stats.aot_compiles
        rng = np.random.default_rng(2)
        for s in _mixed_specs(rng, 12):
            eng.query(s, timeout=120)
        st = eng.stats
        assert st.aot_compiles == c0     # warm ladder: zero new traces
        assert st.aot_hits >= 12 or st.dispatches < 12
    finally:
        eng.shutdown()


def test_plan_swap_precompiles_executables(session):
    """A merge/compaction stages the incoming plan's executables on the
    merge thread (``on_plan_swap`` listener), so the post-swap dispatch
    promotes instead of relowering: zero new compiles after a swap."""
    eng = ServingEngine(session)
    spec = QuerySpec.range("sum", 5.0, 60.0)
    try:
        before = eng.query(spec, timeout=120)
        eng.insert("sum", np.array([10.0, 20.0]),
                   np.array([7.0, 3.0]), wait=True)
        buffered = eng.query(spec, timeout=120)
        assert float(buffered.answer[0]) == pytest.approx(
            float(before.answer[0]) + 10.0)
        c0 = eng.stats.aot_compiles
        promo0 = eng.stats.aot_promotions
        eng.flush("sum")                 # merge -> plan swap
        assert eng.stats.aot_precompiles > 0   # staged pre-install
        merged = eng.query(spec, timeout=120)
        # the refit plan approximates anew: answers agree within the two
        # certified Q_abs bounds, not bitwise
        assert abs(float(merged.answer[0])
                   - float(buffered.answer[0])) <= 100.0
        st = eng.stats
        assert st.aot_compiles == c0           # zero new compiles post-swap
        assert st.aot_promotions > promo0      # served the staged executable
        # engine answers == session answers on the swapped plan too
        _assert_identical(merged, session.query(spec))
    finally:
        eng.shutdown()
        # leave the module-scoped session clean for the other tests
        session.flush("sum")


def test_concurrent_reader_pool_bit_identical(session):
    """Many reader threads hammering the queue still each get exactly
    their own serial answer (futures scatter per request)."""
    rng = np.random.default_rng(3)
    specs = _mixed_specs(rng, 60)
    serial = [session.query(s) for s in specs]
    eng = ServingEngine(session, workers=2)
    errors = []

    def reader(lo, hi):
        try:
            for i in range(lo, hi):
                got = eng.query(specs[i], timeout=120)
                _assert_identical(got, serial[i])
        except BaseException as e:       # pragma: no cover - surfaced below
            errors.append(e)

    try:
        threads = [threading.Thread(target=reader, args=(i, i + 15))
                   for i in range(0, 60, 15)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not errors, errors
    finally:
        eng.shutdown()


def test_mixed_readers_writers_linearizable(session):
    """Concurrent readers + async writers: with writes staged through the
    engine, every read matches a serial replay of the write log at *some*
    prefix (monotone in time), and after a full drain the engine answer
    equals the serial answer of the complete log."""
    eng = ServingEngine(session)
    spec = QuerySpec.range("sum", 0.0, 100.0)
    base = float(session.query(spec).answer[0])
    chunks = 6
    chunk = 16
    per_chunk = 2.0 * chunk              # each record adds measure 2.0
    errors = []
    seen = []

    def writer():
        try:
            rng = np.random.default_rng(4)
            for _ in range(chunks):
                eng.insert("sum", rng.uniform(0, 100, chunk),
                           np.full(chunk, 2.0), wait=False)
                time.sleep(0.01)
        except BaseException as e:
            errors.append(e)

    def reader():
        try:
            for _ in range(12):
                seen.append(float(eng.query(spec, timeout=120).answer[0]))
        except BaseException as e:
            errors.append(e)

    try:
        threads = [threading.Thread(target=writer),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not errors, errors
        eng.drain_updates()
        final = float(eng.query(spec, timeout=120).answer[0])
        assert final == pytest.approx(base + chunks * per_chunk)
        # reads only ever see whole staged-chunk prefixes, in order
        tol = 1e-6 * max(1.0, abs(base))
        valid = [base + k * per_chunk for k in range(chunks + 1)]
        for v in seen:
            assert min(abs(v - x) for x in valid) < tol, (v, valid)
        assert seen == sorted(seen)      # write visibility is monotone
    finally:
        eng.shutdown()
        session.flush("sum")


def test_backpressure_reject_and_block(session):
    spec = QuerySpec.range("min", 0.0, 1.0)
    eng = ServingEngine(session, max_queue=4, admission="reject",
                        start=False)   # nothing drains: deterministic
    for _ in range(4):
        eng.submit(spec)
    with pytest.raises(QueueFull):
        eng.submit(spec)
    assert eng.stats.rejected == 1
    assert eng.queue_depth == 4
    eng.start()                          # drain the queued four
    eng.shutdown(drain=True)
    assert eng.stats.answered == 4

    blocking = ServingEngine(session, max_queue=2, admission="block",
                             start=False)
    blocking.submit(spec)
    blocking.submit(spec)
    with pytest.raises(QueueFull):       # block admission honors timeout
        blocking.submit(spec, timeout=0.05)
    blocking.start()
    blocking.shutdown(drain=True)


def test_shutdown_drain_answers_everything(session):
    rng = np.random.default_rng(5)
    specs = _mixed_specs(rng, 10)
    eng = ServingEngine(session, start=False)
    futures = [eng.submit(s) for s in specs]
    eng.insert("sum", np.array([1.0]), np.array([1.0]), wait=False)
    eng.start()
    eng.shutdown(drain=True)             # must answer + apply everything
    assert all(f.done() and f.exception() is None for f in futures)
    assert eng.staged_depth == 0
    with pytest.raises(RuntimeError):
        eng.submit(specs[0])
    eng.shutdown()                       # idempotent
    session.flush("sum")


def test_shutdown_no_drain_cancels_queued(session):
    spec = QuerySpec.range("sum", 0.0, 1.0)
    eng = ServingEngine(session, start=False)
    futures = [eng.submit(spec) for _ in range(5)]
    eng.shutdown(drain=False)
    for f in futures:
        assert isinstance(f.exception(timeout=5), RuntimeError)


def test_delete_error_surfaces(session):
    eng = ServingEngine(session)
    try:
        with pytest.raises(KeyError):    # no live occurrence of key 1e9
            eng.delete("sum", np.array([1e9]), wait=True)
        eng.delete("sum", np.array([2e9]), wait=False)
        with pytest.raises(KeyError):    # deferred error lands on drain
            eng.drain_updates()
    finally:
        eng.shutdown()


def test_update_normalization_errors(session):
    eng = ServingEngine(session, start=False)
    with pytest.raises(ValueError):
        eng.insert("sum", np.array([1.0]), wait=False)   # measures missing
    with pytest.raises(ValueError):
        eng.delete("c2", np.array([1.0]), wait=False)    # ys missing
    with pytest.raises(RuntimeError):                    # static table
        eng.insert("min", np.array([1.0]), np.array([1.0]), wait=False)


# -- host-assembled admission: one transfer in, one host copy out ----------

@pytest.fixture(scope="module")
def kinds_session():
    """One table per admission case: 1-D COUNT (also quantiles), SUM and
    MAX, 2-D COUNT, a dynamic SUM with buffered inserts, a windowed COUNT,
    and a dynamic SUM kept for the plan-swap test."""
    rng = np.random.default_rng(0xAD)
    keys = np.sort(rng.uniform(0.0, 100.0, N1))
    vals = rng.uniform(0.0, 10.0, N1)
    xs, ys = rng.uniform(0.0, 50.0, N2), rng.uniform(0.0, 50.0, N2)
    b = ErrorBudget(abs=50.0, rel=0.01)
    dyn = dict(dynamic=True, capacity=256, auto_refit=False)
    sess = PolyFit.fit(
        {"cnt": keys, "sm": (keys, vals), "mx": (keys, vals),
         "c2": (xs, ys), "dyn": (keys, vals), "w": (keys, None),
         "swap": (keys, vals)},
        {"cnt": TableSpec("count", b), "sm": TableSpec("sum", b),
         "mx": TableSpec("max", ErrorBudget(abs=0.5, rel=0.01)),
         "c2": TableSpec("count2d", b), "dyn": TableSpec("sum", b, **dyn),
         "w": TableSpec("count", b, window=4),
         "swap": TableSpec("sum", b, **dyn)},
        backend="ref")
    sess.insert("dyn", rng.uniform(0.0, 100.0, 40), np.full(40, 3.0))
    sess.ingest("w", rng.uniform(0.0, 100.0, 200))
    sess.advance_epoch("w")
    return sess


def _case_specs(case, rng, n):
    """``n`` requests of 1-8 queries each on the case's table."""
    specs = []
    for m in rng.integers(1, 9, n):
        lq = rng.uniform(0.0, 80.0, m)
        if case == "quantile":
            specs.append(QuerySpec.quantile("cnt", rng.uniform(0, 1, m)))
        elif case == "count2d":
            lx, ly = rng.uniform(0, 40, m), rng.uniform(0, 40, m)
            specs.append(QuerySpec.rect("c2", lx, lx + 8, ly, ly + 8))
        elif case == "window":
            specs.append(QuerySpec.window("w", lq, lq + 10.0, 0, 1))
        else:
            table = {"count": "cnt", "sum": "sm", "max": "mx",
                     "dynamic_sum": "dyn"}[case]
            specs.append(QuerySpec.range(table, lq, lq + 10.0))
    return specs


@pytest.mark.parametrize("case", ["count", "sum", "max", "count2d",
                                  "quantile", "dynamic_sum", "window"])
def test_coalesced_bit_identical_to_serial_by_kind(kinds_session, case):
    """Coalesced groups, assembled on the host and answered from one host
    copy, give every request exactly its serial ``session.query`` answer,
    certificates included."""
    sess = kinds_session
    specs = _case_specs(case, np.random.default_rng(11), 30)
    serial = [sess.query(s) for s in specs]
    eng = ServingEngine(sess, start=False)
    futures = [eng.submit(s) for s in specs]   # all queued before serving
    eng.start()
    try:
        for fut, want in zip(futures, serial):
            got = fut.result(timeout=120)
            _assert_identical(got, want)
            assert np.array_equal(np.asarray(got.refined),
                                  np.asarray(want.refined))
            if case == "quantile":
                for g, w in zip(got.bound, want.bound):
                    assert np.array_equal(np.asarray(g), np.asarray(w))
            else:
                assert got.bound == want.bound
        st = eng.stats
        assert st.coalesced > 0 and st.dispatches < len(specs)
        assert st.scatter_host_copies == st.dispatches
    finally:
        eng.shutdown()


def test_warm_ladder_serves_without_compiling(kinds_session):
    """After ``warmup(max_bucket=1024)``, coalesced requests of 1-8 ranges
    over every bucket 64..1024, lone requests and full buckets trigger no
    XLA compile: no eager device op runs between queue and future."""
    sess = kinds_session
    eng = ServingEngine(sess, max_batch=1024, start=False)
    assert eng.warmup(max_bucket=1024, tables=["cnt", "c2"]) == 10
    rng = np.random.default_rng(12)
    waves = [_case_specs("count", rng, n) for n in (10, 20, 40, 100, 220)]
    waves.append(_case_specs("count2d", rng, 60))
    lone = [QuerySpec.range("cnt", rng.uniform(0, 50, n),
                            rng.uniform(50, 100, n))
            for n in (3, 64, 200, 1024)]
    compiles = []

    def listener(event, duration, **_):
        if event == spans.COMPILE_EVENT:
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        eng.start()
        for wave in waves:   # queued while the worker answers the last
            for f in [eng.submit(s) for s in wave]:
                f.result(timeout=120)
        for s in lone:
            eng.query(s, timeout=120)
    finally:
        eng.shutdown()
        jax.monitoring.unregister_event_duration_listener(listener)
    st = eng.stats
    assert st.coalesced > 0
    assert st.answered == sum(map(len, waves)) + len(lone)
    assert compiles == []
    assert st.aot_compiles == 10


def test_scatter_counts_passthrough_and_host_copies(kinds_session):
    """A lone request that fills its bucket gets the executable's device
    arrays as they are; a coalesced group, or a request smaller than its
    bucket, is answered from one host copy as numpy views."""
    sess = kinds_session
    eng = ServingEngine(sess, start=False)
    try:
        eng.warmup(max_bucket=64, tables=["cnt"])
        lq = np.linspace(0.0, 60.0, 64)
        full = QuerySpec.range("cnt", lq, lq + 5.0)
        group = [QuerySpec.range("cnt", lq[:m], lq[:m] + 5.0)
                 for m in (1, 5, 8)]
        futures = [eng.submit(s) for s in group]
        eng.start()
        answers = [f.result(timeout=120) for f in futures]
        st = eng.stats
        assert (st.dispatches, st.scatter_host_copies,
                st.scatter_passthrough) == (1, 1, 0)
        for a, s in zip(answers, group):
            assert isinstance(a.value, np.ndarray)
            assert a.value.shape == (len(s),)
            _assert_identical(a, sess.query(s))
        whole = eng.query(full, timeout=120)
        assert eng.stats.scatter_passthrough == 1
        assert isinstance(whole.value, jax.Array)
        _assert_identical(whole, sess.query(full))
        eng.query(group[1], timeout=120)    # 5 queries in a 64 bucket
        st = eng.stats
        assert (st.scatter_host_copies, st.scatter_passthrough) == (2, 1)
    finally:
        eng.shutdown()


def test_refine_overflows_counted_from_host_copies(kinds_session):
    """A host-copy dispatch whose flagged non-empty ranges outnumber
    ``refine_slots`` counts one overflow; fewer flagged ranges, or flagged
    rows with ``lq == uq`` only (padding among them), count none.  Every
    answer is the session's, bit for bit."""
    sess = kinds_session
    eng = ServingEngine(sess, start=False)
    try:
        eng.warmup(max_bucket=64, tables=["cnt"])
        assert refine_slots(64) == 8
        lq = np.linspace(1.0, 60.0, 40)
        specs = [QuerySpec.range("cnt", lq[:5], lq[:5] + 5.0),   # 5 <= 8
                 QuerySpec.range("cnt", lq[:20], lq[:20]),       # empty
                 QuerySpec.range("cnt", lq, lq + 5.0)]           # 40 > 8
        overflows = []
        eng.start()
        for s in specs:
            got = eng.query(s, timeout=120)
            want = sess.query(s)
            _assert_identical(got, want)
            assert np.array_equal(got.refined, np.asarray(want.refined))
            assert got.refined.all()   # every row flagged at this budget
            overflows.append(eng.stats.refine_overflows)
        assert overflows == [0, 0, 1]
        futures = [eng.submit(QuerySpec.range("cnt", lq[:m], lq[:m] + 5.0))
                   for m in (2, 3)]
        for f in futures:
            f.result(timeout=120)
        assert eng.stats.refine_overflows == 1
        assert eng.stats.scatter_host_copies == eng.stats.dispatches
    finally:
        eng.shutdown()


def test_refine_overflows_on_passthrough_only_with_spans(kinds_session):
    """A lone full 1024 request is answered correctly either way; its
    overflow is counted, with one copy of the flags in its own span, only
    while spans are enabled, so the serving path adds no transfer when
    they are off."""
    sess = kinds_session
    eng = ServingEngine(sess, max_batch=1024, start=False)
    rng = np.random.default_rng(17)
    lq = rng.uniform(0.0, 90.0, 1024)
    full = QuerySpec.range("cnt", lq, lq + 5.0)          # 1024 flagged
    few = np.where(np.arange(1024) < 30, lq + 5.0, lq)   # 30 flagged
    compact = QuerySpec.range("cnt", lq, few)
    try:
        eng.warmup(max_bucket=1024, tables=["cnt"])
        eng.start()
        for s in (full, compact):
            _assert_identical(eng.query(s, timeout=120), sess.query(s))
        assert eng.stats.scatter_passthrough == 2
        assert eng.stats.refine_overflows == 0
        spans.enable()
        try:
            for s in (full, compact):
                _assert_identical(eng.query(s, timeout=120), sess.query(s))
            snap = spans.snapshot()
        finally:
            spans.disable()
        assert eng.stats.refine_overflows == 1
        assert snap["by_name"]["polyfit.serve.refine_count"]["count"] == 2
    finally:
        eng.shutdown()


def test_refine_scope_searches_the_slots(kinds_session):
    """The bucket-1024 serving executable keeps its exact searches under
    the ``refine`` scope, where ``bench/spans.py`` attributes device time:
    one pair of ``while`` loops on the 64 slots, one on the whole bucket
    for batches that overflow them."""
    from bench.spans import hlo_scopes
    sess = kinds_session
    eng = ServingEngine(sess, max_batch=1024, start=False)
    try:
        eng.warmup(max_bucket=1024, tables=["cnt"])
        rel = sess.resolve_rel("cnt")
        text = eng._cache[("cnt", rel, 1024)].compiled.as_text()
    finally:
        eng.shutdown()
    scopes = hlo_scopes([text])
    shapes = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?(while[\w.\-]*)\s*=\s*\((.*?)\)"
                     r"\s*while\(", line)
        if m:
            shapes[m.group(1)] = m.group(2)
    refine = [n for n in shapes if scopes.get(n) == "refine"]
    assert len(refine) == 4
    assert sorted("[64]" in shapes[n] for n in refine) == [False, False,
                                                           True, True]
    assert all("[1024]" in shapes[n] for n in refine
               if "[64]" not in shapes[n])


def test_plan_swap_promotes_stacked_executables(kinds_session):
    """A merge on a dynamic table stages the incoming plan's range and
    quantile executables in the stacked ``(plan, buf, q[k, bucket])``
    signature; after the swap every dispatch promotes one, with no
    relower, and answers equal the session's on the new plan."""
    sess = kinds_session
    eng = ServingEngine(sess)
    rng = np.random.default_rng(13)
    lq = rng.uniform(0.0, 80.0, 6)
    rng_spec = QuerySpec.range("swap", lq, lq + 10.0)
    q_spec = QuerySpec.quantile("swap", rng.uniform(0.0, 1.0, 5))
    try:
        assert eng.warmup(max_bucket=128, tables=["swap"],
                          kinds=("range", "quantile")) == 4
        eng.insert("swap", rng.uniform(0.0, 100.0, 30), np.full(30, 2.0),
                   wait=True)
        c0 = eng.stats.aot_compiles
        eng.flush("swap")                        # merge -> plan swap
        assert eng.stats.aot_precompiles == 4    # both ladders staged
        got = [eng.query(rng_spec, timeout=120),
               eng.query(q_spec, timeout=120)]
        st = eng.stats
        assert st.aot_compiles == c0             # no relower post-swap
        assert st.aot_promotions == 2            # range and quantile
        for g, s in zip(got, (rng_spec, q_spec)):
            _assert_identical(g, sess.query(s))
    finally:
        eng.shutdown()
