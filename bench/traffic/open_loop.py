"""Open-loop traffic: reads sent on a fixed schedule, whatever the server
does, and optionally a writer inserting batches on a schedule of its own.

Parameters (a mix's JSON file):

* ``rate_rps`` — read requests per second; ``ranges_per_request``
  ``[lo, hi]`` — each request holds that many range queries, uniform in
  count; endpoints are drawn from the table's keys (paper §7.1).
* ``max_batch``, ``min_bucket``, ``max_bucket`` — the serving engine's
  admission cap and the bucket ladder it warms.
* ``check_requests`` — how many read requests, drawn from the seed, are
  compared with the reference after the window.
* ``writer`` (optional) — ``batch`` records per insert, ``rate_batches_per_s``,
  ``key_window`` (inserted keys are uniform in that top share of the key
  range; values uniform over the measures' range) and ``threads`` (the
  pool each ``insert(..., wait=True)`` runs on).

Every seed gets the same work in another order: the gaps between requests
are the quantiles of the exponential distribution at the rate, shuffled
and scaled to fill the window exactly, and the request sizes are a fixed
multiset, shuffled.  Latency runs from when a request was due, so a late
generator or a stalled server shows in it; lateness is reported apart.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from typing import List

import numpy as np

from bench.data import make_queries_1d
from bench.harness import DRAIN_SECONDS, Record, seeds, sleep_until
from bench.reference import InsertBatch


def schedule(n: int, rate: float, seconds: float, rng) -> np.ndarray:
    """``n`` arrival times in [0, seconds): exponential gaps at ``rate``,
    the same multiset for every seed, in the order ``rng`` draws."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps *= seconds / gaps.sum()
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


@dataclasses.dataclass
class Schedule:
    arrivals: np.ndarray
    sizes: np.ndarray
    specs: list
    sample: np.ndarray
    warm_specs: list
    w_arrivals: np.ndarray = None
    w_keys: List[np.ndarray] = None
    w_vals: List[np.ndarray] = None
    warm_batch: tuple = None


def _writes(ctx, w: dict, n: int, rng):
    keys = ctx.keys
    lo = keys[-1] - w["key_window"] * (keys[-1] - keys[0])
    meas = np.asarray(ctx.data[1] if isinstance(ctx.data, tuple)
                      else np.ones_like(keys), np.float64)
    b = int(w["batch"])
    ks = [rng.uniform(lo, keys[-1], b) for _ in range(n)]
    vs = [rng.uniform(meas.min(), meas.max(), b) for _ in range(n)]
    return ks, vs


def prepare(ctx, params: dict, seed: int, seconds: float) -> Schedule:
    from repro.api import QuerySpec
    rng = np.random.default_rng(seeds(seed, 1))
    n = max(1, round(params["rate_rps"] * seconds))
    arrivals = schedule(n, params["rate_rps"], seconds, rng)
    lo, hi = params["ranges_per_request"]
    sizes = rng.permutation(np.resize(np.arange(lo, hi + 1), n))
    lq, uq = make_queries_1d(ctx.keys, int(sizes.sum()) + hi,
                             seed=seeds(seed, 2))
    cut = np.concatenate([[0], np.cumsum(sizes)])
    specs = [QuerySpec(ctx.table, (lq[a:b], uq[a:b]))
             for a, b in zip(cut[:-1], cut[1:])]
    # one request of every size the schedule sends, for the warm-up
    warm = [QuerySpec(ctx.table, (lq[-m:], uq[-m:]))
            for m in range(lo, hi + 1)]
    sample = np.sort(rng.choice(n, min(n, params["check_requests"]),
                                replace=False))
    sch = Schedule(arrivals, sizes, specs, sample, warm)
    if "writer" in params:
        w = params["writer"]
        wrng = np.random.default_rng(seeds(seed, 3))
        nb = max(1, round(w["rate_batches_per_s"] * seconds))
        sch.w_arrivals = schedule(nb, w["rate_batches_per_s"], seconds,
                                  wrng)
        ks, vs = _writes(ctx, w, nb + 1, wrng)
        sch.w_keys, sch.w_vals = ks[:nb], vs[:nb]
        sch.warm_batch = (ks[nb], vs[nb])
    return sch


def _insert_args(ctx, keys, vals):
    return (keys,) if ctx.agg == "count" else (keys, vals)


def warm(ctx, sch: Schedule) -> List[InsertBatch]:
    """Serve one request of each size alone, and insert one batch: the
    shapes a quiet moment of this traffic uses.  Returns the warm-up
    inserts, which stay in the table."""
    for spec in sch.warm_specs:
        ctx.engine.submit(spec).result()
    if sch.warm_batch is None:
        return []
    k, v = sch.warm_batch
    ctx.engine.insert(ctx.table, *_insert_args(ctx, k, v), wait=True)
    return [InsertBatch(k, v, -np.inf, -np.inf)]


def drive(ctx, sch: Schedule, seconds: float, t0: float) -> Record:
    import jax
    engine = ctx.engine
    n = len(sch.arrivals)
    submitted = np.full(n, np.nan)
    resolved = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    kept = {}
    wanted = set(sch.sample.tolist())
    lock = threading.Lock()
    left = [n]
    all_done = threading.Event()

    def on_done(i):
        def cb(fut):
            resolved[i] = time.perf_counter() - t0
            ok[i] = fut.exception() is None
            with lock:
                left[0] -= 1
                if left[0] == 0:
                    all_done.set()
        return cb

    writer = None
    w_rec = {}
    if sch.w_arrivals is not None:
        writer = threading.Thread(target=_write_loop,
                                  args=(ctx, sch, t0, w_rec), daemon=True,
                                  name="bench-writer")
        writer.start()

    sleep_until(t0)
    for i in range(n):
        sleep_until(t0 + sch.arrivals[i])
        submitted[i] = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("bench.submit"):
            fut = engine.submit(sch.specs[i])
        if i in wanted:
            kept[i] = fut
        fut.add_done_callback(on_done(i))
    all_done.wait(timeout=max(0.0, t0 + seconds + DRAIN_SECONDS
                              - time.perf_counter()))
    if writer is not None:
        writer.join()

    rec = Record(window_s=seconds, sizes=sch.sizes, scheduled=sch.arrivals,
                 submitted=submitted, resolved=resolved, ok=ok,
                 read_failed=int(n - ok.sum()))
    rec.span_s = float(np.nanmax(resolved)) if ok.any() else seconds
    if writer is not None:
        rec.batches = w_rec["batches"]
        rec.insert_scheduled = sch.w_arrivals
        rec.insert_acked = w_rec["acked"]
        rec.insert_failed = w_rec["failed"]
    _collect(rec, sch, kept, ok)
    return rec


def _write_loop(ctx, sch: Schedule, t0: float, out: dict) -> None:
    import jax
    nb = len(sch.w_arrivals)
    issued = np.full(nb, np.inf)
    acked = np.full(nb, np.inf)

    def insert(j):
        issued[j] = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("bench.insert"):
            ctx.engine.insert(ctx.table, *_insert_args(
                ctx, sch.w_keys[j], sch.w_vals[j]), wait=True)
        acked[j] = time.perf_counter() - t0

    pool = ThreadPoolExecutor(ctx.cell.traffic["writer"]["threads"],
                              thread_name_prefix="bench-insert")
    futs = []
    for j in range(nb):
        sleep_until(t0 + sch.w_arrivals[j])
        futs.append(pool.submit(insert, j))
    done, _ = wait(futs, timeout=max(
        0.0, t0 + sch.w_arrivals[-1] + DRAIN_SECONDS - time.perf_counter()))
    failed = sum(1 for f in futs if f not in done or f.exception() is not None)
    pool.shutdown(wait=False, cancel_futures=True)
    out["batches"] = [InsertBatch(sch.w_keys[j], sch.w_vals[j], issued[j],
                                  acked[j]) for j in range(nb)]
    out["acked"] = acked
    out["failed"] = failed


def _collect(rec: Record, sch: Schedule, kept: dict, ok) -> None:
    """The sampled requests' queries, times and answers, on the host."""
    parts = {k: [] for k in ("lq", "uq", "sub", "res", "value", "refined")}
    for i in sch.sample:
        if not ok[i]:
            continue
        ans = kept[i].result()
        lq, uq = sch.specs[i].ranges
        parts["lq"].append(np.asarray(lq))
        parts["uq"].append(np.asarray(uq))
        m = len(lq)
        parts["sub"].append(np.full(m, rec.submitted[i]))
        parts["res"].append(np.full(m, rec.resolved[i]))
        parts["value"].append(np.asarray(ans.value, np.float64))
        parts["refined"].append(np.asarray(ans.refined, bool))
    cat = {k: (np.concatenate(v) if v else np.zeros(0)) for k, v in
           parts.items()}
    rec.lq, rec.uq = cat["lq"], cat["uq"]
    rec.q_submitted, rec.q_resolved = cat["sub"], cat["res"]
    rec.value, rec.refined = cat["value"], cat["refined"].astype(bool)

