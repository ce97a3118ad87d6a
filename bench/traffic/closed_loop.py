"""Closed-loop traffic: a few clients, each sending its next request only
when the last one has been answered.

Parameters (a mix's JSON file):

* ``clients`` — concurrent clients; ``queries_per_request`` — range
  queries in each request, endpoints drawn from the table's keys (paper
  §7.1); ``pool_requests`` — distinct requests drawn from the seed, sent
  in turn.
* ``max_batch``, ``min_bucket``, ``max_bucket`` — the serving engine's
  admission cap and the bucket ladder it warms.
* ``check_requests`` — how many of the pool's requests, drawn from the
  seed, have their first answer compared with the reference.

The clients stop sending at the window's close; the rate is every query
answered over the time from the window's start to the last answer.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time

import numpy as np

from bench.data import make_queries_1d
from bench.harness import DRAIN_SECONDS, Record, seeds, sleep_until


@dataclasses.dataclass
class Schedule:
    specs: list
    sample: set


def prepare(ctx, params: dict, seed: int, seconds: float) -> Schedule:
    from repro.api import QuerySpec
    rng = np.random.default_rng(seeds(seed, 1))
    m, pool = params["queries_per_request"], params["pool_requests"]
    lq, uq = make_queries_1d(ctx.keys, m * pool, seed=seeds(seed, 2))
    specs = [QuerySpec(ctx.table, (lq[i * m:(i + 1) * m],
                                   uq[i * m:(i + 1) * m]))
             for i in range(pool)]
    sample = set(rng.choice(pool, min(pool, params["check_requests"]),
                            replace=False).tolist())
    return Schedule(specs, sample)


def warm(ctx, sch: Schedule) -> list:
    """One request per client, each alone: the one shape this mix uses."""
    for spec in sch.specs[:ctx.cell.traffic["clients"]]:
        ctx.engine.submit(spec).result()
    return []


def drive(ctx, sch: Schedule, seconds: float, t0: float) -> Record:
    import jax
    engine = ctx.engine
    turn = itertools.count()
    lock = threading.Lock()
    rows = []          # (request, pool index, sent, resolved, ok)
    answers = {}

    def client():
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                return
            k = next(turn)
            i = k % len(sch.specs)
            with jax.profiler.TraceAnnotation("bench.submit"):
                fut = engine.submit(sch.specs[i])
            try:
                ans = fut.result(timeout=DRAIN_SECONDS)
                ok = True
            except Exception:
                ans, ok = None, False
            done = time.perf_counter() - t0
            with lock:
                rows.append((k, i, now, done, ok))
                if ok and i in sch.sample and i not in answers:
                    answers[i] = (now, done, ans)
            if not ok:
                return

    sleep_until(t0)
    threads = [threading.Thread(target=client, daemon=True,
                                name=f"bench-client-{c}")
               for c in range(ctx.cell.traffic["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 2 * DRAIN_SECONDS)
    rows.sort()
    sent = np.array([r[2] for r in rows])
    res = np.array([r[3] for r in rows])
    ok = np.array([r[4] for r in rows], bool)
    sizes = np.array([len(sch.specs[r[1]]) for r in rows])
    rec = Record(window_s=seconds, sizes=sizes, scheduled=sent,
                 submitted=sent, resolved=res, ok=ok,
                 read_failed=int((~ok).sum()))
    rec.span_s = float(res[ok].max()) if ok.any() else seconds
    parts = [[], [], [], [], [], []]
    for i, (sub, done, ans) in sorted(answers.items()):
        lq, uq = sch.specs[i].ranges
        m = len(lq)
        for p, v in zip(parts, (lq, uq, np.full(m, sub), np.full(m, done),
                                np.asarray(ans.value, np.float64),
                                np.asarray(ans.refined, bool))):
            p.append(np.asarray(v))
    cat = [np.concatenate(p) if p else np.zeros(0) for p in parts]
    rec.lq, rec.uq, rec.q_submitted, rec.q_resolved, rec.value = cat[:5]
    rec.refined = cat[5].astype(bool)
    return rec
