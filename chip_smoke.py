"""Drive the served query path once on a TPU, at the paper's data sizes.

    python chip_smoke.py              # one chip: build, compile, serve, update
    python chip_smoke.py --chips 4    # four chips: sharded vs unsharded only

One process, no children.  Every phase prints its own lines; any failed
check raises, and the process exits non-zero.  The last line of a passing
run is the JSON object ``{"ok": true, "device": {...}}``.

Phases (one chip):

1. device  — ``jax.devices()[0]`` must be a TPU; there is no CPU fallback.
2. build   — the eight tables ``AggregateService`` declares
             (``serve/aggregates.py``: TWEET COUNT, HKI SUM/MAX/MIN, OSM
             COUNT2D/SUM2D/MAX2D/MIN2D) fitted by ``PolyFit.fit`` with
             ``backend="xla"``; the SUM table is fitted ``dynamic=True``
             for the update phase.  Per table: build seconds, device bytes
             (``size_bytes``) and the device its plan lives on.
3. compile — ``ServingEngine.warmup`` AOT-compiles the one bucket the serve
             phase uses (1024 queries per table); reported as set-up.
4. serve   — per kind, a few batches of 1024 queries with endpoints drawn
             as in paper §7.1 (``make_queries_1d``/``make_queries_2d``) go
             through ``ServingEngine.submit`` (the worker-thread path).  Every
             answer is checked against an exact answer computed on the host
             in plain numpy: ``|answer - truth| <= bound + slack``.  No
             executable may compile inside this phase.
5. update  — a batch is inserted into the dynamic SUM table through
             ``ServingEngine.insert(..., wait=True)``, read back with
             covering queries, deleted, and read again; each answer must
             match the exact answer over the live records within the bound.
             A flush then merges the buffer into a fresh plan, whose
             executable is pre-compiled on the swap; none may fail.

``--chips 4`` runs only the sharded path and what it is compared with: the
eight tables with ``shards=4`` (1-D key ranges, 2-D Morton z-ranges), served
through ``ServingEngine``, against the same queries on the same (unsharded)
plans on one device of the host, and against the host truth.

Sizes, and every cut from the paper's deployment:

* 1-D tables: 1,000,000 rows — the paper's TWEET scale (1M); HKI's 0.9M
  minute bars round up to the shared row count.
* 2-D tables: 250,000 points, cut from the paper's 100M-point OSM set.  At
  100M the exact-refinement merge-sort tree alone is about 22 GB per
  (L, n) f64 array (ROADMAP R2), more than a 16 GB chip holds.  At 1M
  points the host build of the CF-fitted COUNT2D table alone took over ten
  minutes on an 8-core host (its quadtree solves an LP per region), so the
  eight tables plus their compiles would not fit the 1200 s this script
  is given.  At 250,000 points all eight tables (1-D at 1M) built in 358 s
  on that host.
* ``--chips 4``: 100,000 rows and 50,000 points.  This path checks that
  plans partition over four chips and that the sharded answers agree with
  the unsharded ones, not scale; its host build is kept short because a
  four-chip call holds four chips while the host fits.
* Serving uses ``backend="xla"``: Mosaic refuses every Pallas kernel of
  the repository today (ROADMAP R1).

Answers are f64 on the chip, which emulates it, so each check allows a
rounding slack of ``SLACK`` times (|truth| + bound) on top of the bound.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N1 = 1_000_000
N2 = 250_000
N1_SHARDED = 100_000
N2_SHARDED = 50_000
BATCH = 1024           # queries per request, and the one warmed bucket
BATCHES = 3            # requests per kind in the serve phase
N_UPDATE = 512         # records inserted, then deleted, in the update phase
UPDATE_WINDOW = 0.005  # share of the key range those records fall in
SLACK = 1e-9

KINDS = ("count", "sum", "max", "min", "count2d", "sum2d", "max2d", "min2d")


def require_tpu(count: int = 1):
    """The devices to run on; exits (non-zero) unless they are TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX's first device is "
                         f"{devs[0].platform!r}; nothing is run elsewhere")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: --chips {count} needs {count} TPU "
                         f"devices, JAX sees {len(devs)}")
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


# ---------------------------------------------------------------------------
# queries and their exact answers, in plain numpy on the host
# ---------------------------------------------------------------------------

def make_requests(kind: str, datasets, n: int, seed: int):
    """``n`` queries for ``kind`` drawn as in paper §7.1."""
    from repro.data import make_queries_1d, make_queries_2d
    data = datasets[kind]
    if kind in ("count", "sum", "max", "min"):
        keys = data if kind == "count" else data[0]
        return make_queries_1d(keys, n, seed=seed)
    x0, x1, y0, y1 = make_queries_2d(data[0], data[1], n, seed=seed)
    if kind in ("max2d", "min2d"):
        return x1, y1            # dominance corners (DESIGN.md §12)
    return x0, x1, y0, y1


class Truth:
    """Exact answers over a table's live records — sorted arrays and
    slices, independent of the index and its refinement structures."""

    def __init__(self, kind: str, data):
        self.kind = kind
        if kind in ("count", "sum", "max", "min"):
            keys = data if kind == "count" else data[0]
            meas = np.ones_like(keys) if kind == "count" else data[1]
            order = np.argsort(keys, kind="stable")
            self.k = np.asarray(keys, np.float64)[order]
            self.m = np.asarray(meas, np.float64)[order]
            self.cf = np.concatenate([[0.0], np.cumsum(self.m)])
        else:
            xs, ys = data[0], data[1]
            ws = np.ones_like(xs) if kind == "count2d" else data[2]
            order = np.argsort(xs, kind="stable")
            self.x = np.asarray(xs, np.float64)[order]
            self.y = np.asarray(ys, np.float64)[order]
            self.w = np.asarray(ws, np.float64)[order]

    def __call__(self, *q) -> np.ndarray:
        q = [np.asarray(c, np.float64) for c in q]
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
            out = np.empty(len(lx))
            for t, (a, b) in enumerate(zip(i, j)):
                ys = self.y[a:b]
                out[t] = self.w[a:b][(ys > ly[t]) & (ys <= uy[t])].sum()
            return out
        u, v = q                            # dominance: x <= u, y <= v
        j = np.searchsorted(self.x, u, side="right")
        red = np.max if kind == "max2d" else np.min
        empty = -np.inf if kind == "max2d" else np.inf
        out = np.empty(len(u))
        for t, b in enumerate(j):
            sel = self.w[:b][self.y[:b] <= v[t]]
            out[t] = red(sel) if len(sel) else empty
        return out


def check(kind: str, answers, truth: np.ndarray, bound: float,
          what: str) -> float:
    """Largest |answer - truth| / bound; raises past bound + slack."""
    ans = np.asarray(answers, np.float64)
    if ans.shape != truth.shape or not np.all(np.isfinite(ans)):
        raise AssertionError(f"{what} {kind}: answers of shape {ans.shape} "
                             f"(want {truth.shape}), finite="
                             f"{bool(np.all(np.isfinite(ans)))}")
    err = np.abs(ans - truth)
    limit = bound + SLACK * (np.abs(truth) + bound)
    bad = np.flatnonzero(err > limit)
    if len(bad):
        t = bad[0]
        raise AssertionError(
            f"{what} {kind}: {len(bad)} answers out of bound, first: "
            f"answer={ans[t]!r} truth={truth[t]!r} bound={bound!r}")
    return float(err.max() / bound)


def plan_devices(plan) -> str:
    import jax
    devs = {d for leaf in jax.tree.leaves(plan) for d in leaf.devices()}
    return ",".join(sorted(str(d) for d in devs))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def build_phase(n1: int, n2: int, **spec_kw):
    """Fit the service's eight tables; prints one line per table."""
    from repro.api import PolyFit
    from repro.serve.aggregates import aggregate_tables
    datasets, specs, domains = aggregate_tables(n1, n2, **spec_kw)
    if not spec_kw.get("shards"):
        specs["sum"] = dataclasses.replace(specs["sum"], dynamic=True)
    t0 = time.perf_counter()
    session = PolyFit.fit(datasets, specs, backend="xla", min_bucket=BATCH)
    wall = time.perf_counter() - t0
    secs, size = session.build_seconds(), session.size_bytes()
    for kind in KINDS:
        rows = n2 if kind.endswith("2d") else n1
        print(f"build: {kind:7s} rows={rows} seconds={secs[kind]:.1f} "
              f"bytes={size[kind]} on={plan_devices(session.plan(kind))}",
              flush=True)
    print(f"build: wall_seconds={wall:.1f} (tables fit concurrently)",
          flush=True)
    return session, datasets, wall


def serve_phase(engine, session, datasets, batches: int, seed: int):
    """Per kind: submit, wait, check; returns {kind: (max err/bound,
    refined share)} and the phase seconds."""
    out = {}
    compiles = engine.stats.aot_compiles
    t_all = time.perf_counter()
    for kind in KINDS:
        truth_of = Truth(kind, datasets[kind])
        reqs = [make_requests(kind, datasets, BATCH, seed + b)
                for b in range(batches)]
        t0 = time.perf_counter()
        futures = [engine.submit(_spec(kind, r)) for r in reqs]
        answers = [f.result() for f in futures]
        secs = time.perf_counter() - t0
        bound = session.budget(kind).bound(session.spec(kind).agg)
        worst, refined = 0.0, 0
        for r, a in zip(reqs, answers):
            worst = max(worst, check(kind, a.value, truth_of(*r), bound,
                                     "serve"))
            refined += int(np.asarray(a.refined).sum())
        share = refined / (batches * BATCH)
        out[kind] = (worst, share)
        print(f"serve: {kind:7s} batches={batches}x{BATCH} "
              f"seconds={secs:.3f} max_err_over_bound={worst:.4g} "
              f"refined_share={share:.4f}", flush=True)
    seconds = time.perf_counter() - t_all
    new = engine.stats.aot_compiles - compiles
    if new:
        raise AssertionError(f"serve: {new} executables compiled inside the "
                             "serve phase (warmup must cover it)")
    print(f"serve: seconds={seconds:.1f} compiles_in_phase=0", flush=True)
    return out, seconds


def _spec(kind: str, ranges):
    import jax.numpy as jnp
    from repro.api import QuerySpec
    return QuerySpec(kind, tuple(jnp.asarray(c) for c in ranges))


def update_phase(engine, session, datasets, seed: int):
    """Insert, read back, delete, read back on the dynamic SUM table."""
    from repro.data import make_queries_1d
    keys, vals = (np.asarray(a, np.float64) for a in datasets["sum"])
    rng = np.random.default_rng(seed)
    # a late batch of minute bars: fresh keys inside one narrow window of
    # the series, off the existing key set so a delete names exactly the
    # inserted record.  The merge re-fits only the segments the window
    # touches (a batch spread over the whole domain re-fits every segment)
    width = UPDATE_WINDOW * (keys.max() - keys.min())
    start = rng.uniform(keys.min(), keys.max() - width)
    new_k = rng.uniform(start, start + width, N_UPDATE)
    new_k = new_k[~np.isin(new_k, keys)]
    new_v = rng.uniform(vals.min(), vals.max(), len(new_k))
    bound = session.budget("sum").bound("sum")
    mass = float(new_v.sum())
    if mass <= 2 * bound:
        raise AssertionError("update: inserted mass must exceed the bound "
                             "for a lost write to be visible")
    lo = np.nextafter(keys.min(), -np.inf)
    t0 = time.perf_counter()
    engine.insert("sum", new_k, new_v, wait=True)
    ins_s = time.perf_counter() - t0

    def read(live_k, live_v, what):
        lq, uq = make_queries_1d(live_k, BATCH - 1, seed=seed + 99)
        lq = np.concatenate([[lo], lq])        # one query covers all rows
        uq = np.concatenate([[keys.max()], uq])
        res = engine.submit(_spec("sum", (lq, uq))).result()
        truth = Truth("sum", (live_k, live_v))(lq, uq)
        return check("sum", res.value, truth, bound, what)

    after_ins = read(np.concatenate([keys, new_k]),
                     np.concatenate([vals, new_v]), "update(insert)")
    t0 = time.perf_counter()
    engine.delete("sum", new_k, wait=True)
    del_s = time.perf_counter() - t0
    after_del = read(keys, vals, "update(delete)")
    # merge what the buffer holds into a fresh plan (and join any merge the
    # inserts' drift started): the swap runs the plan-swap pre-compile, and
    # no merge thread outlives the phase
    t0 = time.perf_counter()
    engine.flush("sum")
    flush_s = time.perf_counter() - t0
    after_flush = read(keys, vals, "update(flush)")
    stats = engine.stats
    if stats.aot_precompile_failures:
        raise AssertionError(f"update: {stats.aot_precompile_failures} "
                             "plan-swap precompiles failed")
    print(f"update: sum records={len(new_k)} mass={mass:.6g} "
          f"bound={bound:.6g} insert_seconds={ins_s:.3f} "
          f"delete_seconds={del_s:.3f} flush_seconds={flush_s:.1f} "
          f"max_err_over_bound(insert)={after_ins:.4g} "
          f"max_err_over_bound(delete)={after_del:.4g} "
          f"max_err_over_bound(flush)={after_flush:.4g} "
          f"precompiles={stats.aot_precompiles} "
          f"precompile_failures=0", flush=True)
    return after_ins, after_del, after_flush


def run_single(n1: int = N1, n2: int = N2, batches: int = BATCHES,
               seed: int = 0) -> dict:
    from repro.serve import ServingEngine
    session, datasets, build_s = build_phase(n1, n2)
    engine = ServingEngine(session, max_batch=BATCH)
    try:
        t0 = time.perf_counter()
        n_exec = engine.warmup(max_bucket=BATCH)
        compile_s = time.perf_counter() - t0
        print(f"compile: executables={n_exec} bucket={BATCH} "
              f"seconds={compile_s:.1f} (set-up)", flush=True)
        served, serve_s = serve_phase(engine, session, datasets, batches,
                                      seed)
        update_phase(engine, session, datasets, seed)
    finally:
        engine.shutdown()
    return {"build_s": build_s, "compile_s": compile_s, "serve_s": serve_s,
            "served": served}


# ---------------------------------------------------------------------------
# four chips: the sharded path and its unsharded reference
# ---------------------------------------------------------------------------

def run_sharded(nshards: int = 4, n1: int = N1_SHARDED,
                n2: int = N2_SHARDED, seed: int = 0) -> dict:
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor
    from repro.engine import execute
    from repro.serve import ServingEngine
    session, datasets, _ = build_phase(n1, n2, shards=nshards)
    requests = {k: make_requests(k, datasets, BATCH, seed) for k in KINDS}

    def unsharded(kind):
        return execute(session.plan(kind),
                       tuple(jnp.asarray(c) for c in requests[kind]),
                       backend="xla", eps_rel=session.resolve_rel(kind),
                       min_bucket=BATCH)

    # each kind's first dispatch compiles its executors: issue every kind
    # at once, sharded through the engine's workers and unsharded on a
    # pool, so the sixteen compiles overlap
    engine = ServingEngine(session, max_batch=BATCH, workers=len(KINDS))
    out = {}
    try:
        t0 = time.perf_counter()
        futures = {k: engine.submit(_spec(k, requests[k])) for k in KINDS}
        with ThreadPoolExecutor(len(KINDS)) as pool:
            refs = dict(zip(KINDS, pool.map(unsharded, KINDS)))
        got = {k: f.result() for k, f in futures.items()}
        print(f"dispatch: first batch of every kind, compiles included, "
              f"seconds={time.perf_counter() - t0:.1f}", flush=True)
        for kind in KINDS:
            sp = session._table(kind).sharded.shard(session.plan(kind))
            lead = sp.leaf_coeffs if kind.endswith("2d") else sp.coeffs
            shards = [str(s.device) for s in lead.addressable_shards]
            if len(set(shards)) != nshards:
                raise AssertionError(f"sharded {kind}: shards on {shards}, "
                                     f"want {nshards} distinct devices")
            a = np.asarray(got[kind].value, np.float64)
            b = np.asarray(refs[kind].answer, np.float64)
            identical = bool(np.array_equal(a, b))
            diff = float(np.max(np.abs(a - b)))
            if diff > SLACK * (float(np.max(np.abs(b))) + 1.0):
                raise AssertionError(f"sharded {kind}: differs from the "
                                     f"unsharded answer by {diff!r}")
            bound = session.budget(kind).bound(session.spec(kind).agg)
            worst = check(kind, a, Truth(kind, datasets[kind])(
                *requests[kind]), bound, "sharded")
            out[kind] = (identical, worst)
            print(f"sharded: {kind:7s} shards={','.join(shards)} "
                  f"unsharded_on={plan_devices(session.plan(kind))} "
                  f"bit_identical={identical} max_abs_diff={diff:.3g} "
                  f"max_err_over_bound={worst:.4g}", flush=True)
    finally:
        engine.shutdown()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its reference")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    devs = require_tpu(args.chips)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(nshards=4, n1=N1_SHARDED, n2=N2_SHARDED)
    else:
        run_single(n1=N1, n2=N2, batches=BATCHES)
    print(f"total: seconds={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
