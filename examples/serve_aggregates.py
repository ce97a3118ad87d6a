"""End-to-end serving driver (the paper's deployment scenario): an analytics
service answering batched approximate range-aggregate requests against one
PolyFit session — declarative TableSpecs with a shared ErrorBudget, grouped
QueryBatch dispatch, backend selection (XLA reference vs Pallas kernels),
fused Q_rel refinement, and latency accounting.

    PYTHONPATH=src python examples/serve_aggregates.py --batches 200
    PYTHONPATH=src python examples/serve_aggregates.py --backend pallas
    PYTHONPATH=src python examples/serve_aggregates.py --mixed
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.api import QueryBatch, QuerySpec
from repro.compile_cache import enable_compile_cache
from repro.serve import AggregateService


def _random_request(srv, kind, n, rng):
    if kind in ("count", "max"):
        lo, hi = srv.domains[kind]
        a = rng.uniform(lo, hi, n); c = rng.uniform(lo, hi, n)
        return (jnp.asarray(np.minimum(a, c)), jnp.asarray(np.maximum(a, c)))
    if kind == "max2d":   # dominance corners (DESIGN.md §12)
        x1, y1 = srv.domains[kind]
        return (jnp.asarray(rng.uniform(x1 - 40, x1, n)),
                jnp.asarray(rng.uniform(y1 - 40, y1, n)))
    x0, x1, y0, y1 = srv.domains[kind]
    ax = rng.uniform(x0, x1, n); bx = ax + rng.uniform(0.1, 5, n)
    ay = rng.uniform(y0, y1, n); by = ay + rng.uniform(0.1, 5, n)
    return tuple(map(jnp.asarray, (ax, bx, ay, by)))


def run_mixed(srv, batches, batch_size, rng):
    """The declarative path: one QueryBatch interleaving all three
    aggregate kinds per iteration, answered in request order."""
    sub = max(batch_size // 4, 1)
    times = []
    for _ in range(batches):
        batch = QueryBatch.of(
            QuerySpec("count", _random_request(srv, "count", sub, rng)),
            QuerySpec("sum2d", _random_request(srv, "sum2d", sub, rng)),
            QuerySpec("max", _random_request(srv, "max", sub, rng)),
            QuerySpec("max2d", _random_request(srv, "max2d", sub, rng)))
        t0 = time.perf_counter()
        results = srv.session.query(batch)
        jax.block_until_ready([r.answer for r in results])
        times.append(time.perf_counter() - t0)
    ts = np.array(times[1:] or times)
    print(f"  mixed   : p50 {np.median(ts)*1e3:7.2f} ms/batch "
          f"({np.median(ts)/(4*sub)*1e6:6.2f} us/query, "
          f"4 specs x {sub} queries)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--backend", choices=("xla", "pallas", "pallas_scan", "ref"),
                    default="xla")
    ap.add_argument("--mixed", action="store_true",
                    help="also time mixed-aggregate QueryBatch dispatch")
    args = ap.parse_args()

    enable_compile_cache()
    srv = AggregateService(backend=args.backend)
    rng = np.random.default_rng(0)
    stats = {k: [] for k in ("count", "max", "count2d", "sum2d", "max2d")}
    refined = {k: 0 for k in stats}
    total = {k: 0 for k in stats}
    for b in range(args.batches):
        kind = ("count", "max", "count2d", "sum2d", "max2d")[b % 5]
        req = _random_request(srv, kind, args.batch_size, rng)
        t0 = time.perf_counter()
        res = srv.serve(kind, *req)
        dt = time.perf_counter() - t0
        stats[kind].append(dt)
        refined[kind] += int(np.asarray(res.refined).sum())
        total[kind] += args.batch_size

    print(f"\n[server] served {args.batches} batches x {args.batch_size} "
          f"requests (backend={args.backend})")
    for k, ts in stats.items():
        if not ts:
            continue
        ts = np.array(ts[1:] or ts)  # drop compile batch
        print(f"  {k:8s}: p50 {np.median(ts)*1e3:7.2f} ms/batch "
              f"({np.median(ts)/args.batch_size*1e6:6.2f} us/query)  "
              f"refine-rate {refined[k]/max(total[k],1):.3f}")
    if args.mixed:
        run_mixed(srv, max(args.batches // 3, 2), args.batch_size, rng)


if __name__ == "__main__":
    main()
