"""Show acknowledged inserts going invisible while a merge installs its plan.

    python3 bench/swap_witness.py [--rows 20000] [--batches 200]

A dynamic HKI SUM table (``TableSpec(dynamic=True)``, the program's
defaults) takes batches of 32 positive late bars from one thread, each
``PolyFit.insert`` returning before the next, while a second thread reads
the SUM over the whole key range again and again.  Every value is
positive, so once an insert has returned no later read may total less.
The script prints how often a read did (``reads_fell``) and the largest
fall.  It reads the session directly, not through the serving engine, so
it is a second witness beside ``hki-sum.rw-online``'s own check.  It runs
on the CPU or the chip, whichever JAX finds.
"""
import argparse
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=20_000)
    ap.add_argument("--batches", type=int, default=200)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import jax
    from bench.data import hki_series
    from repro.api import ErrorBudget, PolyFit, QuerySpec, TableSpec
    ts, v = hki_series(args.rows, seed=args.seed)
    spec = TableSpec("sum", ErrorBudget(abs=100 * np.abs(v).mean()), deg=2,
                     dynamic=True)
    s = PolyFit.fit({"s": (ts, v)}, {"s": spec}, backend="xla")
    whole = QuerySpec("s", (np.array([ts[0] - 1.0]),
                            np.array([ts[-1] + 1e6])), rel=None)
    rng = np.random.default_rng(args.seed)
    done = threading.Event()
    falls = []

    def writer():
        lo = ts[-1] - 0.005 * (ts[-1] - ts[0])
        for _ in range(args.batches):
            s.insert("s", rng.uniform(lo, ts[-1], 32),
                     rng.uniform(v.min(), v.max(), 32))
            time.sleep(0.01)
        done.set()

    def reader():
        prev = None
        while not done.is_set():
            tot = float(np.asarray(s.query(whole).value)[0])
            if prev is not None and tot < prev - 1.0:
                falls.append(prev - tot)
            prev = tot

    threads = [threading.Thread(target=writer), threading.Thread(target=reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s.flush("s")
    print(f"swap_witness: device={jax.devices()[0].device_kind} "
          f"rows={args.rows} batches={args.batches} "
          f"reads_fell={len(falls)} "
          f"largest_fall={max(falls, default=0.0)!r} "
          f"mean_value={float(np.mean(v))!r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
