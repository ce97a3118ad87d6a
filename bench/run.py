"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a workload of ``BENCHMARK.json``.  The run refuses (exit
code non-zero, no result line) unless JAX sees the TPUs the cell asks for.
With ``--trace 0`` the result's metrics are the cell's end-to-end ones;
with ``--trace 1`` a profiler trace is taken of part of the window and the
metrics are the cell's per-layer ones.  The last line on standard output
is the result as one JSON object; the compared numbers, each beside its
limit, are the last lines on standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_tpu(cell.chips)
    peaks = harness.device_peaks(cell.bench_dir, devices[0].device_kind)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, devices, peaks)
    harness.result_line(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
