"""The control of a cell's comparison: the plain reference, computed in
float32, put in the program's place.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed this runs the cell as ``bench/run.py`` does (its own build,
warm-up and window) and then reads the compared numbers twice over the
same sampled queries: once for the program's answers, once for the
control's.  The control answers every query over the records the read
had to see, exact to float32 (float64 is what the configuration serves
in), so each of its answers counts as refined.  It has to come out as not
correct; the program has to come out as correct.  One line per seed:
``control: {...}``.  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


def control_numbers(rec, truth, lo, hi, bound, limits) -> dict:
    """The compared numbers of the float32 reference in the program's
    place, over the run's sampled queries."""
    from bench import reference
    ans = reference.control_answers(truth, rec.lq, rec.uq, rec.q_submitted)
    return reference.compare(ans, lo, hi, np.ones(len(ans), bool), bound,
                             limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness, reference
    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_tpu(cell.chips)
    peaks = harness.device_peaks(cell.bench_dir, devices[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        got = {}

        def hook(rec, truth, lo, hi, bound, limits):
            got["control"] = control_numbers(rec, truth, lo, hi, bound,
                                             limits)
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), devices, peaks,
                               control=hook)
        print("control: " + json.dumps({
            "workload": cell.name, "seed": seed,
            "program_correct": out["correct"], "program": out["check"],
            "control_correct": reference.passed(got["control"]),
            "control": got["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
