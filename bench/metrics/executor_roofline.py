"""Share of the HBM roofline the served executables reach, in %.

Bytes are defined by the query's semantics, not by what a backend does.
Per range query: its two endpoints in and its answer out (8 bytes each);
per endpoint, a locate over the H segment boundaries (ceil(log2 H) reads)
and the deg+1 coefficients of the segment found; and for a query that
Q_rel refines, per endpoint a search over the n refinement keys
(ceil(log2 n) reads) and one CF read.  All values are 8-byte floats.
The refined share is that of the checked answers.  Bytes over executor
device time over the chip's HBM bandwidth (``peaks.json``).
"""
import math

WORD = 8


def query_bytes(h: int, n: int, deg: int, refined_share: float) -> float:
    endpoint = math.ceil(math.log2(max(h, 2))) * WORD + (deg + 1) * WORD
    refine = math.ceil(math.log2(max(n, 2))) * WORD + WORD
    return 3 * WORD + 2 * endpoint + refined_share * 2 * refine


def read(run):
    if run.trace is None or run.peaks is None or run.trace.executor_s <= 0:
        return None
    rec = run.record
    queries = float(rec.sizes[run.answered_in_trace()].sum())
    if not queries or not len(rec.refined):
        return None
    p = run.plan
    total = queries * query_bytes(p["h"], p["n"], p["deg"],
                                  float(rec.refined.mean()))
    return total / run.trace.executor_s / run.peaks["hbm_bytes_per_s"] * 100
