"""Tests of the benchmark itself: ``python -m pytest bench/tests``.

They run on the CPU at small sizes (the harness's look for a chip is
patched out where a test drives a run) and import the program from
``src/``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402
import shutil  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

SMALL_ROWS = 20_000


def copy_benchmark(dest: Path, rows: int = SMALL_ROWS) -> Path:
    """BENCHMARK.json and the benchmark's files (tests left out) under
    ``dest``, every configuration cut to ``rows`` for the CPU."""
    shutil.copy(ROOT / "BENCHMARK.json", dest)
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg["data"]["rows"] = rows
        f.write_text(json.dumps(cfg))
    return dest


@pytest.fixture
def small_root(tmp_path):
    return copy_benchmark(tmp_path)


def run_small(root: Path, workload: str, seconds: float = 2.0,
              rate: float = 40.0, seed: int = 2**31 + 17, **traffic):
    """One run of a cell on the CPU, the harness's look for a chip left
    out; returns the result object."""
    import jax
    from bench import harness
    cell = harness.load_cell(root, workload)
    if "rate_rps" in cell.traffic:
        cell.traffic["rate_rps"] = rate
    cell.traffic.update(traffic)
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            jax.devices(), None)
