"""The harness finds a cell by name from files alone, runs it, and refuses
to run anywhere but on the chips the cell asks for."""
import json
import os
import subprocess
import sys

from bench import harness
from conftest import ROOT, copy_benchmark, run_small

TINY_CONFIG = {
    "name": "tiny-count", "source": "test",
    "data": {"generator": "tweet_latitudes", "rows": 5000},
    "table": {"name": "c", "agg": "count", "deg": 2, "abs": 100,
              "rel": 0.01, "spec": {}},
    "limits": {"err_over_bound": 1.0, "rel_err": 0.01,
               "refined_err_over_bound": 0.0},
}
TINY_MIX = {"generator": "open_loop", "rate_rps": 30,
            "ranges_per_request": [2, 3], "max_batch": 256,
            "min_bucket": 64, "max_bucket": 256, "check_requests": 1000}
TINY_METRIC = '''"""Read requests the window sent."""


def read(run):
    return len(run.record.scheduled)
'''


def test_a_cell_from_new_files_alone(tmp_path):
    root = copy_benchmark(tmp_path)
    b = root / "bench"
    (b / "configs" / "tiny-count.json").write_text(json.dumps(TINY_CONFIG))
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(TINY_MIX))
    (b / "metrics" / "tiny.requests.py").write_text(TINY_METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-count", "source": "test",
                            "file": "bench/configs/tiny-count.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.cell", "config": "tiny-count",
                              "traffic": "tiny-mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "tiny.requests", "unit": "requests",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(root, "tiny.cell")
    assert cell.config["data"]["rows"] == 5000
    assert cell.traffic["ranges_per_request"] == [2, 3]
    names = [m["name"] for m in cell.end_to_end]
    assert "tiny.requests" in names and "queries_per_s" not in names
    out = run_small(root, "tiny.cell", seconds=1.0, rate=30)
    assert out["correct"], out["check"]
    assert out["metrics"]["tiny.requests"]["value"] == 30
    assert set(out["metrics"]) == set(names)


def test_online_cell_runs_and_checks(small_root):
    out = run_small(small_root, "tweet-count.paper-online")
    assert out["correct"], out["check"]
    assert out["attempted"] == 80 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "read_p50_ms"}
    assert list(out)[-1] == "check"


def test_closed_cell_runs_and_checks(small_root):
    out = run_small(small_root, "tweet-count.dashboard-closed",
                    pool_requests=8, check_requests=4)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"setup_s", "queries_per_s"}


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "tweet-count.paper-online", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_with_only_the_benchmark_files(tmp_path):
    copy_benchmark(tmp_path)
    p = _run_py(tmp_path, {"JAX_PLATFORMS": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


DYN_CONFIG = {
    "name": "tiny-sum-dynamic", "source": "test",
    "data": {"generator": "hki_series", "rows": 5000},
    "table": {"name": "s", "agg": "sum", "deg": 2, "abs": 100,
              "abs_scale": "mean_abs_measure", "rel": 0.01,
              "spec": {"dynamic": True, "capacity": 1024,
                       "background": True, "auto_refit": False}},
    "limits": {"err_over_bound": 1.0, "rel_err": 0.01,
               "refined_err_over_bound": 1e-6},
}
RW_MIX = dict(TINY_MIX, writer={"batch": 32, "rate_batches_per_s": 10,
                                "key_window": 0.005, "threads": 8})


def _rw_root(tmp_path):
    """A throwaway read-write cell whose table never merges (so no plan
    is swapped while it is read)."""
    root = copy_benchmark(tmp_path)
    b = root / "bench"
    (b / "configs" / "tiny-sum-dynamic.json").write_text(
        json.dumps(DYN_CONFIG))
    (b / "traffic" / "tiny-rw.json").write_text(json.dumps(RW_MIX))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-sum-dynamic", "source": "test",
                            "file": "bench/configs/tiny-sum-dynamic.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.rw", "config":
                              "tiny-sum-dynamic", "traffic": "tiny-rw",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "insert_visible_p99_ms",
                               "unit": "ms", "better": "lower",
                               "bound": 0.25, "source": "host_clock",
                               "workloads": ["tiny.rw"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_writer_inserts_are_checked_for_visibility(tmp_path):
    out = run_small(_rw_root(tmp_path), "tiny.rw", seconds=2.0, rate=30)
    assert out["correct"], out["check"]
    assert out["attempted"] == 60 + 20
    assert out["metrics"]["insert_visible_p99_ms"]["value"] > 0


def test_dropped_inserts_are_not_correct(tmp_path, monkeypatch):
    """The fault of a step that returns its state unchanged: every insert
    is acknowledged and none is applied."""
    from repro.engine.dynamic import DynamicEngine
    monkeypatch.setattr(DynamicEngine, "insert", lambda self, *a, **k: None)
    out = run_small(_rw_root(tmp_path), "tiny.rw", seconds=2.0, rate=30)
    assert out["correct"] is False
    assert out["check"]["err_over_bound"]["value"] > 1.0
