"""The reduction of the program's spans (``bench/spans.py``): on a
hand-made snapshot, on hand-made planes, and the tool on the CPU at a
small size (spans on and off; a trace needs the chip)."""
import numpy as np
import pytest

from bench import spans as bs
from bench.trace import Event, Plane
from conftest import copy_benchmark

MS = 1_000_000


def _snapshot(rows, dropped=0, none_compiles=0):
    """rows: (seq, name, t0_ms, t1_ms, parent, request, dispatch, compiles)."""
    cols = list(zip(*rows))
    snap = {k: np.asarray(v, dt) for k, v, dt in zip(
        ("seq", "name", "t0", "t1", "parent", "request", "dispatch",
         "compiles"), cols,
        (np.int64, str, np.int64, np.int64, np.int64, np.int64, np.int64,
         np.int32))}
    snap["t0"] = snap["t0"] * MS
    snap["t1"] = snap["t1"] * MS
    dur = snap["t1"] - snap["t0"]
    own = dur.copy()
    for k, p in enumerate(snap["parent"]):
        if p >= 0:
            own[list(snap["seq"]).index(p)] -= dur[k]
    snap["by_name"] = {
        n: {"count": int((snap["name"] == n).sum()),
            "total_ns": int(dur[snap["name"] == n].sum()),
            "self_ns": int(own[snap["name"] == n].sum()),
            "compiles": int(snap["compiles"][snap["name"] == n].sum())}
        for n in set(snap["name"].tolist())}
    snap.update(dropped=dropped, none_compiles=none_compiles, threads={})
    return snap


def _two_dispatches():
    s = "polyfit.serve."
    return _snapshot([
        # request 0 alone in dispatch 1; requests 1, 2 together in 2
        (0, s + "batch", 10, 20, -1, -1, -1, 0),
        (1, s + "queued", 8, 10, -1, 0, 1, 0),
        (2, s + "dispatch", 10, 20, 0, -1, 1, 0),
        (3, s + "prepare", 10, 14, 2, -1, -1, 2),
        (4, "polyfit.aot.lookup", 14, 15, 2, -1, -1, 0),
        (5, s + "execute", 15, 16, 2, -1, -1, 0),
        (6, s + "device_wait", 16, 17, 2, -1, -1, 0),
        (7, s + "scatter", 17, 20, 2, -1, -1, 1),
        (8, s + "batch", 30, 40, -1, -1, -1, 0),
        (9, s + "queued", 21, 30, -1, 1, 2, 0),
        (10, s + "queued", 25, 30, -1, 2, 2, 0),
        (11, s + "dispatch", 30, 40, 8, -1, 2, 0),
        (12, s + "prepare", 30, 32, 11, -1, -1, 0),
        (13, "polyfit.aot.compile", 31, 32, 12, -1, -1, 1),
        (14, s + "execute", 32, 34, 11, -1, -1, 0),
        (15, s + "device_wait", 34, 38, 11, -1, -1, 0),
        (16, s + "scatter", 38, 40, 11, -1, -1, 0),
    ], none_compiles=3)


def test_span_numbers_on_a_hand_made_snapshot():
    out = bs.span_numbers(_two_dispatches(), (0, 50 * MS))
    assert out["dispatches"] == 2
    # prepare self time: 4 ms, and 2 ms less its 1 ms compile child
    assert out["prepare_ms_per_dispatch"] == pytest.approx((4 + 1) / 2)
    assert out["scatter_ms_per_dispatch"] == pytest.approx((3 + 2) / 2)
    assert out["call_ms_per_dispatch"] == pytest.approx((1 + 1 + 2 + 4) / 2)
    # compiles under a dispatch: prepare 2, scatter 1, the AOT compile 1
    assert out["dispatch_compiles"] == 4
    assert out["none_compiles"] == 3
    assert out["queue_wait_p99_ms"] == pytest.approx(
        np.percentile([2, 9, 5], 99))
    assert out["worker_busy_share_pct"] == pytest.approx(20 / 50 * 100)
    # requests: 2 + 10, 9 + 10, 5 + 10
    assert out["request_path_ms"] == pytest.approx(15.0)
    assert out["by_name"]["polyfit.serve.dispatch"]["mean_ms"] == 10
    assert out["by_name"]["polyfit.serve.dispatch"]["self_ms"] == 0 + 0


def test_busy_share_is_clipped_to_the_window():
    out = bs.span_numbers(_two_dispatches(), (15 * MS, 35 * MS))
    assert out["worker_busy_share_pct"] == pytest.approx(10 / 20 * 100)


def test_hlo_scopes_from_compiled_text():
    text = '''
  %while.12 = (s64[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(fn)/jit(_exec_sum)/approx/jit(searchsorted)/while"}
  ROOT %while.14 = (s64[]) while(%u), metadata={op_name="jit(fn)/jit(_exec_sum)/refine/jit(searchsorted)/while"}
  %fusion.3 = f64[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(fn)/jit(_exec_sum)/select_n"}
'''
    other = text.replace("while.12", "while.99").replace(
        "refine/jit(searchsorted)", "approx/jit(searchsorted)")
    assert bs.hlo_scopes([text]) == {"while.12": "approx",
                                     "while.14": "refine",
                                     "fusion.3": "other"}
    assert bs.hlo_scopes([text, other])["while.14"] == "?"


def _planes(offset=0.0):
    """One chip's trace: two dispatches of the served executable and a
    concatenate, every device time ``offset`` seconds behind the host's."""
    ops = [Event("%while.14 = (...) while()", 1.0, 1.6),
           Event("%fusion.1", 1.1, 1.3),          # inside the while
           Event("%while.12", 1.6, 2.0),
           Event("%copy.3", 3.0, 3.5),
           Event("%while.14", 6.0, 6.5)]
    mods = [Event("jit_bench_mark(7)", 0.4, 0.5),
            Event("jit_fn(12)", 1.0, 2.0),
            Event("jit_concatenate(3)", 3.0, 3.5),
            Event("jit_fn(12)", 6.0, 6.5),
            Event("jit_bench_mark(7)", 9.5, 9.6)]
    s = "polyfit.serve."
    worker = [Event(s + "batch", 0.6, 2.5), Event(s + "dispatch", 0.6, 2.5),
              Event(s + "prepare", 0.6, 0.9),
              Event(s + "execute", 0.9, 0.95),
              Event(s + "device_wait", 0.95, 2.1),
              Event(s + "scatter", 2.1, 2.5),
              Event(s + "batch", 4.0, 7.0), Event(s + "dispatch", 4.0, 7.0),
              Event(s + "prepare", 4.0, 5.0),
              Event(s + "execute", 5.0, 5.5),
              Event(s + "device_wait", 5.5, 6.6),
              Event(s + "scatter", 6.6, 7.0)]
    main = [Event("bench.traced", 0.5, 9.5), Event("bench.submit", 3.9, 4.0)]
    # the host launches each program 0.05 s before it starts on the
    # device and sees it done 0.02 s after it ends
    runtime = [e for m in mods for e in (
        Event(bs.LAUNCH, m.start - 0.05, m.start - 0.04),
        Event(bs.DONE, m.end + 0.01, m.end + 0.02))]

    def dev(evs):
        return [Event(e.name, e.start - offset, e.end - offset) for e in evs]
    return [Plane("/host:CPU", {"python": main, "python#2": worker,
                                "#3": runtime}),
            Plane("/device:TPU:0", {"XLA Ops": dev(ops),
                                    "XLA Modules": dev(mods)})]


SCOPE_OF = {"while.14": "refine", "fusion.1": "refine", "while.12": "approx",
            "copy.3": "other"}


def test_scope_seconds_count_each_device_instant_once():
    out = bs.scope_seconds(_planes(), SCOPE_OF)
    assert out["refine"] == pytest.approx(0.6 + 0.5)
    assert out["approx"] == pytest.approx(0.4)
    assert out["executor"] == pytest.approx(1.5)     # not the concatenate
    assert "other" not in out
    out = bs.scope_seconds(_planes(), {"while.14": "refine"})
    assert out["refine"] == pytest.approx(1.1)
    assert out["?"] == pytest.approx(0.4 + 0.2)


@pytest.mark.parametrize("offset", [0.0, 1.7e-3, -0.25, 1.2])
def test_clock_offset_is_bounded_by_launch_and_done(offset):
    clock = bs.clock_offset(_planes(offset))
    assert clock["low"] == pytest.approx(offset - 0.05)
    assert clock["high"] == pytest.approx(offset + 0.01)
    assert clock["offset"] == pytest.approx(offset - 0.02)
    # unmoved, the second program seems to start before its launch
    if offset > 0.5:
        assert bs.clock_check(_planes(offset))["matched"] < 2
    shifted = bs.on_host_clock(_planes(offset), clock["offset"])
    assert bs.clock_check(shifted) == {"matched": 2, "dispatches": 2}


def test_idle_by_span_names_the_innermost_host_span():
    out = bs.idle_by_span(_planes())
    s = "polyfit.serve."
    # window 0.5 .. 9.5; busy 1.0-2.0, 3.0-3.5, 6.0-6.5
    assert out[s + "prepare"] == pytest.approx(0.3 + 1.0)
    assert out[s + "execute"] == pytest.approx(0.05 + 0.5)
    assert out[s + "device_wait"] == pytest.approx(0.05 + 0.1 + 0.5 + 0.1)
    assert out[s + "scatter"] == pytest.approx(0.4 + 0.4)
    assert s + "batch" not in out and s + "dispatch" not in out
    assert out["none"] == pytest.approx(0.1 + (3.0 - 2.5) + (4.0 - 3.5)
                                        + (9.5 - 7.0))
    assert sum(out.values()) == pytest.approx(9.0 - 2.0)


def test_clock_check_pairs_each_dispatch_with_its_module():
    planes = _planes()
    assert bs.clock_check(planes) == {"matched": 2, "dispatches": 2}
    # the second module starting after its dispatch waited: not matched
    mods = planes[1].lines["XLA Modules"]
    mods[3] = Event("jit_fn(12)", 8.0, 8.5)
    assert bs.clock_check(planes) == {"matched": 1, "dispatches": 2}


def test_tool_runs_windows_with_spans_on_and_off(tmp_path):
    import jax
    from bench import harness
    root = copy_benchmark(tmp_path)
    cell = harness.load_cell(root, "tweet-count.paper-online")
    cell.traffic["rate_rps"] = 30.0
    out = bs.run(cell, 2**31 + 5, 1.0, ["on", "off"], jax.devices(),
                 tmp_path)
    on, off = out
    assert (on["mode"], off["mode"]) == ("on", "off")
    assert on["seed"] + 1 == off["seed"]
    assert "spans" not in off and off["read_p50_ms"] > 0
    sp = on["spans"]
    assert sp["dropped"] == 0 and sp["dispatches"] >= 1
    assert sp["by_name"]["polyfit.serve.queued"]["count"] == 30
    assert {"polyfit.serve.batch", "polyfit.serve.dispatch",
            "polyfit.serve.prepare", "polyfit.aot.lookup",
            "polyfit.serve.execute", "polyfit.serve.device_wait",
            "polyfit.serve.scatter"} <= set(sp["by_name"])
    assert 0 < sp["worker_busy_share_pct"] <= 100
    lines = tmp_path / "spans-tweet-count.paper-online.jsonl"
    assert len(lines.read_text().splitlines()) == 2
