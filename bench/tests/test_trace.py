"""The trace reduction, on a hand-made trace and on one recorded on a
TPU v5e (``data/chip_trace.json.gz``: about 1 s of the online mix at
25 req/s on the 1M-row TWEET table, kept with ``trace.write_compact``).
That recording predates the ``jit_bench_mark`` programs, so the test
places the two marks at the ends of its ``bench.traced`` annotation, the
part a ``Tracer`` marks."""
import os

import pytest

from bench import trace
from bench.trace import Event, Plane

DATA = os.path.join(os.path.dirname(__file__), "data")


def _planes():
    ops = [Event("fusion.1", 1.0, 1.5), Event("fusion.2", 1.4, 2.0),
           Event("copy.3", 3.0, 3.5), Event("fusion.1", 9.5, 11.0)]
    mods = [Event("jit_bench_mark(7)", 0.4, 0.5),
            Event("jit_fn(12)", 1.0, 2.0), Event("jit_concatenate(3)", 3.0,
                                                 3.5),
            Event("jit_fn(12)", 9.5, 11.0),
            Event("jit_bench_mark(7)", 10.5, 10.6)]
    host = [Event("bench.traced", 0.5, 10.5), Event("bench.submit", 2.1, 2.9),
            Event("outer", 3.4, 9.6), Event("backend_compile", 4.0, 9.0)]
    return [Plane("/host:CPU", {"python": host}),
            Plane("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": mods})]


def test_reduce_hand_made_trace():
    s = trace.reduce(_planes())
    assert s.window_s == pytest.approx(10.0)
    # busy: [1, 2] + [3, 3.5] + [9.5, 10.5] clipped to the window
    assert s.busy_s == pytest.approx(2.5)
    assert s.executor_s == pytest.approx(1.0 + 1.0)
    assert s.module_s["jit_concatenate"] == pytest.approx(0.5)
    assert s.top_ops[0] == ["jit_fn:fusion.1", pytest.approx(1.5)]
    assert ["jit_concatenate:copy.3", pytest.approx(0.5)] in s.top_ops
    gaps = {name: secs for name, secs in s.top_gaps}
    # the longest hole, 3.5 -> 9.5, is mostly the compile
    assert s.top_gaps[0] == ["backend_compile", pytest.approx(6.0)]
    assert gaps["bench.submit"] == pytest.approx(1.0)


def test_reduce_refuses_a_trace_without_its_marks():
    planes = _planes()
    mods = planes[1].lines["XLA Modules"]
    planes[1].lines["XLA Modules"] = [e for e in mods
                                      if "bench_mark" not in e.name]
    with pytest.raises(RuntimeError):
        trace.reduce(planes)


def test_reduce_refuses_a_trace_without_a_tpu():
    planes = [p for p in _planes() if p.name.startswith("/host")]
    with pytest.raises(RuntimeError):
        trace.reduce(planes)


def _chip_planes():
    planes = trace.read_compact(os.path.join(DATA, "chip_trace.json.gz"))
    (mark,) = [e for p in planes if p.name.startswith("/host:")
               for evs in p.lines.values() for e in evs
               if e.name == "bench.traced"]
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    dev.lines["XLA Modules"] = dev.lines["XLA Modules"] + [
        Event("jit_bench_mark(1)", mark.start - 1e-5, mark.start),
        Event("jit_bench_mark(1)", mark.end, mark.end + 1e-5)]
    return planes, mark


def test_reduce_chip_trace():
    planes, mark = _chip_planes()
    s = trace.reduce(planes)
    assert s.chips == 1
    assert s.window_s == pytest.approx(mark.end - mark.start)
    assert 0.0 < s.executor_s <= s.busy_s < s.window_s
    assert s.module_s["jit_fn"] == pytest.approx(s.executor_s)
    assert len(s.top_ops) == trace.TOP and len(s.top_gaps) == trace.TOP
    assert any(name.startswith("jit_fn:") for name, _ in s.top_ops)
    assert all(secs > 0 for _, secs in s.top_gaps)
    # the gaps lie inside the window and leave the busy time over
    assert sum(secs for _, secs in s.top_gaps) <= s.window_s - s.busy_s + 1e-9
