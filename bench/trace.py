"""Reduce a profiler trace of a benchmark window to device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX (``jax.profiler.ProfileData``).  From the TPU planes it takes the
``XLA Ops`` line (what ran on the device, and when) and the ``XLA
Modules`` line (which compiled program it belonged to); from the host
plane, JAX's host events and the harness's own ``TraceAnnotation``s.

* The traced window runs from the first to the last ``jit_bench_mark``
  module on the device: the harness runs that no-op program at each end
  of the traced part, so the window is on the device's own clock.
* Busy time is the union of the op intervals inside it, averaged over
  the chips.
* Executor time is the device time of the served executables' modules:
  the programs the serving engine AOT-compiles from
  ``repro.engine.fused_executor`` (a module named ``jit_fn``, whose ops
  carry the ``_exec_*`` executors' names).
* Idle gaps are the holes between busy intervals, each named by the
  innermost host event covering at least half of it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

__all__ = ["Event", "Summary", "reduce", "reduce_dir", "reduce_file",
           "read_planes", "read_compact", "write_compact",
           "EXECUTOR_MODULE"]

WINDOW = "jit_bench_mark"
# the serving executables: fused_executor's closure is AOT-lowered as `fn`
EXECUTOR_MODULE = re.compile(r"^jit_fn(\(|$)")
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float       # seconds
    end: float


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    chips: int
    module_s: Dict[str, float]     # device seconds per module, per chip
    executor_s: float              # device seconds of the served executors
    top_ops: List[list]            # [[op, seconds]]
    top_gaps: List[list]           # [[host activity, seconds]]


def read_planes(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            # host threads often share a name ('python3'): keep each line
            key, k = line.name, 1
            while key in lines:
                k += 1
                key = f"{line.name}#{k}"
            lines[key] = [Event(e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events]
        out.append(Plane(plane.name, lines))
    return out


def write_compact(planes: List[Plane], path: str) -> None:
    """Keep a trace small (gzipped JSON, each event ``[name, start,
    end]``): op names cut to their HLO name, which is all ``reduce``
    reads of them, and JAX's Python-tracer events (``$file:line``)
    dropped.  ``bench/tests/data`` keeps a chip trace this way."""
    out = []
    for p in planes:
        lines = {ln: [[e.name.split(" = ")[0] if ln == "XLA Ops"
                       else e.name, e.start, e.end]
                      for e in evs if not e.name.startswith("$")]
                 for ln, evs in p.lines.items()}
        out.append({"name": p.name,
                    "lines": {k: v for k, v in lines.items() if v}})
    with gzip.open(path, "wt") as f:
        json.dump({"planes": out}, f)


def read_compact(path: str) -> List[Plane]:
    with gzip.open(path, "rt") as f:
        planes = json.load(f)["planes"]
    return [Plane(p["name"], {ln: [Event(*e) for e in evs]
                              for ln, evs in p["lines"].items()})
            for p in planes]


def reduce_dir(d: str) -> Summary:
    paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {d}, found {paths}")
    return reduce_file(paths[0])


def reduce_file(path: str) -> Summary:
    return reduce(read_planes(path))


def _union(iv: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev: Event, w0: float, w1: float):
    a, b = max(ev.start, w0), min(ev.end, w1)
    return (a, b) if b > a else None


def reduce(planes: List[Plane]) -> Summary:
    host = [p for p in planes if p.name.startswith("/host:")]
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and "XLA Ops" in p.lines]
    if not devices:
        raise RuntimeError("trace has no TPU plane with an 'XLA Ops' line")
    host_events = [e for p in host for evs in p.lines.values() for e in evs]
    marks = [e for p in devices for e in p.lines.get("XLA Modules", [])
             if _module_name(e.name) == WINDOW]
    if len(marks) < 2:
        raise RuntimeError(f"trace has {len(marks)} {WINDOW!r} marks, "
                           "want one at each end")
    w0 = min(e.end for e in marks)
    w1 = max(e.start for e in marks)

    busy = 0.0
    modules: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for p in devices:
        iv = [c for c in (_clip(e, w0, w1) for e in p.lines["XLA Ops"])
              if c is not None]
        u = _union(iv)
        busy += sum(b - a for a, b in u)
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        mods = sorted(p.lines.get("XLA Modules", []), key=lambda e: e.start)
        starts = [e.start for e in mods]
        for e in p.lines["XLA Ops"]:
            c = _clip(e, w0, w1)
            if c is not None:
                ops[_op_name(e, mods, starts)] += c[1] - c[0]
        for e in mods:
            c = _clip(e, w0, w1)
            if c is not None:
                modules[_module_name(e.name)] += c[1] - c[0]
    n = len(devices)
    candidates = [e for e in host_events
                  if not e.name.startswith("bench.traced")
                  and e.end - e.start < w1 - w0]
    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [[_activity(g, candidates), g[1] - g[0]]
                for g in gaps[:TOP]]
    top_ops = sorted(([k, v / n] for k, v in ops.items()),
                     key=lambda kv: -kv[1])[:TOP]
    per_chip = {k: v / n for k, v in modules.items()}
    return Summary(window_s=w1 - w0, busy_s=busy / n, chips=n,
                   module_s=per_chip,
                   executor_s=sum(v for k, v in per_chip.items()
                                  if EXECUTOR_MODULE.search(k)),
                   top_ops=top_ops, top_gaps=top_gaps)


def _module_name(name: str) -> str:
    """``jit_fn(123)`` -> ``jit_fn``: the program id varies by run."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(op: Event, mods: List[Event], starts: List[float]) -> str:
    """``<module>:<op>``, the op's HLO name without its text (``%while.14 =
    (...) while(...)`` -> ``%while.14``) and the module it ran in."""
    i = bisect.bisect_right(starts, op.start) - 1
    mod = (_module_name(mods[i].name)
           if i >= 0 and op.start < mods[i].end else "?")
    return f"{mod}:{op.name.split(' = ')[0][:64]}"


def _activity(gap: Tuple[float, float], events: List[Event]) -> str:
    """The innermost host event covering at least half of the gap, else
    the one overlapping it most."""
    span = gap[1] - gap[0]
    best, key = "host: no traced activity", (False, 0.0)
    for e in events:
        ov = min(e.end, gap[1]) - max(e.start, gap[0])
        if ov <= 0:
            continue
        half = ov >= span / 2
        k = (half, -(e.end - e.start) if half else ov)
        if k > key:
            best, key = e.name, k
    return best
