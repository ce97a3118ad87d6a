"""The program's own spans (``repro.spans``) reduced to per-layer numbers,
and a tool that runs a cell with them on.

Two sources:

* ``repro.spans.snapshot()`` of a window, on the host clock
  (``perf_counter_ns``): ``span_numbers`` gives, per span name, count,
  mean, median, self time and compiles, and the numbers the admission and
  executor metrics want (queue wait, prepare and scatter time per
  dispatch, compiles inside dispatches, the executable call, the worker's
  busy share).
* A profiler trace of the same window (planes as ``bench/trace.py`` reads
  them), in which every span is a ``TraceAnnotation`` on the host plane:
  ``scope_seconds`` gives executor device time by ``jax.named_scope``
  (``approx``, ``refine``), read from the executables' HLO text, since a
  TPU's op events carry no ``op_name``; ``idle_by_span`` the device's idle
  time by the innermost ``polyfit.*`` span open on the host; and
  ``clock_check`` how many dispatches have their device program start
  inside their ``execute`` .. ``device_wait`` interval.

Device instants are counted once: a union of op intervals, because a
``while`` op contains its body's ops.

The host and device planes of a TPU trace are not on one clock: device
programs show up about 1.5 ms before the host launched them.
``clock_offset`` bounds the difference from the trace itself (a program
starts after its launch and ends before the host sees it done) and
``on_host_clock`` moves the device planes by it; ``idle_by_span`` and
``clock_check`` want planes so moved.

The tool builds a cell once through ``bench/harness.py`` and drives its
traffic for one or more windows, each with its own seed::

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> \\
        --modes traced,off,on,off,on,off,on [--out DIR]

``off`` runs with the spans off (as the benchmark's runs do), ``on`` with
them on and no profiler, ``traced`` with them on and a profiler trace of
the middle of the window (``harness.TRACE_SECONDS``).  One JSON line per
window goes to standard output; with ``--out DIR`` also to
``DIR/spans-<name>.jsonl``, and each trace, kept small, to
``DIR/trace-<name>-<seed>.json.gz``.  It refuses to run without the TPUs
the cell asks for.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys
import time
import traceback
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402
from bench.trace import Event, Plane  # noqa: E402

__all__ = ["span_numbers", "hlo_scopes", "scope_seconds", "idle_by_span",
           "clock_offset", "on_host_clock", "clock_check", "trace_numbers",
           "run"]

PREFIX = "polyfit."
QUEUED = "polyfit.serve.queued"
BATCH = "polyfit.serve.batch"
DISPATCH = "polyfit.serve.dispatch"
PREPARE = "polyfit.serve.prepare"
EXECUTE = "polyfit.serve.execute"
DEVICE_WAIT = "polyfit.serve.device_wait"
SCATTER = "polyfit.serve.scatter"
SCOPES = ("approx", "refine")
NONE = "none"
# the host's record of a device program: launched, and seen done
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
DONE = "tpu::System::Execute=>Done"


# ---------------------------------------------------------------------------
# the snapshot
# ---------------------------------------------------------------------------

def span_numbers(snap: dict, window_ns: tuple) -> dict:
    """The per-layer numbers of one window's snapshot.

    ``window_ns`` is the window's (start, end) on ``perf_counter_ns``'s
    clock; only the worker's busy share is clipped to it, every other
    number reads every span of the snapshot."""
    names = snap["name"]
    dur = snap["t1"] - snap["t0"]
    by = snap["by_name"]

    def total(n, key="total_ns"):
        return by.get(n, {}).get(key, 0)

    dispatches = int((names == DISPATCH).sum())
    out = {"dispatches": dispatches, "dropped": snap["dropped"],
           "none_compiles": snap["none_compiles"],
           "by_name": {n: {"count": v["count"],
                           "mean_ms": v["total_ns"] / v["count"] / 1e6,
                           "p50_ms": float(np.median(dur[names == n])) / 1e6,
                           "self_ms": v["self_ns"] / 1e6,
                           "compiles": v["compiles"]}
                       for n, v in sorted(by.items())}}
    q = dur[names == QUEUED]
    out["queue_wait_p99_ms"] = (float(np.percentile(q, 99)) / 1e6
                                if len(q) else None)
    if dispatches:
        out["prepare_ms_per_dispatch"] = total(PREPARE, "self_ns") \
            / dispatches / 1e6
        out["scatter_ms_per_dispatch"] = total(SCATTER) / dispatches / 1e6
        out["call_ms_per_dispatch"] = (total(EXECUTE) + total(DEVICE_WAIT)) \
            / dispatches / 1e6
    out["dispatch_compiles"] = _compiles_under(snap, DISPATCH)
    w0, w1 = window_ns
    m = names == BATCH
    busy = sum(b - a for a, b in trace._union(
        zip(np.clip(snap["t0"][m], w0, w1), np.clip(snap["t1"][m], w0, w1))))
    out["worker_busy_share_pct"] = busy / (w1 - w0) * 100.0
    out["request_path_ms"] = _request_path_ms(snap)
    return out


def _compiles_under(snap: dict, name: str) -> int:
    """Compiles counted against spans that are ``name`` or lie under one."""
    parent = dict(zip(snap["seq"].tolist(), snap["parent"].tolist()))
    label = dict(zip(snap["seq"].tolist(), snap["name"].tolist()))
    n = 0
    for seq, c in zip(snap["seq"].tolist(), snap["compiles"].tolist()):
        s = seq
        while c and s >= 0:
            if label.get(s) == name:
                n += c
                break
            s = parent.get(s, -1)
    return n


def _request_path_ms(snap: dict) -> Optional[float]:
    """Median over requests of their ``queued`` span plus the ``dispatch``
    span that served them: submit to answered, as the program sees it."""
    names, dur = snap["name"], snap["t1"] - snap["t0"]
    d = names == DISPATCH
    disp = dict(zip(snap["dispatch"][d].tolist(), dur[d].tolist()))
    q = names == QUEUED
    path = [w + disp[k] for k, w in zip(snap["dispatch"][q].tolist(),
                                        dur[q].tolist()) if k in disp]
    return float(np.median(path)) / 1e6 if path else None


# ---------------------------------------------------------------------------
# the profiler trace
# ---------------------------------------------------------------------------

_HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """``approx`` or ``refine`` when ``op_name`` lies under that named
    scope, else ``other``."""
    parts = op_name.split("/")
    for s in SCOPES:
        if s in parts:
            return s
    return "other"


def hlo_scopes(texts) -> Dict[str, str]:
    """HLO instruction name -> scope, from ``compiled.as_text()`` of each
    executable; a name two executables disagree on maps to ``?``."""
    out: Dict[str, str] = {}
    for text in texts:
        for line in text.splitlines():
            m = _HLO_LINE.match(line)
            if m:
                name, scope = m.group(1), scope_of(m.group(2))
                out[name] = scope if out.get(name, scope) == scope else "?"
    return out


def _window(devices: List[Plane]) -> tuple:
    marks = [e for p in devices for e in p.lines.get("XLA Modules", [])
             if trace._module_name(e.name) == trace.WINDOW]
    if len(marks) < 2:
        raise RuntimeError(f"trace has {len(marks)} {trace.WINDOW!r} marks, "
                           "want one at each end")
    return min(e.end for e in marks), max(e.start for e in marks)


def _tpu_planes(planes: List[Plane]) -> List[Plane]:
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and "XLA Ops" in p.lines]
    if not devices:
        raise RuntimeError("trace has no TPU plane with an 'XLA Ops' line")
    return devices


def _executor_ops(p: Plane, w0: float, w1: float):
    """(op event, clipped interval) of the served executables' ops."""
    mods = sorted((e for e in p.lines.get("XLA Modules", [])
                   if trace.EXECUTOR_MODULE.search(
                       trace._module_name(e.name))),
                  key=lambda e: e.start)
    starts = [e.start for e in mods]
    for e in p.lines["XLA Ops"]:
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or e.start >= mods[i].end:
            continue
        c = trace._clip(e, w0, w1)
        if c is not None:
            yield e, c


def scope_seconds(planes: List[Plane],
                  op_scopes: Dict[str, str]) -> Dict[str, float]:
    """Executor device seconds in the traced window by scope (``approx``,
    ``refine``, ``other``; ``?`` for an op ``op_scopes`` does not name) and
    in all (``executor``), averaged over the chips.  ``op_scopes`` maps HLO
    names to scopes (``hlo_scopes``): a TPU's op events carry no
    ``op_name``."""
    devices = _tpu_planes(planes)
    w0, w1 = _window(devices)
    out: Dict[str, float] = defaultdict(float)
    for p in devices:
        iv: Dict[str, list] = defaultdict(list)
        for e, c in _executor_ops(p, w0, w1):
            iv[op_scopes.get(e.name.split(" = ")[0].lstrip("%"), "?")] \
                .append(c)
            iv["executor"].append(c)
        for k, v in iv.items():
            out[k] += sum(b - a for a, b in trace._union(v))
    return {k: v / len(devices) for k, v in out.items()}


def _innermost(events: List[Event]) -> List[tuple]:
    """Cut time into pieces labelled by the innermost of ``events`` (spans
    that nest) open there: [(start, end, name)]."""
    edges = sorted({t for e in events for t in (e.start, e.end)})
    if not edges:
        return []
    # outer before inner: by start, then the longer first
    order = sorted(range(len(events)), key=lambda i: (
        events[i].start, events[i].start - events[i].end, i))
    out, open_, k = [], [], 0
    for a, b in zip(edges[:-1], edges[1:]):
        while k < len(order) and events[order[k]].start <= a:
            open_.append(k)
            k += 1
        open_ = [j for j in open_ if events[order[j]].end > a]
        if open_:
            # the last to open is the innermost
            out.append((a, b, events[order[max(open_)]].name))
    return out


def idle_by_span(planes: List[Plane]) -> Dict[str, float]:
    """Device idle seconds in the traced window, by the innermost
    ``polyfit.*`` span open on a host thread (``none`` where none is),
    averaged over the chips."""
    devices = _tpu_planes(planes)
    w0, w1 = _window(devices)
    pieces = _innermost([e for p in planes if p.name.startswith("/host:")
                         for evs in p.lines.values() for e in evs
                         if e.name.startswith(PREFIX)])
    out: Dict[str, float] = defaultdict(float)
    for p in devices:
        busy = trace._union(c for c in (trace._clip(e, w0, w1)
                                        for e in p.lines["XLA Ops"])
                            if c is not None)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for a, b in gaps:
            covered = 0.0
            for s, e, name in _overlapping(pieces, a, b):
                ov = min(e, b) - max(s, a)
                out[name] += ov
                covered += ov
            out[NONE] += (b - a) - covered
    return {k: v / len(devices) for k, v in out.items()}


def _overlapping(pieces: List[tuple], a: float, b: float):
    """The pieces (disjoint, in order) that overlap [a, b)."""
    i = max(bisect.bisect_left(pieces, (a,)) - 1, 0)
    while i < len(pieces) and pieces[i][0] < b:
        if pieces[i][1] > a:
            yield pieces[i]
        i += 1


def _paired(a: List[float], b: List[float]) -> np.ndarray:
    """``b[i + k] - a[i]`` for the shift ``k`` (|k| <= 3) whose differences
    spread least: the pairing of two sequences of the same dispatches
    that a trace's edges may have cut unevenly."""
    a, b = np.asarray(a), np.asarray(b)
    best = None
    for k in range(-3, 4):
        i0, i1 = max(0, -k), min(len(a), len(b) - k)
        if i1 - i0 < max(2, min(len(a), len(b)) // 2):
            continue
        d = b[i0 + k:i1 + k] - a[i0:i1]
        q1, q3 = np.percentile(d, [25, 75])
        if best is None or q3 - q1 < best[0]:
            best = (q3 - q1, d)
    if best is None:
        raise RuntimeError("too few device programs to pair with the host")
    return best[1]


def clock_offset(planes: List[Plane]) -> Dict[str, float]:
    """Host clock minus device clock of a trace, in seconds, bounded by
    causality: each program on the device starts after the host launched
    it (``LAUNCH``) and ends before the host saw it done (``DONE``).
    ``low`` and ``high`` are those bounds, ``offset`` their middle."""
    dev = _tpu_planes(planes)[0]
    mods = sorted(dev.lines.get("XLA Modules", []), key=lambda e: e.start)
    host = [e for p in planes if p.name.startswith("/host:")
            for evs in p.lines.values() for e in evs]
    launch = sorted(e.start for e in host if e.name == LAUNCH)
    done = sorted(e.start for e in host if e.name == DONE)
    low = float(_paired([m.start for m in mods], launch).max())
    high = float(_paired([m.end for m in mods], done).min())
    return {"offset": (low + high) / 2, "low": low, "high": high,
            "programs": len(mods)}


def on_host_clock(planes: List[Plane], offset: float) -> List[Plane]:
    """The planes with every device event moved by ``offset`` seconds
    (``clock_offset``) onto the host's clock."""
    return [p if p.name.startswith("/host:") else
            Plane(p.name, {ln: [Event(e.name, e.start + offset,
                                      e.end + offset) for e in evs]
                           for ln, evs in p.lines.items()})
            for p in planes]


def clock_check(planes: List[Plane]) -> Dict[str, int]:
    """Dispatches in the traced window whose ``execute`` .. ``device_wait``
    interval on the host plane holds the start of an executor module on
    the device plane: ``matched`` of ``dispatches``."""
    devices = _tpu_planes(planes)
    w0, w1 = _window(devices)
    starts = sorted(e.start for p in devices
                    for e in p.lines.get("XLA Modules", [])
                    if trace.EXECUTOR_MODULE.search(
                        trace._module_name(e.name)))
    matched = total = 0
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for evs in p.lines.values():
            ex = sorted((e for e in evs if e.name == EXECUTE),
                        key=lambda e: e.start)
            wait = sorted((e for e in evs if e.name == DEVICE_WAIT),
                          key=lambda e: e.start)
            wstarts = [e.start for e in wait]
            for e in ex:
                if not w0 <= e.start < w1:
                    continue
                j = bisect.bisect_left(wstarts, e.end)
                end = wait[j].end if j < len(wait) else e.end
                total += 1
                k = bisect.bisect_left(starts, e.start)
                matched += k < len(starts) and starts[k] <= end
    return {"matched": int(matched), "dispatches": int(total)}


# ---------------------------------------------------------------------------
# the tool
# ---------------------------------------------------------------------------

def run(cell, seed: int, seconds: float, modes: List[str], devices,
        out_dir: Optional[Path] = None) -> List[dict]:
    """Build ``cell`` once, then drive one window per mode (seed, seed + 1,
    ...); returns one result object per window.  With ``out_dir``, each
    goes as a line to ``spans-<cell>.jsonl`` there, and each trace, kept
    small (``trace.write_compact``), to ``trace-<cell>-<seed>.json.gz``."""
    from bench import harness
    from repro import spans
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    gen = harness.load_module(cell.bench_dir / "traffic"
                              / f"{cell.traffic['generator']}.py")
    readers = {m: harness.load_module(cell.bench_dir / "metrics" / f"{m}.py")
               for m in ("read_p50_ms", "queries_per_s")}
    t_start = time.perf_counter()
    data, keys, session, engine, bound = harness.build(cell, seed)
    table = cell.config["table"]["name"]
    ctx = harness.Context(cell=cell, table=table,
                          agg=cell.config["table"]["agg"], data=data,
                          keys=keys, session=session, engine=engine,
                          bound=bound)
    engine.warmup(max_bucket=cell.traffic["max_bucket"])
    gen.warm(ctx, gen.prepare(ctx, cell.traffic, seed, seconds))
    setup_s = time.perf_counter() - t_start
    op_scopes = hlo_scopes(e.compiled.as_text()
                           for e in engine._cache.values()
                           if e.compiled is not None)
    results = []
    for i, mode in enumerate(modes):
        schedule = gen.prepare(ctx, cell.traffic, seed + i, seconds)
        tracer = harness.Tracer(engine, seconds) if mode == "traced" \
            else None
        if mode != "off":
            spans.enable()
        t_window = time.perf_counter() + 0.05
        if tracer is not None:
            tracer.start(t_window)
        try:
            rec = gen.drive(ctx, schedule, seconds, t_window)
        finally:
            if tracer is not None:
                tracer.join()
        snap = spans.snapshot() if mode != "off" else None
        spans.disable()
        out = {"workload": cell.name, "seed": seed + i, "mode": mode,
               "setup_s": setup_s, "read_failed": rec.read_failed,
               "late_p50_ms": float(np.median(rec.lateness)) * 1e3}
        for m, r in readers.items():
            out[m] = r.read(SimpleNamespace(record=rec))
        if snap is not None:
            w0 = int(t_window * 1e9)
            out["spans"] = span_numbers(snap, (w0, w0 + int(seconds * 1e9)))
        if tracer is not None:
            keep = (out_dir / f"trace-{cell.name}-{seed + i}.json.gz"
                    if out_dir else None)
            try:
                out["trace"] = trace_numbers(tracer.dir.name, op_scopes,
                                             keep)
                out["trace"]["dispatches"] = tracer.stats["dispatches"]
            except Exception as e:    # the other windows still count
                traceback.print_exc()
                out["trace_error"] = repr(e)
            finally:
                tracer.dir.cleanup()
        results.append(out)
        line = json.dumps(out)
        print(line, flush=True)
        if out_dir:
            with open(out_dir / f"spans-{cell.name}.jsonl", "a") as f:
                f.write(line + "\n")
    engine.shutdown()
    return results


def trace_numbers(trace_dir: str, op_scopes: Dict[str, str],
                  keep: Optional[Path] = None) -> dict:
    """Everything this module reads from the one trace under
    ``trace_dir``; ``keep`` writes the trace there, kept small."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    planes = trace.read_planes(paths[0])
    if keep is not None:
        trace.write_compact(planes, str(keep))
    summary = trace.reduce(planes)
    clock = clock_offset(planes)
    shifted = on_host_clock(planes, clock["offset"])
    return {"window_s": summary.window_s, "busy_s": summary.busy_s,
            "executor_s": summary.executor_s, "top_ops": summary.top_ops,
            "scopes": scope_seconds(planes, op_scopes),
            "clock": clock,
            "clock_check": clock_check(shifted),
            "clock_check_unshifted": clock_check(planes),
            "idle_by_span": idle_by_span(shifted),
            "while_scopes": {k: v for k, v in op_scopes.items()
                             if k.startswith("while")}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--modes", default="traced")
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_tpu(cell.chips)
    if args.out is not None:
        args.out.mkdir(parents=True, exist_ok=True)
    run(cell, args.seed, args.seconds, args.modes.split(","), devices,
        args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
