"""Load one cell of ``BENCHMARK.json`` by name and run it once.

A cell names a configuration (``<paths>/configs/<config>.json``: the data,
the table and the limits of its comparison) and a traffic mix
(``<paths>/traffic/<traffic>.json``: parameters read by the generator
module ``<paths>/traffic/<generator>.py``).  Every metric is read by
``<paths>/metrics/<name>.py``.  Nothing here names a cell, a mix or a
metric: a new one is new files and entries.

One run: refuse without the chips the cell asks for; make the data and
the traffic from the seed; fit the cell's one table (``PolyFit.fit``,
``backend="xla"``); AOT-warm its bucket ladder and the traffic's own
shapes; drive the traffic for the window; then, with the program shut
down, compare the sampled answers with the plain reference
(``reference.py``) and print the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import reference
from bench.data import GENERATORS

__all__ = ["Cell", "Context", "Record", "Run", "load_cell", "run_cell",
           "require_tpu", "result_line"]

TRACE_SECONDS = 5.0       # length of the traced part of a --trace 1 window
DRAIN_SECONDS = 60.0      # how long past the close answers are waited for


# ---------------------------------------------------------------------------
# finding a cell's files
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    bench_dir: Path
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    wl = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == wl["config"])
    bench_dir = root / spec["paths"][0]
    traffic = json.loads(
        (bench_dir / "traffic" / f"{wl['traffic']}.json").read_text())
    return Cell(name=name, chips=int(wl["chips"]), bench_dir=bench_dir,
                config_name=cfg["name"],
                config=json.loads((root / cfg["file"]).read_text()),
                traffic_name=wl["traffic"], traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric names carry dots)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def require_tpu(count: int):
    """The devices to run on; exits non-zero unless JAX sees ``count``
    TPUs.  Nothing is ever measured elsewhere."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU — JAX's first device is "
                         f"{devs[0].platform!r}; nothing is run elsewhere")
    if len(devs) < count:
        raise SystemExit(f"bench: the cell needs {count} TPU devices, JAX "
                         f"sees {len(devs)}")
    return devs


def device_peaks(bench_dir: Path, kind: str) -> dict:
    peaks = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in peaks["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"peaks.json ({sorted(peaks['devices'])})")
    return peaks["devices"][kind]


# ---------------------------------------------------------------------------
# what a generator works with, and what it hands back
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """The system under test and the cell's data, for a generator."""
    cell: Cell
    table: str
    agg: str
    data: object            # keys (COUNT) or (keys, measures)
    keys: np.ndarray        # the table's keys, sorted (query endpoints)
    session: object
    engine: object
    bound: float


@dataclasses.dataclass
class Record:
    """What one window did, as the generator saw it.  Times are seconds
    from the window's start on ``time.perf_counter``'s clock."""
    window_s: float                       # offered schedule length
    span_s: float = 0.0                   # start to last completion
    sizes: np.ndarray = None              # queries per read request
    scheduled: np.ndarray = None          # per read: when it was due
    submitted: np.ndarray = None          # per read: when it was sent
    resolved: np.ndarray = None           # per read: future resolved
    ok: np.ndarray = None                 # per read: answered
    read_failed: int = 0
    batches: List[reference.InsertBatch] = dataclasses.field(
        default_factory=list)             # every insert, warm-up included
    insert_scheduled: np.ndarray = None   # window inserts: when due
    insert_acked: np.ndarray = None       # window inserts: when visible
    insert_failed: int = 0
    # the sampled reads' queries and answers (for the check)
    lq: np.ndarray = None
    uq: np.ndarray = None
    q_submitted: np.ndarray = None
    q_resolved: np.ndarray = None
    value: np.ndarray = None
    refined: np.ndarray = None

    @property
    def read_latencies(self) -> np.ndarray:
        return self.resolved - self.scheduled

    @property
    def insert_latencies(self) -> np.ndarray:
        return self.insert_acked - self.insert_scheduled

    @property
    def lateness(self) -> np.ndarray:
        return self.submitted - self.scheduled


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    record: Record
    setup_s: float
    build_s: float
    warmup_s: float
    stats: Dict[str, int]            # EngineStats over the window
    compiles: int                    # XLA compiles inside the window
    plan_swaps: int                  # plan swaps inside the window
    plan: Dict[str, int]             # h, n, deg of the plan at the start
    peaks: Optional[dict]
    trace: Optional[object] = None   # trace.Summary of the traced part
    trace_stats: Optional[Dict[str, int]] = None
    trace_clock: Optional[tuple] = None   # traced part, window seconds

    def executor_ms_per_dispatch(self) -> Optional[float]:
        """Device ms of the served executables per dispatch, in the
        traced part of the window."""
        if self.trace is None or self.trace.executor_s <= 0 \
                or not self.trace_stats["dispatches"]:
            return None
        return self.trace.executor_s / self.trace_stats["dispatches"] * 1e3

    def idle_share_pct(self) -> Optional[float]:
        """Share of the traced part in which no operation ran, in %."""
        if self.trace is None or self.trace.window_s <= 0:
            return None
        return (1.0 - self.trace.busy_s / self.trace.window_s) * 100.0

    def answered_in_trace(self) -> np.ndarray:
        """Mask of the read requests answered inside the traced part."""
        rec, (a, b) = self.record, self.trace_clock
        return rec.ok & (rec.resolved >= a) & (rec.resolved < b)


# ---------------------------------------------------------------------------
# counters the harness keeps around the program
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts XLA compiles (JAX's own monitoring events) in this process."""

    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.n += 1


class SwapCounter:
    """Counts plan swaps of a dynamic table (``session.on_plan_swap``)."""

    def __init__(self):
        self.n = 0

    def __call__(self, incoming) -> None:
        self.n += 1


def bench_mark(x):
    """A no-op program run on the chip at each end of the traced part:
    its ``jit_bench_mark`` module events bound the traced window on the
    device's own clock (``bench/trace.py``)."""
    return x + 1


class Tracer:
    """Records a profiler trace of ``TRACE_SECONDS`` in the middle of the
    window, from a thread of its own, and the engine's counters around it.
    JAX's Python tracer stays off: it records every Python call."""

    def __init__(self, engine, seconds: float):
        import jax
        self.engine = engine
        self.length = min(TRACE_SECONDS, seconds / 2)
        self.offset = (seconds - self.length) / 2
        self.dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        self.stats = None
        self.clock = None
        self._thread = None
        self._mark = jax.jit(bench_mark)
        self._x = jax.device_put(np.zeros(1, np.float32))
        self._mark(self._x).block_until_ready()      # compiled in set-up

    def start(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        daemon=True, name="bench-tracer")
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        sleep_until(t0 + self.offset)
        jax.profiler.start_trace(self.dir.name, profiler_options=opts)
        self._mark(self._x).block_until_ready()
        s0 = _stats(self.engine)
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.traced"):
            sleep_until(a + self.length)
        b = time.perf_counter()
        s1 = _stats(self.engine)
        self._mark(self._x).block_until_ready()
        jax.profiler.stop_trace()
        self.stats = {k: s1[k] - s0[k] for k in s1}
        self.clock = (a - t0, b - t0)

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()


def sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(d)


def _stats(engine) -> Dict[str, int]:
    return dataclasses.asdict(engine.stats)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def seeds(seed: int, stream: int) -> np.random.SeedSequence:
    """Independent streams (data, traffic, sample, ...) of one run seed."""
    return np.random.SeedSequence([int(seed), stream])


def make_data(config: dict, seed: int):
    gen, with_measures = GENERATORS[config["data"]["generator"]]
    data = gen(int(config["data"]["rows"]), seed=seeds(seed, 0))
    keys = data[0] if with_measures else data
    return data, np.sort(np.asarray(keys, np.float64))


def build(cell: Cell, seed: int):
    """Data, fitted session and serving engine of one cell."""
    from repro.api import ErrorBudget, PolyFit, TableSpec
    from repro.serve import ServingEngine
    t = cell.config["table"]
    data, keys = make_data(cell.config, seed)
    abs_ = float(t["abs"])
    if t.get("abs_scale") == "mean_abs_measure":
        abs_ *= float(np.abs(data[1]).mean())
    spec = TableSpec(t["agg"], ErrorBudget(abs=abs_, rel=t["rel"]),
                     deg=t["deg"], **t.get("spec", {}))
    tr = cell.traffic
    session = PolyFit.fit({t["name"]: data}, {t["name"]: spec},
                          backend="xla", min_bucket=tr["min_bucket"])
    engine = ServingEngine(session, max_batch=tr["max_batch"],
                           **cell.config.get("engine", {}))
    return data, keys, session, engine, spec.budget.bound(t["agg"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, peaks: Optional[dict],
             control: Optional[Callable] = None) -> dict:
    """Run the cell once; returns the result object (see ``result_line``).

    ``control``, when given, is called with the finished record and the
    check's truth before anything is freed (``bench/control.py``)."""
    import jax
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    gen = load_module(cell.bench_dir / "traffic"
                      / f"{cell.traffic['generator']}.py")

    data, keys, session, engine, bound = build(cell, seed)
    table = cell.config["table"]["name"]
    build_s = session.build_seconds()[table]
    swaps = SwapCounter()
    if cell.config["table"].get("spec", {}).get("dynamic"):
        session.on_plan_swap(table, swaps)
    ctx = Context(cell=cell, table=table,
                  agg=cell.config["table"]["agg"], data=data, keys=keys,
                  session=session, engine=engine, bound=bound)
    plan0 = session.plan(table)
    shape = {"h": int(plan0.h), "n": int(plan0.n), "deg": int(plan0.deg)}
    schedule = gen.prepare(ctx, cell.traffic, seed, seconds)

    # warm-up: every executable of the ladder, kept in the persistent
    # cache whatever its compile time; then the traffic's own shapes
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    t0 = time.perf_counter()
    try:
        n_exec = engine.warmup(max_bucket=cell.traffic["max_bucket"])
        warm_batches = gen.warm(ctx, schedule)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
    warmup_s = time.perf_counter() - t0
    tracer = Tracer(engine, seconds) if trace else None
    setup_s = time.perf_counter() - t_start
    _say(f"setup: seconds={setup_s:.3f} build={build_s:.3f} "
         f"warmup={warmup_s:.3f} executables={n_exec} plan_h={shape['h']} "
         f"rows={shape['n']} cache={cache_dir}")

    # the window
    s0, c0, w0 = _stats(engine), compiles.n, swaps.n
    t_window = time.perf_counter() + 0.05
    if tracer is not None:
        tracer.start(t_window)
    try:
        rec = gen.drive(ctx, schedule, seconds, t_window)
    finally:
        if tracer is not None:
            tracer.join()
    s1, c1, w1 = _stats(engine), compiles.n, swaps.n
    stats = {k: s1[k] - s0[k] for k in s1}
    rec.batches = warm_batches + rec.batches

    mem = devices[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    engine.shutdown()
    if cell.config["table"].get("spec", {}).get("dynamic"):
        session.flush(table)    # join the merge thread a window may leave
    del session, engine, ctx.session, ctx.engine

    # the check, with the program's state freed
    truth = reference.LiveTruth(cell.config["table"]["agg"], data,
                                rec.batches)
    lo, hi = truth.bounds(rec.lq, rec.uq, rec.q_submitted, rec.q_resolved)
    limits = cell.config["limits"]
    numbers = reference.compare(rec.value, lo, hi, rec.refined, bound,
                                limits)
    if control is not None:
        control(rec, truth, lo, hi, bound, limits)

    summary = None
    if tracer is not None:
        from bench import trace as trace_mod
        summary = trace_mod.reduce_dir(tracer.dir.name)
        tracer.dir.cleanup()
    run = Run(cell=cell, record=rec, setup_s=setup_s, build_s=build_s,
              warmup_s=warmup_s, stats=stats, compiles=c1 - c0,
              plan_swaps=w1 - w0, plan=shape, peaks=peaks, trace=summary,
              trace_stats=tracer.stats if tracer else None,
              trace_clock=tracer.clock if tracer else None)
    _diagnostics(run)
    metrics = read_metrics(cell, run, trace)
    attempted = len(rec.scheduled) + (0 if rec.insert_scheduled is None
                                      else len(rec.insert_scheduled))
    failed = rec.read_failed + rec.insert_failed
    dev = devices[0]
    out = {"correct": bool(reference.passed(numbers) and failed == 0
                           and attempted > 0),
           "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "memory_peak_bytes": peak}}
    if summary is not None:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.top_gaps}
    out["check"] = numbers
    return out


def read_metrics(cell: Cell, run: Run, trace: bool) -> dict:
    """The cell's end-to-end metrics (``--trace 0``) or per-layer ones
    (``--trace 1``); a reader that finds nothing returns None and its
    metric is left out."""
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _diagnostics(run: Run) -> None:
    rec = run.record
    late = rec.lateness
    n = len(rec.scheduled)
    sent = int(rec.sizes.sum())
    _say(f"window: seconds={rec.window_s} requests={n} queries={sent} "
         f"offered_rps={n / rec.window_s:.6g} "
         f"achieved_rps={n / max(rec.span_s, 1e-9):.6g} "
         f"span_s={rec.span_s:.6g} read_failed={rec.read_failed}")
    _say(f"generator: late_p99_ms={np.percentile(late, 99) * 1e3:.6g} "
         f"late_max_ms={late.max() * 1e3:.6g}")
    lat = rec.read_latencies[rec.ok] * 1e3
    if len(lat):
        q = np.percentile(lat, [50, 90, 99])
        _say(f"latency: p50_ms={q[0]:.6g} p90_ms={q[1]:.6g} "
             f"p99_ms={q[2]:.6g} max_ms={lat.max():.6g}")
    if rec.insert_scheduled is not None and len(rec.insert_scheduled):
        lat = rec.insert_latencies
        _say(f"writer: batches={len(rec.insert_scheduled)} "
             f"insert_failed={rec.insert_failed} "
             f"visible_p50_ms={np.percentile(lat, 50) * 1e3:.6g} "
             f"visible_max_ms={lat.max() * 1e3:.6g} "
             f"plan_swaps={run.plan_swaps}")
    s = run.stats
    _say(f"engine: dispatches={s['dispatches']} answered={s['answered']} "
         f"coalesced={s['coalesced']} aot_compiles={s['aot_compiles']} "
         f"aot_precompiles={s['aot_precompiles']} "
         f"aot_promotions={s['aot_promotions']} "
         f"staged_records={s['staged_records']} "
         f"fused_applies={s['fused_applies']} xla_compiles={run.compiles}")


def result_line(out: dict) -> None:
    """Print the check's numbers last on stderr, then the result line."""
    for name, n in out["check"].items():
        print(f"check: {name}={n['value']!r} limit={n['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def _say(msg: str) -> None:
    print(msg, flush=True)
