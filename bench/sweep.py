"""Find the highest read rate an online cell sustains: one build, then the
cell's traffic at a ladder of open-loop rates in one process.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 50,100,200 [--out chiprun_out/sweep.json] [--trace-dir DIR]

Each step offers the mix's reads at one rate for ``--seconds``.  A step is
*sustained* when every request was sent, the last answer came within a
second of the schedule's end, and the second half's p99 latency is under
twice the first half's: the backlog did not grow.  A step that falls 2 s
behind its schedule stops there.  How late the generator ran is reported
beside.  The knee is the highest sustained rate; a cell's traffic file
freezes 0.8 of it.

``--trace-dir`` also records a 1 s profiler trace at the lowest rate, the
way a ``--trace 1`` run does, for the trace reduction's test.
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

LATE_STOP = 2.0      # seconds behind schedule that end a step
DRAIN_OK = 1.0


def step(ctx, gen, rate: float, seed: int, seconds: float,
         compiles) -> dict:
    """One rate: the open-loop schedule of the cell's mix at ``rate``."""
    from bench.harness import sleep_until
    params = dict(ctx.cell.traffic, rate_rps=rate, check_requests=1)
    sch = gen.prepare(ctx, params, seed, seconds)
    n = len(sch.arrivals)
    resolved = np.full(n, np.nan)
    sent = np.zeros(n, bool)
    late = np.zeros(n)
    t0 = time.perf_counter() + 0.05
    stats0, c0 = ctx.engine.stats, compiles.n

    def cb(i):
        def f(_):
            resolved[i] = time.perf_counter() - t0
        return f

    sleep_until(t0)
    for i in range(n):
        sleep_until(t0 + sch.arrivals[i])
        late[i] = time.perf_counter() - t0 - sch.arrivals[i]
        if late[i] > LATE_STOP:
            break
        ctx.engine.submit(sch.specs[i]).add_done_callback(cb(i))
        sent[i] = True
    end = time.perf_counter() - t0
    deadline = time.perf_counter() + 60.0
    while np.isnan(resolved[sent]).any() and time.perf_counter() < deadline:
        time.sleep(0.01)
    lat = resolved[sent] - sch.arrivals[sent]
    half = sch.arrivals[sent] < seconds / 2
    p99 = lambda x: float(np.percentile(x, 99) * 1e3) if len(x) else None
    st = ctx.engine.stats
    out = {
        "rate_rps": rate, "offered": int(n), "sent": int(sent.sum()),
        "achieved_rps": float(sent.sum() / max(np.nanmax(resolved), 1e-9)),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": p99(lat), "p99_first_half_ms": p99(lat[half]),
        "p99_second_half_ms": p99(lat[~half]),
        "late_max_ms": float(late[sent].max() * 1e3),
        "drain_s": float(np.nanmax(resolved) - max(end, seconds)),
        "dispatches": st.dispatches - stats0.dispatches,
        "xla_compiles": compiles.n - c0,
        "queries_per_dispatch": float(sch.sizes[sent].sum() / max(
            1, st.dispatches - stats0.dispatches)),
    }
    out["sustained"] = bool(
        out["sent"] == n and out["drain_s"] <= DRAIN_OK
        and out["p99_second_half_ms"] <= 2 * out["p99_first_half_ms"])
    return out


def record_trace(ctx, gen, rate: float, seed: int, trace_dir: str) -> None:
    """A trace of the mix at ``rate``, taken as a ``--trace 1`` run takes
    it (``harness.Tracer``), copied to ``trace_dir``; prints its summary."""
    import shutil
    import jax
    from bench import trace
    from bench.harness import Tracer, sleep_until
    seconds = 2.0
    params = dict(ctx.cell.traffic, rate_rps=rate, check_requests=1)
    sch = gen.prepare(ctx, params, seed, seconds)
    tracer = Tracer(ctx.engine, seconds)
    t0 = time.perf_counter() + 0.05
    tracer.start(t0)
    futs = []
    for i in range(len(sch.arrivals)):
        sleep_until(t0 + sch.arrivals[i])
        with jax.profiler.TraceAnnotation("bench.submit"):
            futs.append(ctx.engine.submit(sch.specs[i]))
    for f in futs:
        f.result()
    tracer.join()
    shutil.copytree(tracer.dir.name, trace_dir, dirs_exist_ok=True)
    print(f"sweep: trace {trace.reduce_dir(trace_dir)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_tpu(cell.chips)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    compiles = harness.CompileCounter()
    data, keys, session, engine, bound = harness.build(cell, args.seed)
    gen = harness.load_module(cell.bench_dir / "traffic"
                              / f"{cell.traffic['generator']}.py")
    ctx = harness.Context(cell=cell,
                          table=cell.config["table"]["name"],
                          agg=cell.config["table"]["agg"], data=data,
                          keys=keys, session=session, engine=engine,
                          bound=bound)
    engine.warmup(max_bucket=cell.traffic["max_bucket"])
    gen.warm(ctx, gen.prepare(ctx, cell.traffic, args.seed, 1.0))
    print(f"sweep: {cell.name} on {devices[0].device_kind} "
          f"set-up {time.perf_counter() - T_START:.1f}s", flush=True)
    rows = []
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            row = step(ctx, gen, rate, args.seed + k, args.seconds,
                       compiles)
            rows.append(row)
            print("sweep: " + json.dumps(row), flush=True)
        knee = max((r["rate_rps"] for r in rows if r["sustained"]),
                   default=None)
        print(f"sweep: knee_rps={knee} frozen_rate_rps="
              f"{None if knee is None else 0.8 * knee}", flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(
                {"workload": cell.name, "device": devices[0].device_kind,
                 "seconds": args.seconds, "steps": rows, "knee_rps": knee},
                indent=1))
        if args.trace_dir:
            record_trace(ctx, gen, min(r["rate_rps"] for r in rows),
                         args.seed, args.trace_dir)
    finally:
        engine.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
