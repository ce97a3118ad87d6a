import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch qwen3-1.7b
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi          # all

Per cell this produces benchmarks/results/dryrun/<mesh>_<arch>_<shape>.json
holding: per-device memory stats, per-device HLO flops/bytes,
collective-bytes by op type (parsed from the optimized HLO), and the
roofline terms of EXPERIMENTS.md §Roofline.
"""
import argparse     # noqa: E402
import json         # noqa: E402
import re           # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import ARCHS, SHAPES  # noqa: E402
from ..dist.sharding import (batch_specs, cache_specs, named,  # noqa: E402
                             param_specs, state_specs)
from ..launch.mesh import dp_axes, make_production_mesh  # noqa: E402
from ..models import init_cache, init_model  # noqa: E402
from ..optim import adamw_init  # noqa: E402
from ..serve.step import make_serve_step, make_prefill  # noqa: E402
from ..train import make_train_step  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

# TPU v5e constants (roofline)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link
HBM_PER_CHIP = 16e9          # v5e HBM

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# Per-arch gradient-accumulation depth for train cells: the smallest M whose
# activations fit 16 GB/chip (probed; EXPERIMENTS.md §Perf P7).  Lower M
# means fewer FSDP weight re-gathers per step — the train cells' dominant
# collective cost scales ~linearly with M.
TRAIN_MICROBATCHES = {
    "phi3-medium-14b": 8, "zamba2-2.7b": 8,
    "phi3.5-moe-42b-a6.6b": 8, "qwen3-moe-30b-a3b": 8,
}
DEFAULT_MICROBATCHES = 4


def _shape_bytes(shape_str: str) -> int:
    """'f32[2,4096]' -> byte count (0 for token/opaque)."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collectives(hlo_text: str):
    """Sum result-shape bytes of every collective op in optimized HLO.

    Bytes are per-device (HLO shapes after SPMD partitioning are local).
    Returns {op_type: {'count': n, 'bytes': b}}.
    """
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    # '%x = TYPE[dims]{layout} all-reduce(' or tuple results
    pat = re.compile(
        r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start|-done)?\(")
    for m in pat.finditer(hlo_text):
        shapes, op = m.groups()
        total = 0
        for sm in re.finditer(r"[a-z0-9]+\[[0-9,]*\]", shapes):
            total += _shape_bytes(sm.group(0))
        # -start/-done pairs would double count; only count starts and plain
        before = hlo_text[m.start():m.end()]
        if "-done(" in before:
            continue
        out[op]["count"] += 1
        out[op]["bytes"] += total
    return out


def input_specs(cfg, shape, mesh):
    """ShapeDtypeStruct stand-ins for a cell's inputs (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        n_txt = S - cfg.n_img_tokens if cfg.family == "vlm" else S
        batch = {"tokens": sds((B, n_txt), jnp.int32)}
        if cfg.family == "encdec":
            batch = {"tokens": sds((B, cfg.dec_seq), jnp.int32),
                     "frames": sds((B, S, cfg.frontend_dim), jnp.float32)}
        elif cfg.family == "vlm":
            batch["images"] = sds((B, cfg.n_img_tokens, cfg.frontend_dim),
                                  jnp.float32)
        return batch
    if shape.kind == "prefill":
        n_txt = S - cfg.n_img_tokens if cfg.family == "vlm" else S
        batch = {"tokens": sds((B, n_txt), jnp.int32)}
        if cfg.family == "encdec":
            batch = {"tokens": sds((B, cfg.dec_seq), jnp.int32),
                     "frames": sds((B, S, cfg.frontend_dim), jnp.float32)}
        elif cfg.family == "vlm":
            batch["images"] = sds((B, cfg.n_img_tokens, cfg.frontend_dim),
                                  jnp.float32)
        return batch
    if shape.kind == "decode":
        cache = jax.eval_shape(lambda: init_cache(cfg, B, S))
        return {"cache": cache, "token": sds((B,), jnp.int32),
                "pos": sds((), jnp.int32)}
    raise ValueError(shape.kind)


def runnable(cfg, shape) -> str:
    """'' if the cell runs; otherwise the documented skip reason."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("skip: pure full-attention arch (no windowing/SSM); 500k "
                "context needs sub-quadratic attention (DESIGN.md §5)")
    return ""


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatches: int = 0):
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    tag = f"{mesh_name}_{arch}_{shape_name}"
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = os.path.join(RESULTS_DIR, tag + ".json")

    reason = runnable(cfg, shape)
    if reason:
        json.dump({"cell": tag, "status": "skipped", "reason": reason},
                  open(out_path, "w"), indent=1)
        print(f"[dryrun] {tag}: SKIP ({reason})")
        return

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    try:
      # the PolyFit core turns global x64 on, which leaks s64 *index*
      # dtypes into the model stack's scans (the layer k/v stacking);
      # the SPMD partitioner rejects the resulting s64/s32 index compares
      # on 512-way meshes.  The model stack is dtype-explicit, so lowering
      # with x64 off is value-identical.
      with jax.experimental.disable_x64():
        params_abs = jax.eval_shape(
            lambda: init_model(jax.random.PRNGKey(0), cfg))
        pspecs = param_specs(params_abs, mesh)
        bspecs = batch_specs(cfg, shape, mesh)

        with jax.sharding.set_mesh(mesh):
            if shape.kind == "train":
                state_abs = jax.eval_shape(adamw_init, params_abs)
                sspecs = state_specs(params_abs, mesh)
                batch_abs = input_specs(cfg, shape, mesh)
                mb = microbatches or TRAIN_MICROBATCHES.get(
                    arch, DEFAULT_MICROBATCHES)
                if shape.global_batch % mb:
                    mb = 1
                step = make_train_step(cfg, microbatches=mb)
                lowered = jax.jit(
                    step,
                    in_shardings=(named(mesh, sspecs),
                                  {k: NamedSharding(mesh, s)
                                   for k, s in bspecs.items()}),
                    donate_argnums=(0,),
                ).lower(state_abs, batch_abs)
            elif shape.kind == "prefill":
                batch_abs = input_specs(cfg, shape, mesh)
                cspecs = cache_specs(cfg, shape, mesh)
                pre = make_prefill(cfg)
                lowered = jax.jit(
                    pre,
                    in_shardings=(named(mesh, pspecs),
                                  {k: NamedSharding(mesh, s)
                                   for k, s in bspecs.items()}),
                    out_shardings=(named(mesh, cspecs), None),
                ).lower(params_abs, batch_abs)
            else:  # decode
                ins = input_specs(cfg, shape, mesh)
                cspecs = cache_specs(cfg, shape, mesh)
                dp = dp_axes(mesh)
                dpsz = int(np.prod([mesh.shape[a] for a in dp]))
                tok_spec = P(dp if len(dp) > 1 else dp[0]) \
                    if shape.global_batch % dpsz == 0 else P(None)
                serve = make_serve_step(cfg)
                lowered = jax.jit(
                    serve,
                    in_shardings=(named(mesh, pspecs), named(mesh, cspecs),
                                  NamedSharding(mesh, tok_spec),
                                  NamedSharding(mesh, P())),
                    out_shardings=(None, named(mesh, cspecs)),
                    donate_argnums=(1,),
                ).lower(params_abs, ins["cache"], ins["token"], ins["pos"])

            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        hlo = compiled.as_text()
        coll = parse_collectives(hlo)

        flops = float(ca.get("flops", 0.0))
        bytes_accessed = float(ca.get("bytes accessed", 0.0))
        coll_bytes = sum(v["bytes"] for v in coll.values())
        terms = {
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_accessed / HBM_BW,
            "collective_s": coll_bytes / ICI_BW,
        }
        dominant = max(terms, key=terms.get)
        # live bytes: outputs aliased onto donated inputs don't re-count
        dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        result = {
            "cell": tag, "status": "ok", "arch": arch, "shape": shape_name,
            "mesh": list(mesh.shape.items()), "chips": n_chips,
            "kind": shape.kind,
            "microbatches": (microbatches or TRAIN_MICROBATCHES.get(
                arch, DEFAULT_MICROBATCHES)) if shape.kind == "train" else 1,
            "compile_s": round(time.time() - t0, 1),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "per_device_total": dev_bytes,
                "fits_hbm": bool(dev_bytes < HBM_PER_CHIP),
            },
            "cost": {"flops_per_device": flops,
                     "bytes_per_device": bytes_accessed},
            "collectives": coll,
            "collective_bytes_per_device": coll_bytes,
            "roofline_terms_s": terms,
            "dominant_term": dominant,
        }
        json.dump(result, open(out_path, "w"), indent=1)
        print(f"[dryrun] {tag}: OK compile={result['compile_s']}s "
              f"mem/dev={dev_bytes/1e9:.2f}GB flops/dev={flops:.3e} "
              f"coll={coll_bytes/1e6:.1f}MB dominant={dominant}")
    except Exception as e:  # noqa: BLE001
        json.dump({"cell": tag, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-4000:]},
                  open(out_path, "w"), indent=1)
        print(f"[dryrun] {tag}: ERROR {type(e).__name__}: {e}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES))
    ap.add_argument("--microbatches", type=int, default=0)
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                run_cell(arch, shape, mp, args.microbatches)


if __name__ == "__main__":
    main()
