"""Training launcher: --arch x --shape on a (data, model) mesh with
checkpoint/restart, heartbeat/straggler monitoring, and injected-failure
recovery (elastic re-mesh + restore).

CPU-runnable end to end with --smoke (reduced config); the production mesh
path is exercised shape-only by launch/dryrun.py.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 20 --fail-at 7 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax
from jax.sharding import NamedSharding

from ..checkpoint import CheckpointManager
from ..configs import ARCHS, SHAPES
from ..data.pipeline import SyntheticTokens
from ..dist.fault_tolerance import (FailureInjector, HeartbeatMonitor,
                                    SimulatedPodFailure, elastic_remesh)
from ..dist.sharding import batch_specs, param_specs, state_specs
from ..models import init_model
from ..optim import adamw_init
from ..train import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--shape", default="train_4k", choices=sorted(SHAPES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny batch (CPU)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject a simulated pod failure at these steps")
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = ARCHS[args.arch]
    shape = SHAPES[args.shape]
    if args.smoke:
        cfg = cfg.smoke()
        import dataclasses as dc
        shape = dc.replace(shape, seq_len=32, global_batch=4)

    def build_mesh():
        return jax.make_mesh((args.data_axis, args.model_axis),
                             ("data", "model"))

    mesh = build_mesh()
    rng = jax.random.PRNGKey(0)
    params = init_model(rng, cfg)
    pspecs = param_specs(params, mesh)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs)
    state = adamw_init(params)
    sspecs = state_specs(params, mesh)

    pipe = SyntheticTokens(cfg.vocab, shape.seq_len, shape.global_batch,
                           frontend=cfg.frontend,
                           frontend_dim=cfg.frontend_dim,
                           n_img_tokens=cfg.n_img_tokens,
                           enc_len=shape.seq_len)
    bspecs = batch_specs(cfg, shape, mesh)
    bshard = {k: NamedSharding(mesh, s) for k, s in bspecs.items()}

    train_step = jax.jit(make_train_step(cfg, microbatches=args.microbatches,
                                         total_steps=args.steps),
                         donate_argnums=(0,))

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    monitor = HeartbeatMonitor()
    injector = FailureInjector(tuple(args.fail_at))
    start_step = 0
    if ckpt and ckpt.latest_step() is not None:
        state = ckpt.restore(state, mesh=mesh, specs=sspecs)
        start_step = ckpt.latest_step() + 1
        print(f"[train] restored checkpoint step {start_step - 1}")

    step = start_step
    while step < args.steps:
        try:
            injector.check(step)
            with jax.sharding.set_mesh(mesh):
                batch = pipe.sharded_batch(step, bshard)
                state, metrics = train_step(state, batch)
            msg = monitor.beat()
            if msg:
                print(f"[train][warn] {msg}")
            if step % 1 == 0:
                print(f"[train] step {step} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f}")
            if ckpt and step % args.ckpt_every == 0:
                ckpt.save_async(step, tuple(state))
            step += 1
        except SimulatedPodFailure as e:
            print(f"[train][FAILURE] {e}; re-meshing + restoring")
            injector = FailureInjector(tuple(s for s in args.fail_at
                                             if s != step))
            if ckpt:
                ckpt.wait()
                state = ckpt.restore(state)
                state, mesh = elastic_remesh(state, sspecs, build_mesh)
                step = ckpt.latest_step() + 1
            else:
                state, mesh = elastic_remesh(state, sspecs, build_mesh)
            # input shardings are mesh-bound; rebind to the rebuilt mesh
            bshard = {k: NamedSharding(mesh, s) for k, s in bspecs.items()}
    if ckpt:
        ckpt.wait()
    print(f"[train] done at step {step}")
    return state


if __name__ == "__main__":
    main()
