"""Distribution self-test: forces an 8-device host topology (scoped to this
module) and verifies the cross-device building blocks end to end:

1. **shard_map PolyFit** — partitioned segment tables answered with
   psum/pmax combination are bit-identical to the single-device engine
   (the certified Q_abs/Q_rel guarantees therefore survive sharding);
2. **int8 ring all-reduce** — reduce-scatter + all-gather over ppermute
   with ``dist.compression`` int8 wire format; error within the analytic
   quantization bound, all replicas agree;
3. **pipeline parallelism** — an 8-stage ppermute pipeline streaming
   microbatches matches the sequential composition;
4. **checkpoint re-sharding** — a pytree saved from one mesh layout
   restores onto a different layout with identical values.

    PYTHONPATH=src python -m repro.dist._selftest

Prints ``ALL_DIST_OK`` on success (tests/test_distributed.py asserts on
this marker).
"""
from __future__ import annotations

import os
import tempfile

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

jax.config.update("jax_enable_x64", True)

WORLD = 8


def check_polyfit_shard_map() -> None:
    """Sharded PolyFit plans: psum (SUM/COUNT) / pmax (MAX) combination is
    bit-identical to the single-device engine, so Lemma 5.1-5.4 transfer."""
    from repro.core import build_index_1d
    from repro.engine import Engine, ShardedEngine, build_plan

    rng = np.random.default_rng(2)
    keys = np.sort(rng.uniform(0, 500, 3000))
    meas = rng.uniform(0, 10, 3000)
    a = keys[rng.integers(0, 3000, 96)]
    b = keys[rng.integers(0, 3000, 96)]
    lq, uq = np.minimum(a, b), np.maximum(a, b)
    for agg, m, deg in (("sum", meas, 2), ("max", meas * 100, 3)):
        plan = build_plan(build_index_1d(keys, m, agg, deg=deg, delta=20.0))
        ref = Engine(backend="xla").query(plan, lq, uq, eps_rel=0.05)
        got = ShardedEngine(WORLD).query(plan, lq, uq, eps_rel=0.05)
        np.testing.assert_array_equal(np.asarray(ref.answer),
                                      np.asarray(got.answer))
    print("[dist-selftest] shard_map PolyFit psum/pmax: OK")


def check_int8_ring_allreduce() -> None:
    """Ring all-reduce (reduce-scatter + all-gather over ppermute) with the
    int8 wire format from dist.compression."""
    from repro.dist.compression import dequantize_int8, quantize_int8

    mesh = Mesh(np.array(jax.devices()[:WORLD]), ("ring",))
    perm = [(i, (i + 1) % WORLD) for i in range(WORLD)]
    chunk = 128

    def body(x):
        x = x.reshape(WORLD, chunk)          # one chunk slot per device
        idx = jax.lax.axis_index("ring")
        acc = x
        # ring reduce-scatter: at step k device d forwards slot (d - k),
        # accumulating into slot (d - k - 1); after W-1 steps device d
        # owns the fully reduced slot (d + 1) mod W.  Each hop ships int8
        # codes + one scale (the compressed wire format).
        for k in range(WORLD - 1):
            send = jnp.take(acc, (idx - k) % WORLD, axis=0)
            q, s = quantize_int8(send)
            q = jax.lax.ppermute(q, "ring", perm)
            s = jax.lax.ppermute(s, "ring", perm)
            recv = dequantize_int8(q, s, x.dtype)
            acc = acc.at[(idx - k - 1) % WORLD].add(recv)
        owned = jnp.take(acc, (idx + 1) % WORLD, axis=0)
        # all-gather the owned slots; row i of the gather is device i's
        # slot (i + 1) mod W, so a static re-order recovers slot order —
        # every replica assembles from the *same* owned chunks
        gathered = jax.lax.all_gather(owned, "ring")
        return gathered[(np.arange(WORLD) - 1) % WORLD]

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (WORLD, WORLD * chunk)), jnp.float32)
    got = jax.jit(shard_map(body, mesh=mesh, in_specs=P("ring"),
                            out_specs=P("ring"), check_vma=False))(x)
    got = np.asarray(got).reshape(WORLD, WORLD, chunk)  # per-device copies
    exact = np.asarray(x).reshape(WORLD, WORLD, chunk).sum(0)
    # each chunk crosses <= W-1 quantized hops, each adding <= scale/2
    # per element with scale <= max|partial| / 127
    tol = (WORLD - 1) * (np.abs(np.asarray(x)).max() * WORLD / 127.0)
    for d in range(WORLD):
        err = np.abs(got[d] - exact).max()
        assert err <= tol, (d, err, tol)
    # all replicas agree bitwise on the assembled result
    for d in range(1, WORLD):
        np.testing.assert_array_equal(got[0], got[d])
    print(f"[dist-selftest] int8 ring all-reduce: OK (tol {tol:.3f})")


def check_pipeline_parallelism() -> None:
    """8-stage ppermute pipeline streaming 16 microbatches == sequential."""
    mesh = Mesh(np.array(jax.devices()[:WORLD]), ("pp",))
    t_micro, width = 16, 32
    rng = np.random.default_rng(4)
    ws = jnp.asarray(rng.normal(0, 0.5, (WORLD, width)), jnp.float64)
    xs = jnp.asarray(rng.normal(0, 1, (t_micro, width)), jnp.float64)

    def stage(w, h):
        return jnp.tanh(h + w)

    def body(w, xs):
        w = w[0]
        shift = [(i, (i + 1) % WORLD) for i in range(WORLD)]
        idx = jax.lax.axis_index("pp")
        state = jnp.zeros((width,), xs.dtype)
        outs = jnp.zeros_like(xs)
        for t in range(t_micro + WORLD - 1):
            feed = xs[jnp.clip(t, 0, t_micro - 1)]
            inp = jnp.where(idx == 0, feed, state)
            h = stage(w, inp)
            state = jax.lax.ppermute(h, "pp", shift)
            done = t - (WORLD - 1)            # microbatch leaving the last
            outs = jnp.where(
                (jnp.arange(t_micro) == done)[:, None]
                & (idx == WORLD - 1), h[None, :], outs)
        return jax.lax.psum(outs, "pp")       # only the last stage wrote

    got = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("pp"), P()),
                            out_specs=P(), check_vma=False))(ws, xs)
    ref = xs
    for s in range(WORLD):
        ref = jax.vmap(lambda h, w=ws[s]: stage(w, h))(ref)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-12, atol=1e-12)
    print("[dist-selftest] pipeline parallelism: OK")


def check_checkpoint_reshard() -> None:
    """Save sharded on ('data',), restore re-sharded on ('model',)."""
    from repro.checkpoint import CheckpointManager

    devs = np.array(jax.devices()[:WORLD])
    mesh_a = Mesh(devs.reshape(WORLD, 1), ("data", "model"))
    mesh_b = Mesh(devs.reshape(1, WORLD), ("data", "model"))
    rng = np.random.default_rng(5)
    tree = {"w": jnp.asarray(rng.normal(0, 1, (WORLD * 4, 16))),
            "b": jnp.asarray(rng.normal(0, 1, (16,)))}
    specs_a = {"w": P("data", None), "b": P()}
    specs_b = {"w": P(None, "model"), "b": P()}
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh_a, s)),
        tree, specs_a)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, placed)
        restored = mgr.restore(tree, mesh=mesh_b, specs=specs_b)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(tree[k]))
        assert restored[k].sharding.spec == specs_b[k]
    print("[dist-selftest] checkpoint re-sharding: OK")


def main() -> None:
    assert jax.device_count() >= WORLD, jax.device_count()
    check_polyfit_shard_map()
    check_int8_ring_allreduce()
    check_pipeline_parallelism()
    check_checkpoint_reshard()
    print("ALL_DIST_OK")


if __name__ == "__main__":
    main()
