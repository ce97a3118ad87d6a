"""Compile the served executors for a described TPU v5e chip, at the shapes
``chip_smoke.py`` serves: no chip needed, and whatever the TPU compiler
refuses fails here.

The topology is described inside a module-scoped fixture (never at import,
in a ``skipif`` or in ``parametrize``), so every test worker collects the
same tests and only the worker that runs this file loads the TPU compiler.
JAX's persistent compilation cache stays off around these compiles: an
entry compiled for a described chip cannot be read back without one.
"""
import dataclasses
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

jax.config.update("jax_enable_x64", True)

from repro.core import build_index_1d, build_index_2d  # noqa: E402
from repro.data import hki_series, osm_points  # noqa: E402
from repro.engine import build_plan, build_plan_2d, fused_executor  # noqa: E402
from repro.engine.dynamic import _append_1d  # noqa: E402

N1 = 1_000_000         # chip_smoke.N1: rows of every 1-D table
N2 = 250_000           # chip_smoke.N2: points of every 2-D table
BUCKET = 1024          # chip_smoke.BATCH: the one warmed bucket
BQ = 256               # the session block size at that bucket


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _levels(n: int) -> int:
    """Rows of the (L, n) sparse table ``build_sparse_table`` keeps."""
    return max(1, int(np.floor(np.log2(max(n, 1)))) + 1)


def _abstract(plan, sharding, big: dict, n: int):
    """The plan as ShapeDtypeStructs on ``sharding``: its small-build
    segment tables as they are, the per-row arrays named in ``big`` at
    their full-size shapes, and the row count ``n`` in the metadata."""
    fields = {}
    for f in dataclasses.fields(plan):
        leaf = getattr(plan, f.name)
        if isinstance(leaf, jax.Array):
            fields[f.name] = jax.ShapeDtypeStruct(
                big.get(f.name, leaf.shape), leaf.dtype, sharding=sharding)
    return dataclasses.replace(plan, n=n, **fields)


def _compile(agg, plan, deg, n_ranges, sharding):
    fn = fused_executor(agg, False, backend="xla", eps_rel=0.01,
                        interpret=None, bq=BQ, deg=deg)
    qs = [jax.ShapeDtypeStruct((BUCKET,), jnp.float64,
                               sharding=sharding)] * n_ranges
    return jax.jit(fn).lower(plan, (), *qs).compile()


@pytest.mark.parametrize("agg,deg", [("count", 2), ("sum", 2), ("max", 3)])
def test_compile_1d_executor_for_v5e(one_chip, agg, deg):
    ts, vals = hki_series(20_000)
    delta = 500.0 if agg == "max" else 1e5
    idx = build_index_1d(ts, None if agg == "count" else vals, agg,
                         deg=deg, delta=delta)
    big = dict(ref_keys=(N1,), ref_cf=(N1,), ref_st=(_levels(N1), N1))
    plan = _abstract(build_plan(idx), one_chip, big, N1)
    compiled = _compile(agg, plan, deg, 2, one_chip)
    # the refinement arrays dominate: the whole (L, n) table for MAX/MIN,
    # keys + prefix CF for SUM/COUNT — all of it an executor argument
    per_row = 8 * (_levels(N1) + 1 if agg == "max" else 2)
    assert compiled.memory_analysis().argument_size_in_bytes >= per_row * N1
    assert "tpu_custom_call" not in compiled.as_text()   # backend='xla'


@pytest.mark.parametrize("batch", [1, 512])
def test_compile_buffer_append_for_v5e(one_chip, batch):
    """The dynamic tables' fused insert/delete append at the buffer
    capacity chip_smoke uses.  Its prefix sums must not lower through
    ``reduce_window``: in f64 that alone took about 160 s to compile for a
    v5e, and the first insert on the chip paid it."""
    cap = 1024
    log = jax.ShapeDtypeStruct((cap,), jnp.float64, sharding=one_chip)
    new = jax.ShapeDtypeStruct((batch,), jnp.float64, sharding=one_chip)
    lowered = _append_1d.lower(log, log, new, new, cap=cap, with_st=False)
    assert "reduce_window" not in lowered.as_text()
    lowered.compile()


def test_compile_2d_dominance_executor_for_v5e(one_chip):
    px, py = osm_points(5_000)
    w = 50.0 + 20.0 * np.sin(px / 7.0)
    idx = build_index_2d(px, py, measures=w, agg="max2d", deg=3, delta=5.0)
    levels = int(np.ceil(np.log2(N2))) + 1      # MergeSortTree levels
    big = {f: (levels, N2) for f in ("ref_ys_levels", "ref_wcum",
                                     "ref_wpmax")}
    big["ref_xs"] = (N2,)
    plan = _abstract(build_plan_2d(idx), one_chip, big, N2)
    compiled = _compile("max2d", plan, 3, 2, one_chip)
    # the dominance path reads the y levels and the prefix maxima (jit
    # drops the unused prefix sums from the executable's arguments)
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        8 * 2 * levels * N2
