"""repro.engine — unified backend-dispatched query execution (DESIGN.md §7).

Lower a constructed index into a canonical device-resident ``IndexPlan``
once, then execute every query type through the module-level ``execute_*``
dispatch path (or the ``Engine`` shim that binds a backend onto it) with
``backend='xla' | 'pallas' | 'pallas_scan' | 'ref'`` (``pallas`` is the
O(log H) locate->gather path, ``pallas_scan`` the one-hot membership scan
it replaced — kept for A/B benchmarking, DESIGN.md §10):

    from repro.core import build_index_1d
    from repro.engine import Engine, build_plan

    plan = build_plan(build_index_1d(keys, meas, "sum", delta=eps / 2))
    eng = Engine(backend="pallas")
    res = eng.query(plan, lq, uq, eps_rel=0.01)   # fused approx + refine

``shard_plan`` + ``ShardedEngine`` (engine/sharded.py) partition a 1-D
plan's segment tables across devices and answer through a ``shard_map``
executor with psum/pmax combination — bit-identical to the single-device
path; ``shard_plan_2d`` + ``ShardedEngine2D`` do the same for 2-D plans by
contiguous Morton z-ranges (DESIGN.md §12).  2-D plans carry measures:
``execute_sum2d`` answers rectangle SUM via the 4-corner decomposition and
``execute_extremum2d`` dominance MAX/MIN at a corner, with
``DynamicEngine2D`` buffering updates and merging through the selective
leaf refit.  ``engine.lsm`` stacks immutable plans into a geometric level
ladder (``LsmEngine``/``LsmEngine2D``) with worst-case update guarantees:
queries fuse O(log n) per-level evaluations exactly and merges become
bounded level-compactions (DESIGN.md §15).  This module is the execution
layer behind the declarative ``repro.api.PolyFit`` facade, which new code
should prefer; the Pallas kernels and their jnp oracles are implementation
details below it.
"""
from .dynamic import (DeltaBuffer, DeltaBuffer2D, DynamicEngine,
                      DynamicEngine2D, fused_executor,
                      fused_quantile_executor)
from .engine import (BACKENDS, Engine, QuantileResult, execute,
                     execute_count2d, execute_extremum, execute_extremum2d,
                     execute_quantile, execute_sum, execute_sum2d,
                     pad_fills, refine_slots)
from .lsm import (CompactionPolicy, LsmEngine, LsmEngine2D, LsmLevel,
                  LsmLevel2D, LsmPlan, LsmPlan2D, composed_bound,
                  execute_lsm, level_executor)
from .plan import (IndexPlan, IndexPlan2D, big_sentinel, build_plan,
                   build_plan_2d, pad_to_multiple)
from .window import WindowEngine
from .sharded import (ShardedDelta, ShardedEngine, ShardedEngine2D,
                      ShardedLsmPlan, ShardedLsmPlan2D, ShardedPlan,
                      ShardedPlan2D, execute_lsm_sharded, make_shard_mesh,
                      shard_buffer, shard_lsm_plan, shard_lsm_plan_2d,
                      shard_plan, shard_plan_2d)

__all__ = ["Engine", "BACKENDS", "IndexPlan", "IndexPlan2D", "build_plan",
           "build_plan_2d", "big_sentinel", "pad_to_multiple",
           "DynamicEngine", "DynamicEngine2D", "DeltaBuffer",
           "DeltaBuffer2D", "fused_executor", "fused_quantile_executor",
           "pad_fills", "refine_slots", "execute", "execute_sum", "execute_extremum",
           "execute_quantile", "QuantileResult", "WindowEngine",
           "execute_count2d", "execute_sum2d", "execute_extremum2d",
           "LsmEngine", "LsmEngine2D", "LsmPlan", "LsmPlan2D", "LsmLevel",
           "LsmLevel2D", "CompactionPolicy", "composed_bound",
           "execute_lsm", "level_executor",
           "ShardedEngine", "ShardedEngine2D", "ShardedPlan",
           "ShardedPlan2D", "ShardedDelta", "shard_plan", "shard_plan_2d",
           "shard_buffer", "make_shard_mesh", "ShardedLsmPlan",
           "ShardedLsmPlan2D", "shard_lsm_plan", "shard_lsm_plan_2d",
           "execute_lsm_sharded"]
