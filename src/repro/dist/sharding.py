"""PartitionSpec builders for the launch stack (train/dryrun contracts).

One rule, applied uniformly: shard exactly one dimension of each leaf —
the largest dimension divisible by the chosen mesh-axis group — and
replicate the rest.  Axis groups are tried widest first (every mesh axis
combined: full ZeRO-style FSDP over pod x data x model), narrowing to
``('data', 'model')``, ``'model'``, ``'data'``; a leaf with no divisible
dimension replicates.  Scan-stacked block leaves (any path through
``blocks`` / ``enc_blocks`` / ``dec_blocks``) never shard their leading
layer axis — it is the ``lax.scan`` carry axis, and sharding it would
force a per-layer re-gather inside the scan.

On the (1, 1) smoke mesh every group has size 1, so every spec degrades
to replication and the same launcher code runs on one CPU device, the
16x16 pod, or the 2x16x16 multi-pod mesh.

``state_specs`` mirrors the param specs onto the AdamW ``TrainState``
(m/v shard exactly like their parameters, the step count replicates);
``batch_specs``/``cache_specs`` shard the batch dimension over the
data-parallel axes; ``named`` maps a spec pytree to ``NamedSharding``s
for jit in/out_shardings.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["param_specs", "state_specs", "batch_specs", "cache_specs",
           "named"]

# leaves reached through these keys are scan-stacked with a leading layer
# axis that must stay replicated
_STACKED_KEYS = ("blocks", "enc_blocks", "dec_blocks")


def _axis_groups(mesh) -> Tuple[Tuple[str, ...], ...]:
    """Candidate shard-axis groups, widest first."""
    names = tuple(mesh.axis_names)
    groups = [names]
    for g in (("data", "model"), ("model",), ("data",)):
        if all(a in names for a in g) and g != names:
            groups.append(g)
    return tuple(groups)


def _group_size(mesh, group: Tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in group], dtype=np.int64))


def _leaf_spec(shape: Tuple[int, ...], mesh, *, skip_leading: bool) -> P:
    """One sharded dim (largest divisible), widest axis group wins."""
    if len(shape) == 0:
        return P()
    entries: list = [None] * len(shape)
    start = 1 if skip_leading and len(shape) > 1 else 0
    dims = sorted(range(start, len(shape)), key=lambda d: -shape[d])
    for group in _axis_groups(mesh):
        size = _group_size(mesh, group)
        if size == 1:
            continue
        for d in dims:
            if shape[d] % size == 0:
                entries[d] = group if len(group) > 1 else group[0]
                return P(*entries)
    return P(*entries)


def _is_stacked(path) -> bool:
    for entry in path:
        key = getattr(entry, "key", getattr(entry, "name", None))
        if key in _STACKED_KEYS:
            return True
    return False


def param_specs(params: Any, mesh) -> Any:
    """A pytree of ``PartitionSpec`` matching ``params`` leaf for leaf.

    Works on concrete arrays and on ``jax.eval_shape`` trees alike (only
    ``.shape`` is read).
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, x: _leaf_spec(tuple(x.shape), mesh,
                                   skip_leading=_is_stacked(path)),
        params)


def state_specs(params: Any, mesh) -> Any:
    """Specs for the AdamW ``TrainState`` over ``params``: m and v shard
    exactly like their parameters, the step count replicates."""
    from ..optim import TrainState
    pspecs = param_specs(params, mesh)
    return TrainState(pspecs, pspecs, pspecs, P())


def _dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _batch_dim_spec(mesh, batch: int) -> Any:
    """Dim-0 entry for a global-batch-leading array: the data axes when
    they divide the batch, else replicated."""
    dp = _dp_axes(mesh)
    if not dp or batch % _group_size(mesh, dp) != 0:
        return None
    return dp if len(dp) > 1 else dp[0]


def batch_specs(cfg, shape, mesh) -> Dict[str, P]:
    """Input-batch specs keyed like ``SyntheticTokens.batch``: the batch
    dimension shards over the data-parallel axes, everything else
    replicates (sequence stays whole — no context parallelism here)."""
    b = _batch_dim_spec(mesh, shape.global_batch)
    specs = {"tokens": P(b)}
    if cfg.frontend == "audio_stub":
        specs["frames"] = P(b)
    elif cfg.frontend == "vision_stub":
        specs["images"] = P(b)
    return specs


def cache_specs(cfg, shape, mesh) -> Any:
    """Decode-cache specs matching ``init_cache(cfg, B, S)`` structurally.

    Built from an ``eval_shape`` of the real cache tree so every family's
    layout (kv / ssm / hybrid / encdec) is covered by one rule: the first
    dimension whose extent equals the global batch shards over the data
    axes, everything else replicates.
    """
    from ..models import init_cache
    B, S = shape.global_batch, shape.seq_len
    abstract = jax.eval_shape(lambda: init_cache(cfg, B, S))
    b = _batch_dim_spec(mesh, B)

    def leaf(x) -> P:
        entries: list = [None] * len(x.shape)
        if b is not None:
            for d, extent in enumerate(x.shape):
                if extent == B:
                    entries[d] = b
                    break
        return P(*entries)

    return jax.tree.map(leaf, abstract)


def named(mesh, specs: Any) -> Any:
    """Map a ``PartitionSpec`` pytree to ``NamedSharding``s on ``mesh``
    (jit in/out_shardings take sharding pytrees, not spec pytrees)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
