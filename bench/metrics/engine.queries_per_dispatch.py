"""Range queries per device dispatch over the window: the queries sent
over ``EngineStats.dispatches`` — how much the admission batcher
coalesces."""


def read(run):
    d = run.stats["dispatches"]
    return float(run.record.sizes.sum()) / d if d else None
