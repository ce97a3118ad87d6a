"""99th percentile read latency in ms, from each request's scheduled
arrival to its future resolving, over every read request of the window.

A per-layer reading of the admission layer: near the knee the tail is set
by the serving thread compiling the shapes of coalesced batches, one after
another, and swings far from run to run; ``read_p50_ms`` is the cell's
end-to-end latency."""
import numpy as np


def read(run):
    lat = run.record.read_latencies[run.record.ok]
    return float(np.percentile(lat, 99) * 1e3) if len(lat) else None
