"""Median read latency in ms: from each request's scheduled arrival to its
future resolving, over every read request of the window."""
import numpy as np


def read(run):
    lat = run.record.read_latencies[run.record.ok]
    return float(np.percentile(lat, 50) * 1e3) if len(lat) else None
