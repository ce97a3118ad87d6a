"""99th percentile insert visibility in ms: from each insert batch's
scheduled time to ``insert(..., wait=True)`` returning, when its records
are query-visible; over every insert of the window."""
import numpy as np


def read(run):
    rec = run.record
    if rec.insert_scheduled is None or not len(rec.insert_scheduled):
        return None
    lat = rec.insert_latencies
    lat = lat[np.isfinite(lat)]
    return float(np.percentile(lat, 99) * 1e3) if len(lat) else None
