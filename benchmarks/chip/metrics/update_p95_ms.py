"""95th percentile of update latency over every insert and delete due in
the window: due time until the call returned (an insert's notification
drain included)."""
import numpy as np

from gen.traffic import DELETE, INSERT


def read(run):
    lat = run.latency_ms([INSERT, DELETE])
    return float(np.percentile(lat, 95)) if lat.size else None
