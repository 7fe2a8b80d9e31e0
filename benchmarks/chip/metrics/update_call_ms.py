"""Median host time of one update call (insert with its notification
drain, or delete), from the benchmark's own span around it."""
import numpy as np


def read(run):
    spans = run.spans("update")
    return float(np.median([t1 - t0 for t0, t1 in spans]) * 1e3) if spans else None
