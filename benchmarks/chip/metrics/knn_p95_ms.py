"""95th percentile of kNN latency, due time to ids on the host, over every
kNN query due in the window."""
import numpy as np

from gen.traffic import KNN


def read(run):
    lat = run.latency_ms([KNN])
    return float(np.percentile(lat, 95)) if lat.size else None
