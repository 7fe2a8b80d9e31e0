"""95th percentile of SKR latency, due time to ids on the host, over every
SKR query due in the window (an unanswered one counts as the grace's end)."""
import numpy as np

from gen.traffic import SKR


def read(run):
    lat = run.latency_ms([SKR])
    return float(np.percentile(lat, 95)) if lat.size else None
