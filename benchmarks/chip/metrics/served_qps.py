"""Read queries (SKR and kNN) answered inside the window, per second of
window."""
import numpy as np

from gen.traffic import KNN, SKR


def read(run):
    reads = np.isin(run.plan.kind, (SKR, KNN))
    if not reads.any():
        return None
    done = run.rec.done[reads]
    return float(np.sum(done <= run.window_s)) / run.window_s
