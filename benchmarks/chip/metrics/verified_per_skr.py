"""Mean of the engine's exact ``verified`` counter per SKR query: objects
whose keywords matched and were checked against the rectangle -- the work
WISK's layout failed to prune."""
import numpy as np


def read(run):
    v = run.rec.counters.get("skr_verified", [])
    return float(np.mean(v)) if len(v) else None
