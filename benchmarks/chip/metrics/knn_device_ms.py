"""Median device-busy time inside one ``serve_knn`` call, from the trace."""
import numpy as np


def read(run):
    spans = run.trace.spans.get("serve_knn", []) if run.trace else []
    if not spans:
        return None
    t0, t1 = np.asarray(spans, np.int64).T
    return float(np.median(run.trace.busy_in(t0, t1)) * 1e3)
