"""Median host time of one ``serve_skr`` call: the benchmark's span minus
the device-busy time inside it (padding, plan syncs, the id copy, the
front door's Python), from the trace."""
import numpy as np


def read(run):
    spans = run.trace.spans.get("serve_skr", []) if run.trace else []
    if not spans:
        return None
    t0, t1 = np.asarray(spans, np.int64).T
    return float(np.median((t1 - t0) * 1e-9 - run.trace.busy_in(t0, t1)) * 1e3)
