"""Median device time of the fused-verify kernel inside one ``serve_skr``
call: the programs named ``jit_fused_verify*`` (the Pallas kernel's own
program, in every variant: resident or prefetch, full or compact width)."""
import numpy as np

PROGRAM = "jit_fused_verify"


def read(run):
    spans = run.trace.spans.get("serve_skr", []) if run.trace else []
    if not spans:
        return None
    t0, t1 = np.asarray(spans, np.int64).T
    per_call = run.trace.module_time_in(PROGRAM, t0, t1)
    return float(np.median(per_call) * 1e3) if per_call.any() else None
