"""Median device time of the fused gather+verify kernel inside one
``serve_skr`` call, read from the device's operations (``XLA Ops``) by the
kernel's own name, whatever program holds it: ``fused_verify`` with
``_prefetch`` and/or ``_compact`` (the ``pallas_call`` names), an HLO
instruction such as ``%fused_verify_prefetch_compact.1``. Unlike
``fused_verify_ms``, which reads the kernel's own ``jit_fused_verify*``
program, it still finds the kernel inside a larger compiled stage."""
import re

import numpy as np

import trace_reduce

KERNEL = re.compile(r"fused_verify(_prefetch)?(_compact)?(\.\d+)?")


def is_kernel(op_name: str) -> bool:
    """``%fused_verify_compact.3 = ...`` -> True: the op's HLO name, without
    its ``%`` and what follows it, is one of the kernel's names."""
    words = op_name.lstrip("%").split()
    return bool(words) and KERNEL.fullmatch(words[0]) is not None


def read(run):
    spans = run.trace.spans.get("serve_skr", []) if run.trace else []
    if not spans or not run.trace.ops:
        return None
    t0, t1 = np.asarray(spans, np.int64).T
    per_chip = [
        trace_reduce.covered(trace_reduce.union([(s, e) for n, s, e in ops if is_kernel(n)]), t0, t1)
        for ops in run.trace.ops.values()
    ]
    per_call = np.mean(per_chip, axis=0) * 1e-9
    return float(np.median(per_call) * 1e3) if per_call.any() else None
