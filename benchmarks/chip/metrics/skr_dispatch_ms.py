"""Median host time per ``serve`` call in the program's ``wisk.descend`` and
``wisk.verify`` spans: dispatching the per-level filter and expand steps,
the leaf selection, the fused kernel and the eager programs around it."""
import prog_trace


def read(run):
    return prog_trace.host_ms(run, "wisk.serve", ("wisk.descend", "wisk.verify"))
