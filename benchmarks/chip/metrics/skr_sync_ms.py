"""Median host time per ``serve`` call in the plan layer's ``wisk.sync``
span: the batched read of the frontier widths, and the exact re-descent
(``wisk.redescend``) when a width overflowed."""
import prog_trace


def read(run):
    return prog_trace.host_ms(run, "wisk.serve", ("wisk.sync",))
