"""Median host time per ``serve`` call in the engine's ``wisk.fetch`` span:
the copy of the dense id plane and the counters to the host."""
import prog_trace


def read(run):
    return prog_trace.host_ms(run, "wisk.serve", ("wisk.fetch",))
