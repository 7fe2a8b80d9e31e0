"""Median length of the program's ``wisk.delta_insert`` span: one insert
call into the delta log (routing, slot allocation, buffer scatters, the
re-upload of the widened levels)."""
import prog_trace


def read(run):
    return prog_trace.span_ms(run, "wisk.delta_insert")
