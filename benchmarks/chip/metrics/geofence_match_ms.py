"""Median length of the program's ``wisk.geofence_match`` span: matching one
insert call's arrivals against the standing geofences (word packing, the
``sub_match`` kernel, queueing the notices)."""
import prog_trace


def read(run):
    return prog_trace.span_ms(run, "wisk.geofence_match")
