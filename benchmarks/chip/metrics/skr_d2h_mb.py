"""Bytes the engine copied to the host per ``serve`` call, pads included:
the counter ``skr.d2h_bytes`` over the window's ``wisk.serve`` calls, in
MiB."""
import prog_trace


def read(run):
    n = prog_trace.calls(run, "wisk.serve")
    got = prog_trace.total(run, "skr.d2h_bytes")
    return got / n / 2**20 if n and got is not None else None
