"""Median host time of the program's ``wisk.serve_knn`` span: its length
minus the device-busy time inside it."""
import prog_trace


def read(run):
    return prog_trace.host_ms(run, "wisk.serve_knn")
