"""Median host time per ``serve`` call in the program's ``wisk.prep`` spans:
padding the batch to its bucket, packing the query words and uploading
them (``prog_trace.host_ms``)."""
import prog_trace


def read(run):
    return prog_trace.host_ms(run, "wisk.serve", ("wisk.prep",))
