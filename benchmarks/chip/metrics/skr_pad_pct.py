"""Share of the SKR batch rows sent to the device that were padding:
``skr.pad_rows`` over ``skr.rows + skr.pad_rows``, counted by the program
in the window."""
import prog_trace


def read(run):
    rows, pad = prog_trace.total(run, "skr.rows"), prog_trace.total(run, "skr.pad_rows")
    if rows is None or pad is None or rows + pad == 0:
        return None
    return 100.0 * pad / (rows + pad)
