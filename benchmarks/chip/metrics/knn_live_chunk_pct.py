"""Share of the kNN leaf phase's chunks in which some (query, leaf) pair was
still within its bound: ``knn.live_chunks`` over ``knn.chunks``, counted
in the window. The rest are scanned to no effect."""
import prog_trace


def read(run):
    live, chunks = prog_trace.total(run, "knn.live_chunks"), prog_trace.total(run, "knn.chunks")
    return 100.0 * live / chunks if live is not None and chunks else None
