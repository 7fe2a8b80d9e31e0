"""Seconds from process start to the window: imports, the collection,
the index (loaded, or built on a checkout's first run), geofences, the
delta backlog and warm-up."""


def read(run):
    return run.setup_s
