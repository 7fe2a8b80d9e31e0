"""Spans and counters the program keeps about itself.

Recording follows the profiler's own switch: while ``jax.profiler.trace``
(or a TensorBoard capture) is on, every ``span`` opens a
``jax.profiler.TraceAnnotation`` -- so it shows in the trace next to the
device's operations -- and appends ``(name, t0_ns, t1_ns, depth, batch)``
on ``time.perf_counter_ns()`` to an in-memory log. With the profiler off a
span is one check and a shared no-op context: no allocation, no clock read.

``count(name, n)`` always adds to a process-wide total (``totals()``, the
operator view behind ``LiveIndex.stats()``); while recording it also logs
``(name, t_ns, n)``, so a reader can sum exactly what happened inside a
traced window.

The log is capped at ``LOG_CAP`` entries; past the cap entries are dropped
and counted under ``obs.dropped``. Spans nest per thread: ``depth`` is the
number of recording spans open around one, and a span opened with none
around it starts a new ``batch`` number that every span inside it shares,
so the spans of one front-door call carry one identifier.

The state is process-wide on purpose: the profiler it follows is.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax

# the profiler's recording switch (private to JAX; tests/test_obs.py fails
# if it moves, rather than recording silently turning off)
from jax._src.lib import _profiler

recording = _profiler.TraceMe.is_enabled

LOG_CAP = 1 << 18

_log: List[Tuple] = []
_totals: Dict[str, int] = {}
_lock = threading.Lock()
_local = threading.local()
_batches = itertools.count()


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _Null()


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


def _append(entry: Tuple) -> None:
    if len(_log) < LOG_CAP:
        _log.append(entry)
    else:
        with _lock:
            _totals["obs.dropped"] = _totals.get("obs.dropped", 0) + 1


class _Span:
    __slots__ = ("name", "into", "meta", "ann", "t0", "batch")

    def __init__(self, name: str, into: Optional[dict], meta: dict) -> None:
        self.name, self.into, self.meta, self.ann = name, into, meta, None

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        if recording():
            stack = _stack()
            self.batch = stack[-1].batch if stack else next(_batches)
            self.ann = jax.profiler.TraceAnnotation(self.name, batch=self.batch, **self.meta)
            self.ann.__enter__()
            stack.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        if self.into is not None:
            self.into[self.name.rsplit(".", 1)[-1]] = (t1 - self.t0) * 1e-9
        if self.ann is not None:
            self.ann.__exit__(*exc)
            stack = _stack()
            stack.pop()
            _append((self.name, self.t0, t1, len(stack), self.batch))
        return False


def span(name: str, into: Optional[dict] = None, **meta):
    """Context manager over one stage, named ``layer.stage``.

    Records only while the profiler is on, with ``meta`` as the trace
    event's stats. ``into``: a dict that always receives the stage's
    seconds under the name's last part (``"build.packing"`` ->
    ``into["packing"]``), profiler or not."""
    if into is None and not recording():
        return _NULL
    return _Span(name, into, meta)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``; logged too while recording."""
    with _lock:
        _totals[name] = _totals.get(name, 0) + n
    if recording():
        _append((name, time.perf_counter_ns(), n))


def log() -> List[Tuple]:
    """The recorded entries in order: spans ``(name, t0_ns, t1_ns, depth,
    batch)`` as they close, counter events ``(name, t_ns, n)``."""
    return list(_log)


def totals() -> Dict[str, int]:
    """Every counter's total since the process started (or ``reset``)."""
    with _lock:
        return dict(_totals)


def reset() -> None:
    """Forget the log and the totals."""
    with _lock:
        _log.clear()
        _totals.clear()
