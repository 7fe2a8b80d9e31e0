"""The program's own spans and counters, put on the trace's clock.

The program records spans and counter events in memory while the profiler
is on (``src/repro/obs.py``), on ``time.perf_counter_ns()``; the trace's
events sit on another clock, a fixed offset away. Each of the loop's calls
holds exactly one front-door call of the program, so the k-th loop span
``serve_skr`` pairs with the k-th program span ``wisk.serve`` (and
``serve_knn`` with ``wisk.serve_knn``, ``update`` with ``wisk.insert`` or
``wisk.delete``). The offset is the median of ``trace start - program
start`` over every pair: only a few Python calls run between the two
starts, while between the ends the program closes its own trace annotation,
which on a v5e host took from microseconds to milliseconds. Nothing is
returned when the counts of a pair differ or the offsets spread (quartile
distance) by more than ``MAX_SPREAD_NS``: the two records would then not be
of the same calls.

``load(run)`` gives the program's spans and counter events on the trace's
clock, or None where the program keeps no record (a program without
``repro.obs``, or an untraced run). A stage's host time is its span's
length minus the device-busy time inside it (``run.trace.busy_in``): the
device's idle time, put down to that stage.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import numpy as np

PAIRS = (("serve_skr", ("wisk.serve",)), ("serve_knn", ("wisk.serve_knn",)),
         ("update", ("wisk.insert", "wisk.delete")))
MAX_SPREAD_NS = 100_000  # 0.1 ms


@dataclasses.dataclass
class ProgramTrace:
    offset_ns: float  # trace clock minus the program's clock
    spread_ns: float  # quartile distance of the paired offsets
    spans: Dict[str, np.ndarray]  # name -> (n, 4) int64: start, end (trace clock), depth, batch
    counts: Dict[str, np.ndarray]  # name -> (n, 2) int64: time (trace clock), amount


def _program_log():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.log()


def align(loop_spans: Dict[str, Sequence], entries: Sequence) -> Optional[ProgramTrace]:
    """The program's ``entries`` (``repro.obs.log()``) on the clock of
    ``loop_spans`` (loop span name -> ``(start_ns, end_ns)``s), or None."""
    by_name: Dict[str, list] = {}
    counted: Dict[str, list] = {}
    for e in entries:
        if len(e) == 5:
            by_name.setdefault(e[0], []).append(e[1:])
        else:
            counted.setdefault(e[0], []).append(e[1:])
    offsets = []
    for loop_name, names in PAIRS:
        loop = sorted(s for s, _ in loop_spans.get(loop_name, []))
        prog = sorted(e[0] for n in names for e in by_name.get(n, []) if e[2] == 0)
        if len(loop) != len(prog):
            return None
        offsets.extend(np.subtract(loop, prog, dtype=np.float64))
    if not offsets:
        return None
    q1, med, q3 = np.percentile(offsets, [25, 50, 75])
    if q3 - q1 > MAX_SPREAD_NS:
        return None
    shift = np.array([med, med, 0, 0])
    spans = {n: np.rint(np.asarray(v, np.float64) + shift).astype(np.int64)
             for n, v in by_name.items()}
    counts = {n: np.rint(np.asarray(v, np.float64) + [med, 0]).astype(np.int64)
              for n, v in counted.items()}
    return ProgramTrace(float(med), float(q3 - q1), spans, counts)


_last = (None, None)


def load(run) -> Optional[ProgramTrace]:
    """``align`` of the program's log against ``run``'s loop spans."""
    global _last
    if _last[0] is run:
        return _last[1]
    got = None
    if run.trace is not None:
        entries = _program_log()
        if entries:
            got = align(run.trace.spans, entries)
    _last = (run, got)
    return got


def _in_window(run, t):
    t0, t1 = run.trace.window
    return (t >= t0) & (t < t1)


def host_ms(run, top: str, stages: Sequence[str] = ()) -> Optional[float]:
    """Median over the program's ``top`` spans of the host time in its
    ``stages`` spans (summed; the ``top`` span itself where none are
    named), in ms."""
    pt = load(run)
    if pt is None or top not in pt.spans:
        return None
    calls = pt.spans[top]
    calls = calls[_in_window(run, calls[:, 0])]
    if not calls.size:
        return None
    parts = [pt.spans[n] for n in stages if n in pt.spans] if stages else [calls]
    if not parts:
        return None
    iv = np.concatenate(parts)
    host = (iv[:, 1] - iv[:, 0]) * 1e-9 - run.trace.busy_in(iv[:, 0], iv[:, 1])
    slot = {int(b): i for i, b in enumerate(calls[:, 3])}
    per_call = np.zeros(len(calls))
    for b, h in zip(iv[:, 3], host):
        i = slot.get(int(b))
        if i is not None:
            per_call[i] += h
    return float(np.median(per_call) * 1e3)


def span_ms(run, name: str) -> Optional[float]:
    """Median length of the program's spans named ``name``, in ms."""
    pt = load(run)
    if pt is None or name not in pt.spans:
        return None
    iv = pt.spans[name]
    iv = iv[_in_window(run, iv[:, 0])]
    return float(np.median(iv[:, 1] - iv[:, 0]) * 1e-6) if iv.size else None


def calls(run, name: str) -> int:
    """The program's spans named ``name`` that start in the window."""
    pt = load(run)
    if pt is None or name not in pt.spans:
        return 0
    return int(_in_window(run, pt.spans[name][:, 0]).sum())


def total(run, name: str) -> Optional[int]:
    """The counter ``name`` summed over its events in the window."""
    pt = load(run)
    if pt is None or name not in pt.counts:
        return None
    ev = pt.counts[name]
    return int(ev[_in_window(run, ev[:, 0]), 1].sum())
