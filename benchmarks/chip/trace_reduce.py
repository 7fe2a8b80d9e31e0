"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Two stages, so the second can be checked on a small recorded trace:

1. ``read_xplane(path)`` -> ``Events``: the device's operation events and
   the benchmark's host spans, as plain ``(name, start_ns, end_ns)`` tuples
   on the trace's one clock.
2. ``reduce(events)`` -> ``Reduction``: inside the traced window (the
   benchmark's ``window`` span),

   * busy intervals: the union of the device operations' intervals, per
     chip; ``busy_s`` is their length averaged over the chips;
   * device time per program (``XLA Modules``), for the breakdown;
   * idle gaps (the window minus the busy intervals), each labelled by the
     benchmark span that covers most of it (``serve_skr``, ``serve_knn``,
     ``update``, ``idle``; ``other`` where none does).

Device planes are ``/device:TPU:<n>``; their operations are the events of
the line ``XLA Ops`` and the programs that hold them the events of ``XLA
Modules``, named ``<jitted function>(<fingerprint>)`` (a Pallas kernel's
program carries the kernel's function name, e.g.
``jit_fused_verify_prefetch_compact``). Host spans are the benchmark's
``TraceAnnotation``s, found by name on any ``/host:`` plane.
"""
from __future__ import annotations

import dataclasses
import glob
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

SPAN_NAMES = ("window", "serve_skr", "serve_knn", "update", "idle")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Events:
    ops: Dict[str, List[Event]]  # device plane name -> its operations
    spans: List[Event]  # the benchmark's host spans
    modules: Dict[str, List[Event]] = dataclasses.field(default_factory=dict)  # plane -> programs


def module_name(event_name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``: the program's name without its fingerprint."""
    return event_name.split("(", 1)[0]


def read_xplane(path) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.end_ns)) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events if e.name in SPAN_NAMES
                )
    return Events(ops, spans, modules)


def union(intervals: Sequence[Tuple[int, int]]) -> np.ndarray:
    """Merged, sorted (k, 2) array of the union of ``[start, end)``s."""
    if not len(intervals):
        return np.zeros((0, 2), np.int64)
    iv = np.asarray(sorted(intervals), np.int64)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def clip(iv: np.ndarray, t0: int, t1: int) -> np.ndarray:
    if iv.size == 0:
        return iv
    iv = np.stack([np.maximum(iv[:, 0], t0), np.minimum(iv[:, 1], t1)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def cover_upto(iv: np.ndarray, t) -> np.ndarray:
    """Nanoseconds before each time in ``t`` that the merged intervals
    ``iv`` cover (vectorised over ``t``)."""
    t = np.asarray(t, np.int64)
    if iv.size == 0:
        return np.zeros(t.shape, np.int64)
    starts, lens = iv[:, 0], iv[:, 1] - iv[:, 0]
    pref = np.concatenate([[0], np.cumsum(lens)])
    i = np.searchsorted(starts, t, side="right")
    j = np.maximum(i - 1, 0)
    part = np.where(i > 0, np.clip(t - starts[j], 0, lens[j]), 0)
    return pref[j] + part


def covered(iv: np.ndarray, t0, t1) -> np.ndarray:
    """Nanoseconds of each ``[t0, t1)`` that the merged intervals cover."""
    return cover_upto(iv, t1) - cover_upto(iv, t0)


@dataclasses.dataclass
class Reduction:
    window: Tuple[int, int]  # ns
    busy: Dict[str, np.ndarray]  # device plane -> merged busy intervals in the window
    ops: Dict[str, List[Event]]  # device plane -> its operations in the window
    spans: Dict[str, List[Tuple[int, int]]]  # span name -> intervals, ns
    gap_seconds: Dict[str, float]  # label -> idle seconds (averaged over chips)
    modules: Dict[str, List[Event]] = dataclasses.field(default_factory=dict)  # in the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return float(self.busy_in([self.window[0]], [self.window[1]])[0])

    def busy_in(self, t0, t1) -> np.ndarray:
        """Device-busy seconds inside each ``[t0, t1)``, averaged over chips."""
        if not self.busy:
            return np.zeros(len(t0))
        return np.mean([covered(iv, t0, t1) for iv in self.busy.values()], axis=0) * 1e-9

    def module_seconds(self) -> Dict[str, float]:
        """Device seconds per program name (fingerprint dropped), summed
        over the chips."""
        out: Dict[str, float] = {}
        t0, t1 = self.window
        for events in self.modules.values():
            for n, s, e in events:
                k = module_name(n)
                out[k] = out.get(k, 0.0) + (min(e, t1) - max(s, t0)) * 1e-9
        return out

    def module_time_in(self, prefix: str, t0, t1) -> np.ndarray:
        """Device seconds of the programs whose name starts with ``prefix``
        inside each ``[t0, t1)``, averaged over the chips."""
        per_chip = []
        for events in self.modules.values():
            iv = union([(s, e) for n, s, e in events if n.startswith(prefix)])
            per_chip.append(covered(iv, t0, t1))
        if not per_chip:
            return np.zeros(len(t0))
        return np.mean(per_chip, axis=0) * 1e-9

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The programs that took the most device time, and the idle time
        by what the host was doing."""
        mods = sorted(self.module_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in mods], "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(ev: Events) -> Reduction:
    windows = [(s, e) for n, s, e in ev.spans if n == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    t0, t1 = windows[0]
    spans: Dict[str, List[Tuple[int, int]]] = {}
    for n, s, e in ev.spans:
        if n != "window" and s < t1 and e > t0:
            spans.setdefault(n, []).append((s, e))
    busy, ops = {}, {}
    for plane, events in ev.ops.items():
        ops[plane] = [(n, s, e) for n, s, e in events if s < t1 and e > t0]
        busy[plane] = clip(union([(s, e) for _, s, e in ops[plane]]), t0, t1)
    modules = {p: [(n, s, e) for n, s, e in events if s < t1 and e > t0]
               for p, events in ev.modules.items()}
    # each idle gap goes to the span that covers most of it
    labels = sorted(spans)
    merged = [union(spans[n]) for n in labels]
    gap_s: Dict[str, float] = {}
    for iv in busy.values():
        edges = np.concatenate([[t0], iv.reshape(-1), [t1]]).reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        if not edges.size:
            continue
        length = (edges[:, 1] - edges[:, 0]) * 1e-9 / len(busy)
        if labels:
            cov = np.stack([covered(m, edges[:, 0], edges[:, 1]) for m in merged])
            who = np.where(cov.max(axis=0) > 0, cov.argmax(axis=0), -1)
        else:
            who = np.full(len(edges), -1)
        for i in np.unique(who):
            name = labels[i] if i >= 0 else "other"
            gap_s[name] = gap_s.get(name, 0.0) + float(length[who == i].sum())
    return Reduction((t0, t1), busy, ops, spans, gap_s, modules)


def reduce_dir(trace_dir: Path) -> Reduction:
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(read_xplane(paths[-1]))
