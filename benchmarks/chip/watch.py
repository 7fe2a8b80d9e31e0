"""Where a window's host stalls sit.

Two records, both kept only while the window runs:

* every Python garbage collection, with its generation, its start on the
  window's clock and its length (``gc.callbacks``);
* a watchdog (``faulthandler.dump_traceback_later``), armed at the start of
  each busy pass of the loop and disarmed at its end, that writes every
  thread's stack to ``dump_path`` when one pass runs longer than
  ``stall_s``: the stack of a stall, taken while it lasts.

Arming and disarming cost microseconds per pass; a dump is written only in
a stall.
"""
from __future__ import annotations

import faulthandler
import gc
import time
from pathlib import Path
from typing import Dict, List, Tuple

STALL_S = 1.0  # a busy pass longer than this is a stall


class Watch:
    def __init__(self, dump_path: Path, stall_s: float = STALL_S) -> None:
        self.dump_path, self.stall_s = Path(dump_path), stall_s
        self.collections: List[Tuple[int, float, float]] = []  # (generation, start s, seconds)
        self._t_open = self._t_gc = 0.0
        self._file = None

    def __enter__(self) -> "Watch":
        self.dump_path.parent.mkdir(parents=True, exist_ok=True)
        self._file = open(self.dump_path, "w")
        self._t_open = time.perf_counter()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        faulthandler.cancel_dump_traceback_later()
        gc.callbacks.remove(self._on_gc)
        self._file.close()
        if self.dump_path.stat().st_size == 0:
            self.dump_path.unlink()

    def _on_gc(self, phase: str, info: Dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t_gc = now
        else:
            self.collections.append((info["generation"], self._t_gc - self._t_open, now - self._t_gc))

    def arm(self) -> None:
        faulthandler.dump_traceback_later(self.stall_s, file=self._file)

    def disarm(self) -> None:
        faulthandler.cancel_dump_traceback_later()

    def gc_summary(self) -> Dict:
        longest = max(self.collections, key=lambda c: c[2], default=(-1, 0.0, 0.0))
        return dict(
            collections=len(self.collections),
            total_ms=f"{sum(c[2] for c in self.collections) * 1e3:.3f}",
            longest_ms=f"{longest[2] * 1e3:.3f}", longest_generation=longest[0],
            longest_at_s=f"{longest[1]:.3f}",
        )

    def stalls(self, calls) -> List[Dict]:
        """Each call, and each stretch of loop between two calls, that took
        ``stall_s`` or longer, with the collections that ran inside it."""
        spans = [(name, t0, t1) for name, t0, t1, _ in calls]
        spans += [("between_calls", a[2], b[1]) for a, b in zip(calls, calls[1:])]
        out = []
        for name, t0, t1 in sorted(spans, key=lambda s: s[1]):
            if t1 - t0 >= self.stall_s:
                gc_ms = sum(d for _, s, d in self.collections if t0 <= s < t1) * 1e3
                out.append(dict(span=name, at_s=f"{t0:.3f}", ms=f"{(t1 - t0) * 1e3:.3f}",
                                gc_ms=f"{gc_ms:.3f}"))
        return out

    def dumps(self) -> str:
        return self.dump_path.read_text() if self.dump_path.is_file() else ""
