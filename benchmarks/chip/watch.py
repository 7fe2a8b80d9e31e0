"""Where a window's host stalls sit.

Two records, both kept only while the window runs:

* every Python garbage collection, with its generation, its start on the
  window's clock and its length (``gc.callbacks``);
* the stacks of a stall: the loop arms the watch at the start of each busy
  pass and disarms it at the end. Once an armed pass has run ``stall_s``, a
  sampler thread reads the loop thread's stack every ``SAMPLE_S`` until the
  pass ends (``sys._current_frames``, under the interpreter lock, so it
  never reads frames that the loop is changing), noting when each sample
  was due and when it was taken.

A sample taken far later than it was due means that the sampler could not
run: the whole process was stopped, or the loop thread sat in a native call
that held the interpreter lock. A sample on time while the stack stays the
same means slow Python or a native call that released the lock.

Arming and disarming write one float each. Outside a stall the sampler
wakes at most ``1 / IDLE_S`` times a second, or once ``stall_s`` after an
arm, and holds the lock only for a few attribute reads.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

STALL_S = 1.0  # a busy pass longer than this is a stall
SAMPLE_S = 0.1  # seconds between two samples of a stalled pass
IDLE_S = 0.25  # longest sleep of the sampler while no pass is armed
MAX_PER_STALL = 100  # samples of one stall: ten seconds of it
MAX_SAMPLES = 1000  # samples of a window, over all its stalls


@dataclasses.dataclass
class Stack:
    """Consecutive samples of one stalled pass that found the same stack.
    Times are seconds on the window's clock; ``late_s`` is the most that one
    of them was taken after it was due."""

    pass_at_s: float
    at_s: float
    due_s: float
    last_at_s: float
    late_s: float
    count: int
    stack: str


class Watch:
    def __init__(self, stall_s: float = STALL_S) -> None:
        self.stall_s = stall_s
        self.collections: List[Tuple[int, float, float]] = []  # (generation, start s, seconds)
        self.stacks: List[Stack] = []
        self.samples = 0  # samples taken, folded into ``stacks``
        self.dropped = 0  # stalled passes, or their tails, left unsampled by the caps
        self._t_open = self._t_gc = 0.0
        self._armed_at: Optional[float] = None  # perf_counter at the running pass's start
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._ident = 0

    def __enter__(self) -> "Watch":
        self._ident = threading.get_ident()
        self._t_open = time.perf_counter()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._sample, name="stall-sampler", daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._armed_at = None
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t_gc = now
        else:
            self.collections.append((info["generation"], self._t_gc - self._t_open, now - self._t_gc))

    def arm(self) -> None:
        self._armed_at = time.perf_counter()

    def disarm(self) -> None:
        self._armed_at = None

    def _sample(self) -> None:
        clock = time.perf_counter
        while not self._stop.is_set():
            t0 = self._armed_at
            if t0 is None:  # an arm is seen within stall_s, so no stall goes unsampled
                self._stop.wait(min(IDLE_S, self.stall_s))
                continue
            due = t0 + self.stall_s
            if clock() < due:
                self._stop.wait(due - clock())
                continue
            taken = 0
            while self._armed_at == t0 and not self._stop.is_set():
                if taken == MAX_PER_STALL or self.samples == MAX_SAMPLES:
                    self.dropped += 1
                    while self._armed_at == t0 and not self._stop.wait(IDLE_S):
                        pass
                    break
                frame = sys._current_frames().get(self._ident)
                at = clock()
                if frame is None or self._armed_at != t0:
                    break
                self._keep(t0, due, at, "".join(traceback.format_stack(frame)))
                del frame
                taken += 1
                due += SAMPLE_S
                self._stop.wait(max(due - clock(), 0.0))

    def _keep(self, t0: float, due: float, at: float, stack: str) -> None:
        w = self._t_open
        last = self.stacks[-1] if self.stacks else None
        self.samples += 1
        if last is not None and last.pass_at_s == t0 - w and last.stack == stack:
            last.count += 1
            last.last_at_s = at - w
            last.late_s = max(last.late_s, at - due)
        else:
            self.stacks.append(Stack(t0 - w, at - w, due - w, at - w, at - due, 1, stack))

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

    def stack_report(self, shown: int) -> str:
        """The sampled stacks, oldest first, cut at ``shown`` characters; ""
        where no pass stalled."""
        if not self.stacks:
            return ""
        late = max(s.late_s for s in self.stacks)
        lines = [f"stall_stacks: samples={self.samples} entries={len(self.stacks)} "
                 f"dropped={self.dropped} every_ms={SAMPLE_S * 1e3:.0f} "
                 f"late_ms_max={late * 1e3:.3f}, first {shown} characters"]
        for s in self.stacks:
            lines.append(f"sample: pass_at_s={s.pass_at_s:.3f} at_s={s.at_s:.3f} "
                         f"due_s={s.due_s:.3f} last_at_s={s.last_at_s:.3f} "
                         f"late_ms_max={s.late_s * 1e3:.3f} count={s.count}")
            lines.append(s.stack.rstrip("\n"))
        return "\n".join(lines)[:shown]
