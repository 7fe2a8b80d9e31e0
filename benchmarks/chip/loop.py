"""The open-loop traffic loop: the caller's event loop around the server.

One host thread. Each operation is due at its planned time, whether or not
the server has kept up. Every pass of the loop:

1. applies each due update in arrival order, one call per update (an
   insert is followed by a drain of the geofence notifications, so its
   latency covers the delivery of its alerts);
2. serves the oldest due SKR queries, at most ``max_batch``, in one call;
3. serves the oldest due kNN queries, at most ``max_batch``, in one call;
4. sleeps until the next operation is due when nothing is waiting.

A ``watch`` (``watch.Watch``) is armed for each busy pass (1-3) and
disarmed after it, so a pass that stalls leaves its stack behind.

A request's latency runs from its due time until its answer is in host
memory (for an update: until its call, and for an insert its drain, has
returned). Requests due in the window that have not been answered a
grace period (``GRACE_S``) after it closes are counted as unanswered.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List

import numpy as np

from gen.traffic import DELETE, INSERT, KNN, SKR, Plan

GRACE_S = 60.0  # seconds after the window that late answers are waited for


@dataclasses.dataclass
class Records:
    """What the window produced, per planned operation and per call."""

    latency: np.ndarray  # (N,) seconds from due to answered; nan = unanswered
    started: np.ndarray  # (N,) seconds after the window opened: its call began
    done: np.ndarray  # (N,) seconds after the window opened: answered
    step: np.ndarray  # (N,) updates applied before the request's call
    skr_ids: List  # per SKR slot: sorted answer ids
    knn_ids: List  # per kNN slot: answer ids in the order returned
    ins_ids: np.ndarray  # per insert slot: the id the program acknowledged
    del_counts: np.ndarray  # per delete slot: objects the delete reported
    notes: Dict[int, np.ndarray]  # insert slot -> (pairs) drained after it
    calls: List  # (name, t0, t1, n requests) per call, window clock
    wake_late: List[float]  # how late the loop woke for a due operation
    counters: Dict[str, List]  # per-query engine counters, by name
    window_s: float = 0.0


def drive(server, plan: Plan, seconds: float, step0: int, max_batch: int, knn_k: int,
          skr_bms: np.ndarray, knn_bms: np.ndarray,
          annotate: Callable = None, watch=None) -> Records:
    ann = annotate or (lambda name: contextlib.nullcontext())
    arm = watch.arm if watch is not None else (lambda: None)
    disarm = watch.disarm if watch is not None else (lambda: None)
    N = plan.n
    n_of = [int((plan.kind == k).sum()) for k in range(4)]
    rec = Records(
        latency=np.full(N, np.nan), started=np.full(N, np.nan), done=np.full(N, np.nan),
        step=np.zeros(N, np.int64),
        skr_ids=[None] * n_of[SKR], knn_ids=[None] * n_of[KNN],
        ins_ids=np.full(n_of[INSERT], -1, np.int64), del_counts=np.full(n_of[DELETE], -1, np.int64),
        notes={}, calls=[], wake_late=[], counters={"skr_verified": []},
    )
    updates, skr, knn = deque(), deque(), deque()
    queues = {SKR: skr, KNN: knn, INSERT: updates, DELETE: updates}
    clock = time.perf_counter
    step = step0
    nxt = 0
    t_open = clock()

    def finish(ops, t_call, t_done, name):
        rec.calls.append((name, t_call, t_done, len(ops)))
        for j in ops:
            rec.started[j] = t_call
            rec.done[j] = t_done
            rec.latency[j] = t_done - plan.due[j]

    while True:
        now = clock() - t_open
        while nxt < N and plan.due[nxt] <= now:
            queues[int(plan.kind[nxt])].append(nxt)
            nxt += 1
        if not (updates or skr or knn):
            if nxt >= N or now > seconds + GRACE_S:
                break
            with ann("idle"):
                time.sleep(max(plan.due[nxt] - now, 0.0))
            rec.wake_late.append(clock() - t_open - plan.due[nxt])
            continue
        if now > seconds + GRACE_S:
            break
        arm()
        while updates:
            j = updates.popleft()
            s = int(plan.slot[j])
            t_call = clock() - t_open
            if plan.kind[j] == INSERT:
                with ann("update"):
                    ids = server.insert(plan.ins_locs[s : s + 1], plan.ins_kw[s : s + 1])
                    pairs = server.drain()
                rec.ins_ids[s] = int(np.asarray(ids).reshape(-1)[0])
                rec.notes[s] = np.asarray(pairs, np.int64).reshape(-1, 2)
            else:
                with ann("update"):
                    rec.del_counts[s] = int(server.delete(plan.del_ids[s : s + 1]))
            rec.step[j] = step
            step += 1
            finish([j], t_call, clock() - t_open, "update")
        if skr:
            ops = [skr.popleft() for _ in range(min(max_batch, len(skr)))]
            rows = plan.slot[ops]
            t_call = clock() - t_open
            with ann("serve_skr"):
                out = server.serve_skr(plan.skr_rects[rows], skr_bms[rows])
            finish(ops, t_call, clock() - t_open, "serve_skr")
            rec.step[ops] = step
            for q, row in zip(rows, server.skr_rows(out, len(ops))):
                rec.skr_ids[q] = row
            rec.counters["skr_verified"].extend(np.asarray(out["verified"]).tolist())
        if knn:
            ops = [knn.popleft() for _ in range(min(max_batch, len(knn)))]
            rows = plan.slot[ops]
            t_call = clock() - t_open
            with ann("serve_knn"):
                out = server.serve_knn(plan.knn_points[rows], knn_bms[rows], knn_k)
            finish(ops, t_call, clock() - t_open, "serve_knn")
            rec.step[ops] = step
            ids = np.asarray(out["ids"])
            for i, q in enumerate(rows):
                rec.knn_ids[q] = ids[i][ids[i] >= 0]
        disarm()
    rec.window_s = seconds
    return rec


def skr_rows(ids: np.ndarray, m: int) -> List[np.ndarray]:
    """The sorted answer ids of each of the first ``m`` rows of a dense id
    plane (``-1`` fill)."""
    ids = np.asarray(ids)[:m]
    keep = ids >= 0
    vals = ids[keep]
    return [np.sort(v) for v in np.split(vals, np.cumsum(keep.sum(axis=1))[:-1])]
