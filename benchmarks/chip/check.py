"""The comparison that decides ``correct``: every answer of the run against
the plain reference, once the window has closed.

It replays the run's updates (the set-up's backlog, then the window's, in
the order they were applied) into a ``reference.World`` and holds every
answer to it:

* each acknowledged insert got the id the front door's contract gives it
  (base size plus arrival number), each delete of a live object reported
  one deletion, and every geofence got its own id;
* each insert's notifications, drained right after it, are exactly the
  geofences it falls in (none missing, extra or repeated);
* each SKR answer equals the reference's id set at the step it was served;
* each kNN answer holds distinct live matching objects, as many as the
  reference, at distances that agree with the reference's to ``knn_gap``;
* every request due in the window was answered.
"""
from __future__ import annotations

from collections import Counter

import numpy as np

from gen.traffic import DELETE, INSERT, KNN, SKR
from reference import Verdict, World, geofence_hits, knn_compare


def _note_errors(got: np.ndarray, want) -> int:
    g = Counter(map(tuple, np.asarray(got, np.int64).reshape(-1, 2).tolist()))
    w = Counter(want)
    return sum((g - w).values()) + sum((w - g).values())


def verify(objs, setup, plan, rec, k: int) -> Verdict:
    world = World(objs.locs, objs.kw_ids)
    v = Verdict()
    fences, fence_kws, fence_ids = setup.fence_rects, setup.fence_kws, setup.fence_ids
    if len(set(fence_ids)) != len(fence_ids):
        v.add("update_wrong", len(fence_ids) - len(set(fence_ids)))

    def insert(loc, kw, got_id, got_notes, step):
        want_id = int(world.insert(loc, kw, step)[0])
        v.add("update_wrong", int(got_id != want_id))
        hits = geofence_hits(np.asarray(loc).reshape(1, 2), np.asarray(kw).reshape(1, -1),
                             fences, fence_kws) if len(fence_ids) else []
        v.add("notify_wrong", _note_errors(got_notes, [(want_id, fence_ids[s]) for _, s in hits]))

    step = 0
    for u in setup.updates:
        if u[0] == "insert":
            insert(u[1], u[2], u[3], u[4], step)
        else:
            v.add("update_wrong", int(u[2] != world.delete(u[1], step)))
        step += 1
    upd = np.flatnonzero(np.isin(plan.kind, (INSERT, DELETE)))
    for j in upd:
        if np.isnan(rec.latency[j]):
            break  # updates apply in order: none after an unanswered one ran
        s = int(plan.slot[j])
        if rec.step[j] != step:
            v.add("update_wrong", 1)
        if plan.kind[j] == INSERT:
            insert(plan.ins_locs[s], plan.ins_kw[s], rec.ins_ids[s], rec.notes.get(s, []), step)
        else:
            v.add("update_wrong", int(rec.del_counts[s] != world.delete(plan.del_ids[s], step)))
        step += 1
    v.add("unanswered", int(np.isnan(rec.latency).sum()))

    world.ready()
    for j in np.flatnonzero(plan.kind == SKR):
        if np.isnan(rec.latency[j]):
            continue
        s = int(plan.slot[j])
        want = world.skr(plan.skr_rects[s], plan.skr_kw[s], int(rec.step[j]))
        got = np.asarray(rec.skr_ids[s], np.int64)
        v.add("skr_wrong", int(got.shape != want.shape or not np.array_equal(got, want)))
    for j in np.flatnonzero(plan.kind == KNN):
        if np.isnan(rec.latency[j]):
            continue
        s = int(plan.slot[j])
        valid, gap = knn_compare(world, plan.knn_points[s], plan.knn_kw[s], int(rec.step[j]), k,
                                 rec.knn_ids[s])
        v.add("knn_invalid", int(not valid))
        v.widen("knn_gap", gap)
    return v
