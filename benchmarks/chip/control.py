"""The control: the plain reference put in the program's place, answering
with one guarantee broken.

The configurations state exact answers in float32 geometry. The control
keeps every coordinate it compares (objects, rectangles, kNN points,
geofences) in bfloat16, the step below float32 that a bandwidth-saving
change to the index planes would take. Run with ``--control bf16``; its
runs must come out not correct (see PERF.md for its readings).
"""
from __future__ import annotations

from typing import List

import numpy as np

from gen.traffic import Objects
from reference import World, geofence_hits


class ControlServer:
    """Answers SKR, kNN, updates and geofences from ``reference.World`` in
    the given ``geometry``."""

    def __init__(self, objs: Objects, geometry: str = "bf16") -> None:
        self.world = World(objs.locs, objs.kw_ids, geometry)
        self.geometry = geometry
        self.step = 0
        self.fence_rects: List[np.ndarray] = []
        self.fence_kws: List[np.ndarray] = []
        self.pending: List = []

    @staticmethod
    def bitmaps(kw_ids: np.ndarray) -> np.ndarray:
        return np.asarray(kw_ids, np.int32)  # the control reads keyword ids

    def serve_skr(self, rects, kw):
        return {"ids": [self.world.skr(r, k, self.step) for r, k in zip(rects, kw)],
                "verified": np.zeros(len(rects), np.int64)}

    @staticmethod
    def skr_rows(out, m: int):
        return out["ids"][:m]

    def serve_knn(self, points, kw, k: int):
        ids = np.full((len(points), k), -1, np.int64)
        for i, (p, q) in enumerate(zip(points, kw)):
            got = self.world.knn(p, q, self.step, k)
            ids[i, : got.size] = got
        return {"ids": ids, "verified": np.zeros(len(points), np.int64)}

    def insert(self, locs, kw):
        ids = self.world.insert(locs, kw, self.step)
        if self.fence_rects:
            hits = geofence_hits(locs, np.asarray(kw), np.stack(self.fence_rects),
                                 self.fence_kws, self.geometry)
            self.pending.extend((int(ids[i]), s) for i, s in hits)
        self.step += 1
        return ids

    def delete(self, ids):
        n = sum(self.world.delete(int(i), self.step) for i in np.atleast_1d(ids))
        self.step += 1
        return n

    def drain(self):
        out = np.asarray(sorted(self.pending), np.int64).reshape(-1, 2)
        self.pending = []
        return out

    def subscribe(self, rect, kw) -> int:
        self.fence_rects.append(np.asarray(rect, np.float32))
        self.fence_kws.append(np.asarray(kw, np.int32))
        return len(self.fence_rects) - 1

    def describe(self):
        return {"control": self.geometry}
