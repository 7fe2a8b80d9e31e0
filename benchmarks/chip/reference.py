"""The plain reference: what every answer of a run must say.

Straightforward numpy over the whole object history, independent of the
code under test. It imports nothing of the program and takes nothing the
program made: object ids follow the documented contract of the front door
(base objects ``0..n-1``, inserts ``n, n+1, ...`` in arrival order), and
the harness checks that contract too.

Semantics (WISK paper sec. 3, appendix A; the configuration's
``guarantees``):

* SKR: every live object inside the closed query rectangle that shares at
  least one keyword with the query;
* Boolean kNN: the k live keyword-matching objects nearest to the point,
  ascending by (squared distance, id); fewer when fewer match;
* geofences: an insert inside a standing closed square that shares a
  keyword with it is notified once, by the drain that follows the insert;
* visibility: a query sees every insert and delete acknowledged before it
  was called, and none after.

``World`` holds the objects with the update step at which each appeared
and vanished: a call made after ``step`` updates sees object ``i`` when
``born[i] < step <= died[i]``. ``geometry`` rounds every coordinate the
reference computes with; the control (``control.py``) uses it to answer in
bfloat16.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

NEVER = np.iinfo(np.int64).max
# Two answers to one kNN query agree when their sorted squared distances
# agree to this relative gap: the program ranks by float32 distances, so
# objects whose true distances lie within float32 rounding may trade places.
# PERF.md gives the readings this limit sits between.
KNN_GAP_LIMIT = 1e-5


def rounded(x: np.ndarray, geometry: str) -> np.ndarray:
    """``x`` as float32 after rounding to ``geometry`` ("f32" or "bf16")."""
    x = np.asarray(x, np.float32)
    if geometry == "f32":
        return x
    if geometry == "bf16":
        import ml_dtypes

        return x.astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown geometry {geometry!r}")


class World:
    """Every object a run holds: the base collection, then each insert."""

    def __init__(self, locs: np.ndarray, kw_ids: np.ndarray, geometry: str = "f32") -> None:
        self.geometry = geometry
        self.n_base = int(locs.shape[0])
        self._locs = [np.asarray(locs, np.float32)]
        self._kw = [np.asarray(kw_ids, np.int32)]
        self._born = [np.full(self.n_base, -1, np.int64)]
        self._died: Dict[int, int] = {}
        self.n = self.n_base
        self._index = None

    def insert(self, locs: np.ndarray, kw_ids: np.ndarray, step: int) -> np.ndarray:
        locs = np.asarray(locs, np.float32).reshape(-1, 2)
        kw = np.asarray(kw_ids, np.int32).reshape(locs.shape[0], -1)
        width = self._kw[0].shape[1]
        if kw.shape[1] < width:
            kw = np.pad(kw, ((0, 0), (0, width - kw.shape[1])), constant_values=-1)
        ids = np.arange(self.n, self.n + locs.shape[0], dtype=np.int64)
        self._locs.append(locs)
        self._kw.append(kw[:, :width])
        self._born.append(np.full(locs.shape[0], step, np.int64))
        self.n += locs.shape[0]
        self._index = None
        return ids

    def delete(self, oid: int, step: int) -> int:
        """Mark ``oid`` gone from ``step`` on; 1 if it was live, else 0."""
        oid = int(oid)
        if oid < 0 or oid >= self.n or oid in self._died:
            return 0
        self._died[oid] = step
        self._index = None
        return 1

    # ---------------------------------------------------------------- index
    def _build(self) -> None:
        self.true_locs = np.concatenate(self._locs)
        self.locs = rounded(self.true_locs, self.geometry)
        self.kw = np.concatenate(self._kw)
        self.born = np.concatenate(self._born)
        self.died = np.full(self.n, NEVER, np.int64)
        if self._died:
            ids = np.fromiter(self._died.keys(), np.int64)
            self.died[ids] = np.fromiter(self._died.values(), np.int64)
        self.xorder = np.argsort(self.locs[:, 0], kind="stable")
        self.xs = self.locs[self.xorder, 0]
        self._index = True

    def ready(self) -> "World":
        if self._index is None:
            self._build()
        return self

    def _slab(self, xlo: float, xhi: float) -> np.ndarray:
        lo = np.searchsorted(self.xs, np.float32(xlo), side="left")
        hi = np.searchsorted(self.xs, np.float32(xhi), side="right")
        return self.xorder[lo:hi]

    def _keep(self, idx: np.ndarray, q_kw: np.ndarray, step: int) -> np.ndarray:
        q = q_kw[q_kw >= 0]
        if q.size == 0 or idx.size == 0:
            return idx[:0]
        hit = np.isin(self.kw[idx], q).any(axis=1)
        live = (self.born[idx] < step) & (self.died[idx] >= step)
        return idx[hit & live]

    # -------------------------------------------------------------- answers
    def skr(self, rect: np.ndarray, q_kw: np.ndarray, step: int) -> np.ndarray:
        """Sorted ids of the SKR answer."""
        self.ready()
        r = rounded(rect, self.geometry)
        idx = self._slab(r[0], r[2])
        y = self.locs[idx, 1]
        idx = idx[(y >= r[1]) & (y <= r[3])]
        return np.sort(self._keep(idx, q_kw, step))

    def dist2(self, ids: np.ndarray, point: np.ndarray) -> np.ndarray:
        """Squared distances in float64 of the objects' true coordinates."""
        self.ready()
        p = np.asarray(point, np.float64)
        xy = self.true_locs[ids].astype(np.float64)
        return (xy[:, 0] - p[0]) ** 2 + (xy[:, 1] - p[1]) ** 2

    def knn(self, point: np.ndarray, q_kw: np.ndarray, step: int, k: int) -> np.ndarray:
        """Ids of the k nearest live matching objects, ascending by
        (squared distance, id), the distance taken in ``geometry``."""
        self.ready()
        p = rounded(point, self.geometry).astype(np.float64)
        r = 1.0 / 64
        while True:
            idx = self._slab(p[0] - r, p[0] + r)
            y = self.locs[idx, 1].astype(np.float64)
            idx = self._keep(idx[np.abs(y - p[1]) <= r], q_kw, step)
            xy = self.locs[idx].astype(np.float64)
            d2 = (xy[:, 0] - p[0]) ** 2 + (xy[:, 1] - p[1]) ** 2
            order = np.lexsort((idx, d2))
            if idx.size >= k and d2[order[k - 1]] <= r * r:
                return idx[order[:k]]
            if r >= 2.0:
                return idx[order[:k]]
            r *= 2

    def matches(self, oid: int, q_kw: np.ndarray, step: int) -> bool:
        """Is ``oid`` a live object sharing a keyword with ``q_kw``?"""
        self.ready()
        if oid < 0 or oid >= self.n:
            return False
        return self._keep(np.array([oid]), q_kw, step).size == 1


def geofence_hits(
    locs: np.ndarray, kw_ids: np.ndarray, rects: np.ndarray, kws: Sequence[np.ndarray],
    geometry: str = "f32",
) -> List[Tuple[int, int]]:
    """(row, geofence) pairs: the row's point lies in the closed square and
    shares a keyword with it."""
    locs = rounded(locs, geometry).reshape(-1, 2)
    rects = rounded(rects, geometry).reshape(-1, 4)
    out = []
    for i in range(locs.shape[0]):
        x, y = locs[i]
        row = kw_ids[i][kw_ids[i] >= 0]
        inside = (rects[:, 0] <= x) & (x <= rects[:, 2]) & (rects[:, 1] <= y) & (y <= rects[:, 3])
        for s in np.flatnonzero(inside):
            if np.isin(row, kws[s]).any():
                out.append((i, int(s)))
    return out


# ------------------------------------------------------------- comparison
class Verdict:
    """The numbers ``correct`` compares, each with its limit."""

    LIMITS = {
        "skr_wrong": 0,  # SKR answers whose id set differs from the reference
        "knn_invalid": 0,  # kNN answers with a dead, non-matching, repeated or missing id
        "knn_gap": KNN_GAP_LIMIT,  # widest relative gap of sorted kNN distances
        "notify_wrong": 0,  # geofence notices missing, extra, repeated or late
        "update_wrong": 0,  # inserts and deletes acknowledged other than the contract says
        "unanswered": 0,  # requests that never got an answer
    }

    def __init__(self, names: Optional[Sequence[str]] = None) -> None:
        self.values = {k: 0 for k in (names or self.LIMITS)}
        if "knn_gap" in self.values:
            self.values["knn_gap"] = 0.0

    def add(self, name: str, n) -> None:
        self.values[name] += n

    def widen(self, name: str, x: float) -> None:
        self.values[name] = max(self.values[name], float(x))

    @property
    def correct(self) -> bool:
        return all(v <= self.LIMITS[k] for k, v in self.values.items())

    def table(self) -> Dict[str, Dict[str, float]]:
        return {k: {"value": v, "limit": self.LIMITS[k]} for k, v in self.values.items()}


def knn_compare(world: World, point, q_kw, step: int, k: int, got: np.ndarray) -> Tuple[bool, float]:
    """``(valid, gap)`` of one kNN answer against the reference: valid when
    every id is a distinct live matching object and the count is right; gap
    is the widest relative difference between the answer's sorted squared
    distances and the reference's."""
    want = world.knn(point, q_kw, step, k)
    got = np.asarray(got, np.int64)
    got = got[got >= 0]
    valid = (
        got.size == want.size
        and np.unique(got).size == got.size
        and all(world.matches(int(o), q_kw, step) for o in got)
    )
    if not valid:
        return False, 0.0
    if got.size == 0:
        return True, 0.0
    dg = np.sort(world.dist2(got, point))
    dw = np.sort(world.dist2(want, point))
    gap = float(np.max(np.abs(dg - dw) / np.maximum(dw, 1e-30)))
    return True, gap
