"""The one traffic generator: a traffic file's parameters plus a seed give
every operation a run sends, with its due time.

A traffic file (``traffic/<mix>.json``) holds only numbers and names, and
every one of these keys (``check`` refuses a file with a key missing or
unknown, so a misspelt key cannot silently run another mix):

* ``rate_per_s`` -- offered operations per second (all kinds together);
* ``mix`` -- the share of each kind: ``skr``, ``knn``, ``insert``, ``delete``;
* ``queries`` -- ``dist``, ``region_frac``, ``n_keywords`` of the SKR
  queries (kNN queries use the same draw, the rectangle's centre as point);
* ``knn_k``, ``max_batch``, ``max_leaves``;
* ``insert_jitter`` -- inserts are copies of random objects moved by a
  normal offset of this standard deviation, keeping their keywords;
* ``delete_inserted_share`` -- the share of deletes that hit an earlier
  insert (the rest hit objects of the base collection);
* ``geofences`` -- ``count``, ``half_side`` [lo, hi], ``max_keywords``;
* ``backlog_per_window_insert`` -- inserts preloaded in set-up, per insert
  the window will send;
* ``warmup_batches`` -- full batches of warm-up queries per kind;
* ``about`` -- one line of prose, read by no code.

The number of operations of each kind is fixed by the rate, the window and
the shares, so every seed sends the same amount of work. The arrivals are a
Poisson process conditioned on its count: one fixed set of gaps between
arrivals (drawn once from the rate and the window), which the seed puts in
its own order. The seed also draws the order of kinds and every payload.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from .workloads import make_queries, spatial_order

KINDS = ("skr", "knn", "insert", "delete")
SKR, KNN, INSERT, DELETE = range(4)
KEYS = {
    "about": None, "rate_per_s": None, "mix": set(KINDS),
    "queries": {"dist", "region_frac", "n_keywords"},
    "knn_k": None, "max_batch": None, "max_leaves": None,
    "insert_jitter": None, "delete_inserted_share": None,
    "geofences": {"count", "half_side", "max_keywords"},
    "backlog_per_window_insert": None, "warmup_batches": None,
}


def check(traffic: Dict, name: str) -> Dict:
    """``traffic`` itself, once it holds exactly the keys of ``KEYS``."""
    def differ(have, want, where):
        missing, unknown = sorted(want - set(have)), sorted(set(have) - want)
        if missing or unknown:
            raise ValueError(f"traffic {name!r}{where}: missing {missing}, unknown {unknown}")

    differ(traffic, set(KEYS), "")
    for key, sub in KEYS.items():
        if sub is not None:
            differ(traffic[key], sub, f" [{key!r}]")
    return traffic


def substream(seed: int, *path: int) -> int:
    """A 32-bit seed for one named stream under ``seed`` (any integer)."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), *path])
    return int(ss.generate_state(1)[0])


def split_counts(total: int, shares: Dict[str, float]) -> List[int]:
    """Whole counts per kind summing to ``total`` (largest remainders)."""
    raw = np.array([float(shares[k]) for k in KINDS]) * total
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: total - counts.sum()]:
        counts[i] += 1
    return [int(c) for c in counts]


@dataclasses.dataclass
class Objects:
    """A collection of POIs: locations, padded keyword ids, vocabulary."""

    locs: np.ndarray
    kw_ids: np.ndarray
    vocab: int

    @property
    def n(self) -> int:
        return int(self.locs.shape[0])


@dataclasses.dataclass
class Plan:
    """Every operation of a window, ordered by due time (seconds)."""

    due: np.ndarray  # (N,) f64 seconds after the window opens
    kind: np.ndarray  # (N,) int8 SKR / KNN / INSERT / DELETE
    slot: np.ndarray  # (N,) index into the payload arrays of that kind
    skr_rects: np.ndarray
    skr_kw: np.ndarray
    knn_points: np.ndarray
    knn_kw: np.ndarray
    ins_locs: np.ndarray
    ins_kw: np.ndarray
    del_ids: np.ndarray  # (n_delete,) object ids, chosen in arrival order

    @property
    def n(self) -> int:
        return int(self.due.size)


def centres(rects: np.ndarray) -> np.ndarray:
    return np.stack(
        [(rects[:, 0] + rects[:, 2]) / 2, (rects[:, 1] + rects[:, 3]) / 2], 1
    ).astype(np.float32)


def queries(objs: Objects, traffic: Dict, m: int, seed: int, order=None):
    q = traffic["queries"]
    return make_queries(
        objs.locs, objs.kw_ids, objs.vocab, m, dist=q["dist"], region_frac=q["region_frac"],
        n_keywords=q["n_keywords"], seed=seed, order=order,
    )


def inserts(objs: Objects, traffic: Dict, m: int, seed: int):
    """``m`` jittered copies of random base objects, keeping their keywords."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, objs.n, size=m)
    jitter = float(traffic["insert_jitter"])
    locs = np.clip(objs.locs[src] + rng.normal(0, jitter, (m, 2)), 0, 1).astype(np.float32)
    return locs, objs.kw_ids[src].copy()


def geofences(objs: Objects, traffic: Dict, seed: int):
    """Standing squares around random objects, each with 1..max_keywords of
    a random object's keywords: ``(rects (S, 4) f32, [kw id arrays])``."""
    g = traffic["geofences"]
    count = int(g["count"])
    rng = np.random.default_rng(seed)
    lo, hi = g["half_side"]
    max_kw = int(g["max_keywords"])
    rects, kws = [], []
    for s in range(count):
        c = objs.locs[rng.integers(objs.n)]
        half = rng.uniform(lo, hi)
        rects.append(np.clip([c[0] - half, c[1] - half, c[0] + half, c[1] + half], 0, 1))
        pick = objs.kw_ids[rng.integers(objs.n)]
        kws.append(pick[pick >= 0][: 1 + s % max_kw].astype(np.int32))
    return np.asarray(rects, np.float32).reshape(-1, 4), kws


def window_counts(traffic: Dict, seconds: float) -> List[int]:
    total = int(round(float(traffic["rate_per_s"]) * seconds))
    return split_counts(total, traffic["mix"])


def backlog_size(traffic: Dict, seconds: float) -> int:
    per = float(traffic["backlog_per_window_insert"])
    return int(round(per * window_counts(traffic, seconds)[INSERT]))


def make_plan(objs: Objects, traffic: Dict, seconds: float, seed: int,
              next_id: int = None, live_inserted=(), deleted=()) -> Plan:
    """The window's operations from ``seed``. Set-up may have inserted and
    deleted already: ``next_id`` is the id the next insert gets,
    ``live_inserted`` the ids of earlier inserts still live and ``deleted``
    the ids already deleted; window deletes pick among what is live."""
    counts = window_counts(traffic, seconds)
    total = sum(counts)
    # the spacings of sorted uniform arrivals are exchangeable, so a seed's
    # permutation of one fixed set of them is again a conditioned Poisson draw
    fixed = np.sort(np.random.default_rng(substream(total, 0)).uniform(0.0, seconds, size=total))
    rng = np.random.default_rng(substream(seed, 1))
    due = np.cumsum(rng.permutation(np.diff(fixed, prepend=0.0)))
    kind = np.repeat(np.arange(4, dtype=np.int8), counts)
    rng.shuffle(kind)
    slot = np.zeros(total, np.int64)
    for k in range(4):
        sel = kind == k
        slot[sel] = np.arange(int(sel.sum()))

    order = spatial_order(objs.locs)
    skr_rects, skr_kw = queries(objs, traffic, counts[SKR], substream(seed, 2), order)
    knn_rects, knn_kw = queries(objs, traffic, counts[KNN], substream(seed, 3), order)
    ins_locs, ins_kw = inserts(objs, traffic, counts[INSERT], substream(seed, 4))

    # deletes, in arrival order: a share hits live earlier inserts (ids after
    # the base collection, in insert order), the rest live base objects
    drng = np.random.default_rng(substream(seed, 5))
    share = float(traffic["delete_inserted_share"])
    live_ins = list(live_inserted)
    next_ins = objs.n if next_id is None else int(next_id)
    dead = set(deleted)
    del_ids = np.zeros(counts[DELETE], np.int64)
    d = 0
    for k in kind:
        if k == INSERT:
            live_ins.append(next_ins)
            next_ins += 1
        elif k == DELETE:
            if live_ins and drng.uniform() < share:
                oid = live_ins.pop(int(drng.integers(len(live_ins))))
            else:
                oid = int(drng.integers(objs.n))
                while oid in dead:
                    oid = int(drng.integers(objs.n))
            dead.add(oid)
            del_ids[d] = oid
            d += 1
    return Plan(
        due=due, kind=kind, slot=slot,
        skr_rects=skr_rects, skr_kw=skr_kw,
        knn_points=centres(knn_rects), knn_kw=knn_kw,
        ins_locs=ins_locs, ins_kw=ins_kw, del_ids=del_ids,
    )
