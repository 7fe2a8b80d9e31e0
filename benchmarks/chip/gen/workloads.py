"""SKR query generation (the benchmark's own copy of ``data/workloads.py``).

A query takes a centre object from the collection, a square of the given
area fraction around it, and the centre object's keywords topped up with
Zipf draws from the collection's keyword frequencies (paper sec. 7.2):

* UNI -- centres drawn uniformly from the collection;
* LAP -- centre rank from a Laplace distribution (mu = n/2, b = n/10) over
  the collection in spatial (Z-curve) order;
* GAU -- Gaussian (mu = n/2, sigma = 100), heavily skewed;
* MIX -- half UNI, half LAP (the paper's default).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def _centers(dist: str, n: int, m: int, rng: np.random.Generator) -> np.ndarray:
    if dist == "UNI":
        return rng.integers(0, n, size=m)
    if dist == "LAP":
        idx = rng.laplace(loc=n / 2, scale=n / 10, size=m)
    elif dist == "GAU":
        idx = rng.normal(loc=n / 2, scale=100.0, size=m)
    elif dist == "MIX":
        half = m // 2
        return np.concatenate([_centers("UNI", n, half, rng), _centers("LAP", n, m - half, rng)])
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(np.round(idx), 0, n - 1).astype(np.int64)


def spatial_order(locs: np.ndarray) -> np.ndarray:
    """Object indices in Z-curve order on a 1024 x 1024 grid."""
    xy = (locs * 1023).astype(np.int64)
    code = 0
    for b in range(10):
        code = code | (((xy[:, 0] >> b) & 1) << (2 * b)) | (((xy[:, 1] >> b) & 1) << (2 * b + 1))
    return np.argsort(code)


def make_queries(
    locs: np.ndarray,
    kw_ids: np.ndarray,
    vocab: int,
    m: int,
    dist: str = "MIX",
    region_frac: float = 0.0005,
    n_keywords: int = 5,
    seed: int = 0,
    order: np.ndarray = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """``(rects (m, 4) f32, kw_ids (m, n_keywords) i32 padded with -1)``.

    ``order`` is ``spatial_order(locs)``, passed in by callers that draw
    several workloads from one collection.
    """
    rng = np.random.default_rng(seed)
    n = locs.shape[0]
    if order is None:
        order = spatial_order(locs)
    centers = order[_centers(dist, n, m, rng)]

    half = np.sqrt(region_frac) / 2
    c = locs[centers]
    rects = np.stack(
        [
            np.clip(c[:, 0] - half, 0, 1),
            np.clip(c[:, 1] - half, 0, 1),
            np.clip(c[:, 0] + half, 0, 1),
            np.clip(c[:, 1] + half, 0, 1),
        ],
        axis=1,
    ).astype(np.float32)

    q_kw = np.full((m, n_keywords), -1, dtype=np.int32)
    freq = np.bincount(kw_ids[kw_ids >= 0], minlength=vocab).astype(np.float64)
    zipf_pool = freq / max(freq.sum(), 1)
    for i, ci in enumerate(centers):
        own = kw_ids[ci][kw_ids[ci] >= 0]
        take = own[:n_keywords]
        q_kw[i, : take.size] = take
        extra = n_keywords - take.size
        if extra > 0:
            fill = rng.choice(vocab, size=extra, replace=False, p=zipf_pool)
            q_kw[i, take.size : take.size + extra] = fill
        row = np.unique(q_kw[i][q_kw[i] >= 0])
        q_kw[i, :] = -1
        q_kw[i, : row.size] = row
    return rects, q_kw
