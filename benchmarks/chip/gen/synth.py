"""Synthetic geo-textual POI collections (the benchmark's own copy).

A copy of the program's ``data/synth.py`` generator, kept here so that the
benchmark's data cannot change when the program does. It returns plain
numpy arrays; the harness hands them to the program in its own types.

* locations: a mixture of 2-D Gaussian hotspots and a uniform background;
* keywords: Zipf frequencies over a vocabulary, each keyword with a few
  topic centres so that its objects concentrate spatially.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

DEFAULTS = dict(hotspot_frac=0.7, kw_locality=0.6, topic_centers_per_kw=2)


def _zipf_probs(v: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, v + 1) ** a
    return p / p.sum()


def make_objects(params: Dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(locs (n, 2) f32, kw_ids (n, max_kw) i32 padded with -1)``.

    ``params`` holds ``n``, ``vocab``, ``max_kw``, ``zipf_a``,
    ``n_hotspots`` and optionally ``hotspot_frac``, ``kw_locality`` and
    ``topic_centers_per_kw``.
    """
    p = {**DEFAULTS, **params}
    n, vocab, max_kw = int(p["n"]), int(p["vocab"]), int(p["max_kw"])
    n_hotspots, tc = int(p["n_hotspots"]), int(p["topic_centers_per_kw"])
    rng = np.random.default_rng(seed)
    # --- locations ---
    n_hot = int(n * p["hotspot_frac"])
    centers = rng.uniform(0.08, 0.92, size=(n_hotspots, 2))
    scales = rng.uniform(0.01, 0.06, size=(n_hotspots, 1))
    which = rng.integers(0, n_hotspots, size=n_hot)
    hot = centers[which] + rng.normal(0, 1, size=(n_hot, 2)) * scales[which]
    bg = rng.uniform(0, 1, size=(n - n_hot, 2))
    locs = np.clip(np.concatenate([hot, bg], axis=0), 0.0, 1.0).astype(np.float32)
    rng.shuffle(locs)

    # --- keyword topic fields ---
    topic_centers = rng.uniform(0, 1, size=(vocab, tc, 2))
    zipf = _zipf_probs(vocab, p["zipf_a"])

    n_kw = rng.integers(1, max_kw + 1, size=n)
    kw_ids = np.full((n, max_kw), -1, dtype=np.int32)

    total = int(n_kw.sum())
    glob = rng.choice(vocab, size=total, p=zipf)
    # local keyword per object: the keyword whose topic centre is nearest
    # among a random Zipf-weighted candidate set
    cand = rng.choice(vocab, size=(n, 8), p=zipf)
    d = np.linalg.norm(
        topic_centers[cand].reshape(n, 8 * tc, 2) - locs[:, None, :], axis=2
    ).reshape(n, 8, tc).min(axis=2)
    local_kw = cand[np.arange(n), d.argmin(axis=1)]

    pos = 0
    use_local = rng.uniform(size=total) < p["kw_locality"]
    for i in range(n):
        k = int(n_kw[i])
        draws = glob[pos : pos + k].copy()
        draws[use_local[pos : pos + k]] = local_kw[i]
        uniq = np.unique(draws)[:max_kw]
        kw_ids[i, : uniq.size] = uniq
        pos += k
    return locs, kw_ids
