"""``correct`` comes out false for the control and for each fault the cells
can have, with the rest of a run unchanged.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/test_correct.py -q

Run by hand (minutes): each case drives a whole run of a cell on the CPU at
a tiny size (``rehearse.tiny_root``), skipping the look for a chip. The
live mix is made update-heavy here and its geofences wide, so that a run
of a few seconds holds enough updates and notifications to judge:

* the control: the plain reference answering in bfloat16 geometry
  (``--control bf16``), in both cells;
* a state left unchanged: inserts and deletes acknowledged but not applied
  (the live cell; the read-only cell changes no state);
* half of each batch left out: the second half's answers come back empty
  (both cells);
* an answer altered where it is produced: one id dropped from each SKR
  answer, one kNN neighbour replaced by a farther one (both cells).

A cell on one chip exchanges nothing between chips, so that fault has no
case here.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))

import rehearse  # noqa: E402

SEED = 2**31 + 99
LIVE, READ_ONLY = "osm-live-mix", "fs-skr-mix"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    over = {
        "mix": {"skr": 0.3, "knn": 0.3, "insert": 0.2, "delete": 0.2},
        "geofences": {"count": 64, "half_side": [0.15, 0.3], "max_keywords": 2},
    }
    return rehearse.tiny_root(tmp_path_factory.mktemp("tiny"), rate=10.0, traffic_over=over)


def _run(root, workload, extra=(), patch=None):
    result, _ = rehearse.run_tiny(root, workload, SEED, seconds=4.0, extra=extra, patch=patch)
    return result, {k: v["value"] for k, v in result["checks"].items()}


@pytest.mark.parametrize("workload", [LIVE, READ_ONLY])
def test_sound_run_is_correct(root, workload):
    result, checks = _run(root, workload)
    assert result["correct"], checks


@pytest.mark.parametrize("workload", [LIVE, READ_ONLY])
def test_control_in_bf16_is_not_correct(root, workload):
    result, checks = _run(root, workload, extra=("--control", "bf16"))
    assert not result["correct"], checks
    assert checks["skr_wrong"] > 0 or checks["knn_gap"] > 1e-5, checks


def _state_unchanged(server):
    live = server.live
    nxt = {"id": live.generation.delta_log._next_id}

    def insert(locs, kw):
        n = np.asarray(locs).reshape(-1, 2).shape[0]
        ids = np.arange(nxt["id"], nxt["id"] + n)
        nxt["id"] += n
        return ids

    server.insert = insert
    server.delete = lambda ids: np.atleast_1d(ids).size


def _half_batch(server):
    skr, knn = server.serve_skr, server.serve_knn

    def halved(serve, first, rest, width):
        m = len(first)
        keep = m // 2
        if keep:
            out = dict(serve(first[:keep], rest[:keep], *width))
        else:
            out = {"ids": np.full((0, max(width, default=1)), -1, np.int32),
                   "verified": np.zeros(0, np.int64)}
        pad = np.full((m - keep, out["ids"].shape[1]), -1, out["ids"].dtype)
        out["ids"] = np.concatenate([out["ids"], pad])
        out["verified"] = np.concatenate([np.asarray(out["verified"]), np.zeros(m - keep, np.int64)])
        return out

    server.serve_skr = lambda rects, bms: halved(skr, rects, bms, ())
    server.serve_knn = lambda points, bms, k: halved(knn, points, bms, (k,))


def _altered_answer(server):
    skr, knn, rows = server.serve_skr, server.serve_knn, server.skr_rows

    def skr_rows(out, m):
        return [r[:-1] if r.size else r for r in rows(out, m)]

    def serve_knn(points, bms, k):
        out = dict(knn(points, bms, k + 1))  # the (k+1)-th neighbour stands in for the k-th
        ids = out["ids"].copy()
        ids[:, k - 1] = ids[:, k]
        out["ids"] = ids[:, :k]
        return out

    server.skr_rows, server.serve_knn = skr_rows, serve_knn


@pytest.mark.parametrize("workload,fault", [
    (LIVE, _state_unchanged), (LIVE, _half_batch), (LIVE, _altered_answer),
    (READ_ONLY, _half_batch), (READ_ONLY, _altered_answer),
], ids=["live-state_unchanged", "live-half_batch", "live-altered_answer",
        "read_only-half_batch", "read_only-altered_answer"])
def test_fault_is_not_correct(root, workload, fault):
    result, checks = _run(root, workload, patch=fault)
    assert not result["correct"], checks
