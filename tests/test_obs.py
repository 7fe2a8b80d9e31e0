"""The program's own spans and counters (``repro.obs``).

Recording follows the profiler's switch: with it off a span records nothing
and a counter only adds to its total; under ``jax.profiler.trace`` the
front doors of ``LiveIndex`` leave one span per layer boundary, nested and
sharing their call's ``batch`` number, both in memory and on the trace's
host plane. The counters are checked against what they count: fetched
bytes, pad rows, plan retries, live kNN chunks, delta-buffer doublings, and
entries dropped past the log's cap. Serving results do not depend on
whether recording is on.
"""
import glob
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.query import knn_query
from repro.data.synth import make_dataset
from repro.data.workloads import make_workload
from repro.launch.wisk_serve import LiveIndex, serve_batch
from repro.serve.engine import IndexSnapshot, _knn_leaf_phase, retrieve_knn
from repro.serve.plan import PlanCache

from test_query_parity import _build_index

SKR_SPANS = {"wisk.serve", "wisk.prep", "wisk.descend", "wisk.sync", "wisk.redescend",
             "wisk.verify", "wisk.fetch", "wisk.observe"}
KNN_SPANS = {"wisk.serve_knn", "wisk.prep", "wisk.descend", "wisk.leaf_phase", "wisk.sync",
             "wisk.fetch", "wisk.observe"}
INSERT_SPANS = {"wisk.insert", "wisk.delta_insert", "wisk.geofence_match"}
DELETE_SPANS = {"wisk.delete", "wisk.delta_delete"}
DRAIN_SPANS = {"wisk.drain"}


@pytest.fixture(scope="module")
def small():
    ds = make_dataset("fs", n=1500, seed=0)
    index, clusters = _build_index(ds, g=6, levels=2)
    wl = make_workload(ds, m=12, dist="MIX", seed=3)
    points = np.stack([(wl.rects[:, 0] + wl.rects[:, 2]) / 2,
                       (wl.rects[:, 1] + wl.rects[:, 3]) / 2], 1).astype(np.float32)
    return SimpleNamespace(ds=ds, index=index, k_leaves=clusters.k, wl=wl, points=points)


def _live(small):
    return LiveIndex(small.ds, small.wl, artifacts=SimpleNamespace(index=small.index))


def _spans(entries):
    return [e for e in entries if len(e) == 5]


def _counted(entries, name):
    return sum(e[2] for e in entries if len(e) == 3 and e[0] == name)


def test_trace_me_switch_resolves():
    """The profiler's switch is a private JAX name; if it moves, this fails
    rather than recording turning off in silence."""
    from jax._src.lib import _profiler

    assert obs.recording is _profiler.TraceMe.is_enabled
    assert obs.recording() is False


def test_profiler_off_records_nothing_and_counters_total(small):
    obs.reset()
    live = _live(small)
    with obs.span("test.noop") as s:
        pass
    assert s is obs.span("test.other")  # one shared no-op context
    for _ in range(2):
        live.serve(small.wl.rects, small.wl.kw_bitmap, max_leaves=small.k_leaves)
        live.serve_knn(small.points, small.wl.kw_bitmap, 5)
    live.insert(small.points[:1], small.ds.kw_ids[:1])
    live.delete(np.array([0]))
    assert obs.log() == []
    got = live.stats()
    assert got["skr.rows"] == 2 * small.wl.m
    assert got["knn.chunks"] >= got["knn.live_chunks"] > 0
    timings = {}
    with obs.span("build.stage", into=timings):  # timed even with the profiler off
        pass
    assert set(timings) == {"stage"} and timings["stage"] >= 0.0
    assert obs.log() == []


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("wisk."):
                        out.append((e.name, dict(e.stats)))
    return out


def test_traced_calls_leave_every_span_nested_by_batch(small, tmp_path):
    live = _live(small)
    live.subscribe(np.array([0, 0, 1, 1], np.float32), small.ds.kw_ids[0][:1])
    rects, bms = small.wl.rects, small.wl.kw_bitmap
    live.serve(rects, bms, max_leaves=small.k_leaves)  # learn the widths: cached mode next
    live.serve_knn(small.points, bms, 5)
    for key in list(live.generation.plan_cache.widths):
        if key[0] == "skr":
            live.generation.plan_cache.widths[key] = 1  # force the overflow re-descent
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        live.serve(rects, bms, max_leaves=small.k_leaves)
        live.serve_knn(small.points, bms, 5)
        live.insert(small.points[:1], small.ds.kw_ids[:1])
        live.delete(np.array([1]))
        live.drain_notifications()
    spans = _spans(obs.log())
    calls = {}
    for name, t0, t1, depth, batch in spans:
        assert t1 >= t0
        calls.setdefault(batch, []).append((name, t0, t1, depth))
    assert len(calls) == 5  # one batch number per front-door call
    want = [SKR_SPANS, KNN_SPANS, INSERT_SPANS, DELETE_SPANS, DRAIN_SPANS]
    for expect, (batch, members) in zip(want, sorted(calls.items())):
        assert {m[0] for m in members} == expect
        (top,) = [m for m in members if m[3] == 0]
        for name, t0, t1, depth in members:
            assert top[1] <= t0 <= t1 <= top[2], name  # children inside their parent
    redescend = [s for s in spans if s[0] == "wisk.redescend"]
    (sync,) = [s for s in spans if s[0] == "wisk.sync" and s[4] == redescend[0][4]]
    assert sync[1] <= redescend[0][1] and redescend[0][2] <= sync[2]
    assert redescend[0][3] == sync[3] + 1
    events = _host_events(tmp_path)
    assert {n for n, _ in events} == set().union(*want)
    assert sorted((n, st["batch"]) for n, st in events) == sorted((s[0], s[4]) for s in spans)


def test_skr_counters_count_fetched_bytes_and_pad_rows(small, tmp_path):
    snap = IndexSnapshot.build(small.index, small.ds)
    m = 11  # a bucket of 16
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        out = serve_batch(snap, small.wl.rects[:m], small.wl.kw_bitmap[:m],
                          max_leaves=small.k_leaves, plan_cache=PlanCache())
    got = obs.totals()
    bucket = 16
    assert got["skr.rows"] == m and got["skr.pad_rows"] == bucket - m
    # ids, counts, nodes_checked, verified and overflow leave the device as int32
    assert got["skr.d2h_bytes"] == bucket * (out["ids"].shape[1] + 4) * 4
    assert _counted(obs.log(), "skr.d2h_bytes") == got["skr.d2h_bytes"]


@pytest.mark.parametrize("poison", [False, True])
def test_plan_retries_count_poisoned_widths(small, poison):
    snap = IndexSnapshot.build(small.index, small.ds)
    cache = PlanCache()
    args = (snap, small.wl.rects, small.wl.kw_bitmap)
    first = serve_batch(*args, max_leaves=small.k_leaves, plan_cache=cache)
    obs.reset()
    if poison:
        for key in list(cache.widths):
            cache.widths[key] = 1
    again = serve_batch(*args, max_leaves=small.k_leaves, plan_cache=cache)
    got = obs.totals()
    assert got.get("plan.retries", 0) == int(poison)
    assert got.get("plan.exact_descents", 0) == int(poison)
    for key in ("ids", "counts", "nodes_checked", "verified", "overflow"):
        np.testing.assert_array_equal(first[key], again[key])


def _live_chunks_numpy(points, q_bm, leaf_d, frontier, probe, ox, oy, obm, oid, k, ch):
    """The leaf phase's scan in numpy, counting chunks with any live pair."""
    d = np.where(frontier == probe[:, None], np.inf, leaf_d)
    order = np.lexsort((frontier, d), axis=1)
    d_s = np.take_along_axis(d, order, 1)
    l_s = np.take_along_axis(frontier, order, 1)
    best = [[] for _ in range(points.shape[0])]  # (d2, id) per query
    live = 0
    for c in range(0, d.shape[1], ch):
        any_active = False
        for q in range(points.shape[0]):
            bound = sorted(best[q])[k - 1][0] if len(best[q]) >= k else np.inf
            for j in range(c, c + ch):
                if not (np.isfinite(d_s[q, j]) and d_s[q, j] <= bound):
                    continue
                any_active = True
                leaf = l_s[q, j]
                for o in range(ox.shape[1]):
                    if oid[leaf, o] >= 0 and np.any(obm[leaf, o] & q_bm[q]):
                        dx, dy = ox[leaf, o] - points[q, 0], oy[leaf, o] - points[q, 1]
                        best[q].append((np.float32(dx * dx + dy * dy), int(oid[leaf, o])))
        live += any_active
    return live


def test_knn_live_chunks_match_a_numpy_recount():
    rng = np.random.default_rng(4)
    M, K, OBJ, F, k, kb, ch = 3, 8, 4, 8, 2, 8, 2
    ox = rng.uniform(size=(K, OBJ)).astype(np.float32)
    oy = rng.uniform(size=(K, OBJ)).astype(np.float32)
    obm = rng.integers(0, 4, size=(K, OBJ, 1)).astype(np.uint32)
    oid = np.arange(K * OBJ, dtype=np.int32).reshape(K, OBJ)
    points = rng.uniform(size=(M, 2)).astype(np.float32)
    q_bm = np.array([[1], [2], [3]], np.uint32)
    frontier = np.tile(np.arange(F, dtype=np.int32), (M, 1))
    leaf_d = rng.uniform(size=(M, F)).astype(np.float32) ** 2
    leaf_d[0, 5:] = np.inf
    probe = np.full(M, -1, np.int32)
    top_d = jnp.full((M, kb), jnp.inf, jnp.float32)
    top_id = jnp.full((M, kb), np.iinfo(np.int32).max, jnp.int32)
    *_, live = _knn_leaf_phase(points, q_bm, leaf_d, frontier, probe, ox, oy, obm, oid,
                               top_d, top_id, k, kb, ch)
    want = _live_chunks_numpy(points, q_bm, leaf_d, frontier, probe, ox, oy, obm, oid, k, ch)
    assert 0 < int(live) == want <= F // ch


def test_knn_counters_and_results_unchanged_by_recording(small, tmp_path):
    snap = IndexSnapshot.build(small.index, small.ds)
    args = (snap, small.points, small.wl.kw_bitmap, 5)
    plain = retrieve_knn(*args, plan_cache=PlanCache())
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        traced = retrieve_knn(*args, plan_cache=PlanCache())
    assert plain.keys() == traced.keys()
    for key in plain:
        np.testing.assert_array_equal(plain[key], traced[key])
    got = obs.totals()
    assert got["knn.chunks"] >= got["knn.live_chunks"] > 0
    assert _counted(obs.log(), "knn.live_chunks") == got["knn.live_chunks"]
    for q in range(small.wl.m):
        want = knn_query(small.index, small.ds, small.points[q], small.wl.kw_bitmap[q], 5)
        row = traced["ids"][q]
        np.testing.assert_array_equal(row[row >= 0], want.ids)
        np.testing.assert_allclose(traced["dist2"][q][: want.ids.size], want.dist2, rtol=1e-6)


def test_delta_grows_counts_a_forced_doubling(small):
    live = LiveIndex(small.ds, small.wl, artifacts=SimpleNamespace(index=small.index),
                     slots_per_leaf=1)
    obs.reset()
    point = small.points[:1]
    live.insert(point, small.ds.kw_ids[:1])  # fills the leaf's one slot
    assert obs.totals().get("delta.grows", 0) == 0
    live.insert(point, small.ds.kw_ids[:1])  # same leaf: 1 -> 2 slots
    assert obs.totals()["delta.grows"] == 1
    assert live.generation.delta_log.buffer.slots_per_leaf == 2


def test_log_cap_counts_dropped(monkeypatch, tmp_path):
    monkeypatch.setattr(obs, "LOG_CAP", 3)
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(5):
            with obs.span("test.s"):
                pass
        obs.count("test.c", 2)
    assert len(obs.log()) == 3
    assert obs.totals()["obs.dropped"] == 3
    assert obs.totals()["test.c"] == 2
