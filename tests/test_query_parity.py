"""Cross-path parity: serial / level-sync / batched dense / batched frontier.

The serving rewrite (sparse frontier descent, DESIGN.md §3) is only
acceptable if it is provably exact: on seeded randomized datasets and
workloads all four execution paths must return identical result-id sets and
consistent Eq.1 cost counters, including flat (no-hierarchy) indexes and
small-``max_leaves`` overflow. Index construction here is deterministic
(grid clusters + spatial grouping) so the suite is fast and seed-stable --
it does not run the DQN packer.
"""
import functools

import numpy as np
import pytest

from repro.core.index import assemble_index, flat_index
from repro.core.packing import HierarchyResult
from repro.core.query import (
    execute_level_sync,
    execute_serial,
    padded_child_table,
    propagate_hits,
)
from repro.core.types import ClusterSet
from repro.data.synth import make_dataset
from repro.data.workloads import make_workload
from repro.serve.engine import IndexSnapshot, retrieve_workload, round_up_bucket


def _grid_clusters(ds, g):
    cell = np.minimum((ds.locs * g).astype(np.int32), g - 1)
    assign = cell[:, 0] * g + cell[:, 1]
    _, assign = np.unique(assign, return_inverse=True)
    return ClusterSet.from_assignment(ds, assign.astype(np.int32))


def _spatial_parents(mbrs, g):
    cent = np.clip((mbrs[:, :2] + mbrs[:, 2:]) / 2, 0.0, 1.0)
    cell = np.minimum((cent * g).astype(np.int32), g - 1)
    pid = cell[:, 0] * g + cell[:, 1]
    _, pid = np.unique(pid, return_inverse=True)
    return pid.astype(np.int32)


def _build_index(ds, g=6, levels=2):
    """Deterministic hierarchy: grid leaves grouped spatially, bottom-up."""
    clusters = _grid_clusters(ds, g)
    parents = []
    mbrs = clusters.mbrs
    gg = max(2, g // 2)
    for _ in range(levels - 1):
        p = _spatial_parents(mbrs, gg)
        if p.max() + 1 >= mbrs.shape[0]:  # grouping stopped shrinking
            break
        parents.append(p)
        n_up = int(p.max()) + 1
        up = np.zeros((n_up, 4), np.float32)
        for u in range(n_up):
            mb = mbrs[p == u]
            up[u] = (mb[:, 0].min(), mb[:, 1].min(), mb[:, 2].max(), mb[:, 3].max())
        mbrs = up
        gg = max(2, gg // 2)
    hier = HierarchyResult(parents=parents, level_labels=[], packs=[]) if parents else None
    return assemble_index(ds, clusters, hier), clusters


def _result_sets(out):
    return [np.sort(row[row >= 0]) for row in out["ids"]]


@pytest.mark.parametrize("seed,levels", [(0, 2), (1, 2), (2, 3), (3, 1)])
def test_all_paths_identical(seed, levels):
    ds = make_dataset("fs", n=1500, seed=seed)
    if levels == 1:
        index, clusters = flat_index(ds, _grid_clusters(ds, 5)), _grid_clusters(ds, 5)
    else:
        index, clusters = _build_index(ds, g=6, levels=levels)
    wl = make_workload(ds, m=20, dist="MIX", seed=seed + 10)
    st_serial = execute_serial(index, ds, wl)
    st_sync = execute_level_sync(index, ds, wl)
    bw = IndexSnapshot.build(index, ds, dense=True)
    outs = {
        mode: retrieve_workload(bw, wl, max_leaves=clusters.k, mode=mode)
        for mode in ("dense", "frontier")
    }
    for a, b in zip(st_serial.results, st_sync.results):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(st_serial.nodes_accessed, st_sync.nodes_accessed)
    np.testing.assert_array_equal(st_serial.verified, st_sync.verified)
    for mode, out in outs.items():
        assert (out["overflow"] == 0).all(), mode
        for got, want in zip(_result_sets(out), st_serial.results):
            np.testing.assert_array_equal(got, np.sort(want), err_msg=mode)
        np.testing.assert_array_equal(out["nodes_checked"], st_serial.nodes_accessed)
        np.testing.assert_array_equal(out["verified"], st_serial.verified)
        np.testing.assert_array_equal(out["counts"], [len(r) for r in st_serial.results])


def test_frontier_scans_fewer_nodes_than_dense_mask():
    """The acceptance gate of the rewrite: per-level kernel work is the
    bucketed frontier width, not the level width, so on a hierarchical index
    the frontier path touches strictly fewer slots than the dense mask --
    and examines exactly the nodes the paper-faithful traversal does."""
    ds = make_dataset("fs", n=2500, seed=5)
    index, clusters = _build_index(ds, g=8, levels=3)
    assert index.height >= 2
    wl = make_workload(ds, m=32, dist="MIX", seed=7)
    bw = IndexSnapshot.build(index, ds, dense=True)
    dense = retrieve_workload(bw, wl, max_leaves=clusters.k, mode="dense")
    frontier = retrieve_workload(bw, wl, max_leaves=clusters.k, mode="frontier")
    assert frontier["nodes_scanned"].sum() < dense["nodes_scanned"].sum()
    assert frontier["nodes_checked"].sum() < dense["nodes_scanned"].sum()
    st = execute_serial(index, ds, wl)
    np.testing.assert_array_equal(frontier["nodes_checked"], st.nodes_accessed)
    # per-level width never exceeds its bucketed level size
    for w, lvl in zip(frontier["frontier_widths"], index.levels):
        assert w <= round_up_bucket(lvl.n)


def test_max_leaves_overflow_parity():
    """Small max_leaves: dense and frontier must drop the SAME leaves (ids
    and overflow counts identical) and return subsets of the exact results."""
    ds = make_dataset("fs", n=1500, seed=8)
    index, clusters = _build_index(ds, g=6, levels=2)
    # big rectangles so queries touch many leaves and actually overflow
    wl = make_workload(ds, m=16, dist="UNI", region_frac=0.2, n_keywords=4, seed=9)
    bw = IndexSnapshot.build(index, ds, dense=True)
    st = execute_serial(index, ds, wl)
    for max_leaves in (1, 2, 4):
        dense = retrieve_workload(bw, wl, max_leaves=max_leaves, mode="dense")
        frontier = retrieve_workload(bw, wl, max_leaves=max_leaves, mode="frontier")
        np.testing.assert_array_equal(dense["overflow"], frontier["overflow"])
        for a, b in zip(_result_sets(dense), _result_sets(frontier)):
            np.testing.assert_array_equal(a, b)
        for got, want in zip(_result_sets(frontier), st.results):
            assert np.isin(got, want).all()
    assert retrieve_workload(bw, wl, max_leaves=1, mode="frontier")["overflow"].sum() > 0
    # with full capacity the overflow vanishes and results are exact again
    full = retrieve_workload(bw, wl, max_leaves=clusters.k, mode="frontier")
    assert (full["overflow"] == 0).all()
    for got, want in zip(_result_sets(full), st.results):
        np.testing.assert_array_equal(got, np.sort(want))


def test_csr_propagation_matches_dense_matmul():
    """CSR frontier expansion == dense adjacency matmul on random parents
    (non-hypothesis twin of the property test in test_properties.py)."""
    rng = np.random.default_rng(11)
    for _ in range(10):
        n_down = int(rng.integers(2, 40))
        n_up = int(rng.integers(1, n_down + 1))
        parent = rng.integers(0, n_up, n_down)
        parent[rng.integers(0, n_down)] = n_up - 1  # ensure last parent used
        ptr = np.zeros(n_up + 1, np.int64)
        order = np.argsort(parent, kind="stable").astype(np.int32)
        np.cumsum(np.bincount(parent, minlength=n_up), out=ptr[1:])

        class L:
            child_ptr, child, n = ptr, order, n_up

        table = padded_child_table(L)
        hit = rng.integers(0, 2, (5, n_up)).astype(bool)
        got = propagate_hits(hit, table, n_down)
        adj = np.zeros((n_up, n_down), np.int8)
        adj[parent, np.arange(n_down)] = 1
        np.testing.assert_array_equal(got, (hit @ adj) > 0)


def test_frontier_width_cache_stays_lossless():
    """The batched-sync width discipline (DESIGN.md §3.2), now owned by the
    explicit PlanCache: the first descent learns per-level widths with exact
    syncs; cached descents run sync-free; a deliberately-poisoned (too
    narrow) cache must trigger the lossless overflow retry and still return
    exact results and counters."""
    from repro.serve.plan import PlanCache

    ds = make_dataset("fs", n=2500, seed=5)
    index, clusters = _build_index(ds, g=8, levels=3)
    wl = make_workload(ds, m=16, dist="UNI", region_frac=0.2, n_keywords=4, seed=9)
    st = execute_serial(index, ds, wl)
    bw = IndexSnapshot.build(index, ds)
    cache = PlanCache()
    first = retrieve_workload(bw, wl, max_leaves=clusters.k, mode="frontier", plan_cache=cache)
    learned = dict(cache.widths)
    assert learned  # exact first descent populated the cache
    assert cache.plan("skr", bw.n_levels - 1).widths is not None
    # cached descent: identical results, widths from the cache
    cached = retrieve_workload(bw, wl, max_leaves=clusters.k, mode="frontier", plan_cache=cache)
    for a, b in zip(_result_sets(first), _result_sets(cached)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(cached["nodes_checked"], st.nodes_accessed)
    # poison every width to the minimum bucket: children would be dropped,
    # so the batched overflow check must fire and re-descend exactly
    for key in list(cache.widths):
        cache.widths[key] = 8
    retried = retrieve_workload(bw, wl, max_leaves=clusters.k, mode="frontier", plan_cache=cache)
    for got, want in zip(_result_sets(retried), st.results):
        np.testing.assert_array_equal(got, np.sort(want))
    np.testing.assert_array_equal(retried["nodes_checked"], st.nodes_accessed)
    np.testing.assert_array_equal(retried["verified"], st.verified)
    assert dict(cache.widths) == learned  # retry re-learned the real widths
    # an independent cache starts unlearned: plans resolve to exact mode
    assert PlanCache().plan("skr", bw.n_levels - 1).widths is None


def test_bucketing_pads_are_inert():
    """serve_batch pads the batch to its power-of-two bucket; pad queries
    must not change real queries' results or counters."""
    from repro.launch.wisk_serve import pad_queries_to_bucket, serve_batch

    ds = make_dataset("fs", n=1200, seed=12)
    index, clusters = _build_index(ds, g=5, levels=2)
    bw = IndexSnapshot.build(index, ds)
    wl = make_workload(ds, m=13, dist="MIX", seed=13)  # not a power of two
    rects, bms, m = pad_queries_to_bucket(wl.rects, wl.kw_bitmap)
    assert m == 13 and rects.shape[0] == 16
    out = serve_batch(bw, wl.rects, wl.kw_bitmap, max_leaves=clusters.k)
    direct = retrieve_workload(bw, wl, max_leaves=clusters.k, mode="frontier")
    assert out["ids"].shape[0] == 13
    for a, b in zip(_result_sets(out), _result_sets(direct)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(out["nodes_checked"], direct["nodes_checked"])


# ------------------------------------- fused leaf verification (DESIGN.md §3.5)
@pytest.mark.parametrize("seed,levels,mode", [
    (0, 1, "frontier"), (1, 2, "frontier"), (2, 3, "frontier"),
    (0, 2, "dense"), (3, 2, "dense"),
])
def test_fused_verify_elementwise_parity(seed, levels, mode):
    """The fused gather+verify path must be ELEMENTWISE-identical to the
    unfused gather -> skr_verify path -- same ids in the same slots, same
    Eq.1 counters -- not merely set-equal, across hierarchy depths and both
    descent modes."""
    ds = make_dataset("fs", n=1200, seed=seed)
    index, clusters = _build_index(ds, g=5, levels=levels)
    snap = IndexSnapshot.build(index, ds, dense=True)
    wl = make_workload(ds, m=24, dist="MIX", seed=seed + 40)
    a = retrieve_workload(snap, wl, max_leaves=clusters.k, mode=mode, fused=False)
    b = retrieve_workload(snap, wl, max_leaves=clusters.k, mode=mode, fused=True)
    c = retrieve_workload(snap, wl, max_leaves=clusters.k, mode=mode)  # auto
    for key in ("ids", "counts", "nodes_checked", "nodes_scanned", "verified", "overflow"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]), err_msg=key)
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(c[key]), err_msg=key)


@pytest.mark.parametrize("max_leaves", [1, 2, 5])
def test_fused_verify_overflow_parity(max_leaves):
    """Capacity-overflow configs: the fused path must spill identically
    (same selected leaves, same overflow counts, same partial results)."""
    ds = make_dataset("fs", n=1200, seed=5)
    index, _ = _build_index(ds, g=6, levels=2)
    snap = IndexSnapshot.build(index, ds)
    wl = make_workload(ds, m=16, dist="UNI", region_frac=0.2, n_keywords=4, seed=9)
    a = retrieve_workload(snap, wl, max_leaves=max_leaves, fused=False)
    b = retrieve_workload(snap, wl, max_leaves=max_leaves, fused=True)
    assert np.asarray(a["overflow"]).sum() > 0  # the config actually spills
    for key in ("ids", "counts", "verified", "overflow"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]), err_msg=key)


def test_fused_stays_on_with_delta():
    """fused=None must keep the fused kernel on the base leaf blocks when a
    DeltaBuffer is live (the PR 6 gap: it used to fall back to the wholesale
    unfused pipeline on any delta): deleted snapshot objects are masked into
    pad slots for the fused pass and only the insert-buffer slots take the
    unfused merge, elementwise-identical -- same ids in the same candidate
    slots, same counters -- to the forced-unfused baseline."""
    from repro.serve.delta import DeltaLog

    ds = make_dataset("fs", n=1000, seed=6)
    index, clusters = _build_index(ds, g=5, levels=2)
    snap = IndexSnapshot.build(index, ds)
    log = DeltaLog(index, ds, snap)
    rng = np.random.default_rng(0)
    log.insert(rng.uniform(0.4, 0.6, (8, 2)).astype(np.float32),
               [[1, 2, 3]] * 8)
    log.delete(np.arange(0, 200, 13))  # the fused pass must mask deletes too
    delta = log.buffer
    wl = make_workload(ds, m=12, dist="MIX", seed=50)
    # pin one query onto the inserted objects so the delta is visible
    R = np.asarray(wl.rects).copy()
    B = np.asarray(wl.kw_bitmap).copy()
    R[0] = (0.35, 0.35, 0.65, 0.65)
    B[0] = 0
    B[0, 0] = (1 << 1) | (1 << 2) | (1 << 3)
    import dataclasses as _dc

    wl = _dc.replace(wl, rects=R, kw_bitmap=B)
    unfused = retrieve_workload(
        snap, wl, max_leaves=clusters.k, delta=delta, fused=False
    )
    for fused in (None, True):
        out = retrieve_workload(
            snap, wl, max_leaves=clusters.k, delta=delta, fused=fused
        )
        for key in ("ids", "counts", "verified", "overflow"):
            np.testing.assert_array_equal(
                np.asarray(out[key]), np.asarray(unfused[key]),
                err_msg=f"{key} (fused={fused})",
            )
    # and the delta actually changed results vs the delta-free descent
    base = retrieve_workload(snap, wl, max_leaves=clusters.k)
    assert any(
        not np.array_equal(np.sort(p[p >= 0]), np.sort(q[q >= 0]))
        for p, q in zip(np.asarray(unfused["ids"]), np.asarray(base["ids"]))
    )


@pytest.mark.parametrize("seed,levels", [(0, 2), (3, 3)])
def test_narrow_descent_engine_parity(seed, levels):
    """The bandwidth-lean narrow descent (DESIGN.md §3.5) vs the forced-f32
    descent at the engine level: ids AND every traversal counter identical
    (the shadow planes are lossless), and the snapshot actually carries the
    int16 codes so quantized=None really exercised the narrow path."""
    ds = make_dataset("fs", n=1500, seed=seed)
    index, clusters = _build_index(ds, g=6, levels=levels)
    snap = IndexSnapshot.build(index, ds)
    assert snap.has_narrow_planes
    wl = make_workload(ds, m=20, dist="MIX", seed=seed + 10)
    wide = retrieve_workload(snap, wl, max_leaves=clusters.k, quantized=False)
    narrow = retrieve_workload(snap, wl, max_leaves=clusters.k, quantized=True)
    auto = retrieve_workload(snap, wl, max_leaves=clusters.k)
    for key in ("ids", "counts", "verified", "overflow",
                "nodes_scanned", "nodes_checked"):
        np.testing.assert_array_equal(
            np.asarray(wide[key]), np.asarray(narrow[key]), err_msg=key)
        np.testing.assert_array_equal(
            np.asarray(narrow[key]), np.asarray(auto[key]), err_msg=key)


# ------------------------- the compiled verify stage (serve.engine._verify_stage)
_STAGE_N = 1200  # objects in the stage cases' collection; inserts get ids from here


@functools.lru_cache(maxsize=None)
def _stage_world():
    """A compact-bank snapshot and three live states: no delta, a delta whose
    inserts stay inside their leaves' dictionaries (``ins_cbm`` kept), and a
    delta after the compact fallback (``ins_cbm`` dropped). Deletes hit base
    objects and one buffered insert in both deltas."""
    from repro.serve.delta import DeltaLog

    ds = make_dataset("fs", n=_STAGE_N, seed=7)
    index, clusters = _build_index(ds, g=5, levels=2)
    snap = IndexSnapshot.build(index, ds)
    assert snap.has_compact_bank
    rng = np.random.default_rng(3)
    src = rng.choice(ds.n, 6, replace=False)
    locs = ds.locs[src].astype(np.float32)
    probe = DeltaLog(index, ds, snap)
    pid = probe.insert(locs, ds.kw_ids[src])
    ins_id = np.asarray(probe.buffer.ins_id)
    leaves = [int(np.argwhere(ins_id == int(i))[0, 0]) for i in pid]
    terms = np.asarray(snap.leaf_terms)
    kw = np.full((src.size, 2), -1, np.int32)
    for i, lf in enumerate(leaves):
        t = terms[lf][terms[lf] >= 0][:2]
        kw[i, : t.size] = t
    deltas = {"none": None}
    for name in ("cbm", "nocbm"):
        log = DeltaLog(index, ds, snap)
        new = log.insert(locs, kw)
        if name == "nocbm":
            fresh = np.setdiff1d(np.arange(ds.vocab_size), terms[leaves[0]])
            log.insert(locs[:1], np.array([[int(fresh[0])]], np.int32))
        log.delete(np.concatenate([np.arange(0, 300, 11), new[-1:]]))
        assert (log.buffer.ins_cbm is not None) == (name == "cbm"), name
        deltas[name] = log.buffer
    # pin the first queries onto the inserts so the delta slots match
    wl = make_workload(ds, m=32, dist="MIX", seed=21)
    R = np.asarray(wl.rects).copy()
    B = np.asarray(wl.kw_bitmap).copy()
    for i in range(4):
        x, y = locs[i]
        R[i] = (x - 0.05, y - 0.05, x + 0.05, y + 0.05)
        B[i] = 0
        for t in kw[i][kw[i] >= 0]:
            B[i, t >> 5] |= np.uint32(1) << np.uint32(t & 31)
    wide = make_workload(ds, m=32, dist="UNI", region_frac=0.2, n_keywords=4, seed=9)
    return snap, clusters.k, deltas, (R, B), (np.asarray(wide.rects), np.asarray(wide.kw_bitmap))


def _stage_inputs(snap, delta, R, B, m):
    """The exact-mode frontier descent's leaf frontier and survivors."""
    import jax.numpy as jnp

    from repro.serve import engine
    from repro.serve.plan import ExecutionPlan

    q_rects = jnp.asarray(R[:m], jnp.float32)
    q_bm = jnp.asarray(B[:m], jnp.uint32)
    frontier, surv, *_ = engine._descend_frontier(
        snap, q_rects, q_bm, ExecutionPlan(tag="skr", widths=None), delta
    )
    return q_rects, q_bm, frontier, surv


@pytest.mark.parametrize("m", [1, 8, 32])
@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("compact", [True, False])
@pytest.mark.parametrize("delta_kind,overflow", [
    ("none", False), ("cbm", False), ("nocbm", False), ("cbm", True),
])
def test_compiled_verify_stage_matches_eager(delta_kind, overflow, compact, fused, m):
    """The compiled verify stage (leaf selection, remap, base_alive mask,
    fused kernel, delta-slot merge and counts in one program) returns exactly
    what the eager composition of ``_select_leaves_frontier`` and
    ``_verify_leaves`` returns: the same ids in the same slots, the same
    counts, ``verified`` and ``overflow``."""
    from repro.serve import engine

    snap, k, deltas, pinned, wide = _stage_world()
    delta = deltas[delta_kind]
    R, B = wide if overflow else pinned
    max_leaves = 2 if overflow else k
    q_rects, q_bm, frontier, surv = _stage_inputs(snap, delta, R, B, m)
    take = min(max_leaves, snap.n_leaves, int(frontier.shape[1]))
    got = engine._run_verify_stage(
        snap, q_rects, q_bm, frontier, surv, take, delta, fused, None, compact
    )
    top_leaf, leaf_ok, want_over = engine._select_leaves_frontier(
        frontier, surv, take, snap.n_leaves
    )
    want = engine._verify_leaves(
        snap, q_rects, q_bm, top_leaf, leaf_ok, delta, fused, None, compact
    ) + (want_over,)
    for key, g, w in zip(("ids", "counts", "kw_scanned", "overflow"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=key)
    if overflow and m > 1:
        assert np.asarray(got[3]).sum() > 0  # the case actually spills
    if delta_kind != "none" and not overflow:
        assert (np.asarray(got[0]) >= _STAGE_N).any()  # a buffered insert matched


def test_compiled_verify_stage_does_not_retrace():
    """A call at shapes already seen reuses the stage program: the jit cache
    neither grows nor lowers anything, also after an update replaces the
    delta with a new buffer of the same structure and shapes."""
    import jax

    from repro.serve import engine
    from repro.serve.delta import DeltaLog

    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_k: lowered.append(event)
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration" else None
    )
    ds = make_dataset("fs", n=1000, seed=8)
    index, clusters = _build_index(ds, g=5, levels=2)
    snap = IndexSnapshot.build(index, ds)
    wl = make_workload(ds, m=8, dist="MIX", seed=22)
    log = DeltaLog(index, ds, snap)
    log.insert(ds.locs[:2].astype(np.float32), ds.kw_ids[:2])
    for delta in (None, log.buffer):
        retrieve_workload(snap, wl, max_leaves=clusters.k, delta=delta)  # settles widths
        retrieve_workload(snap, wl, max_leaves=clusters.k, delta=delta)
        size, n_lowered = engine._verify_stage._cache_size(), len(lowered)
        assert size > 0
        out = retrieve_workload(snap, wl, max_leaves=clusters.k, delta=delta)
        assert engine._verify_stage._cache_size() == size
        assert len(lowered) == n_lowered, lowered[n_lowered:]
    log.delete(np.arange(3))  # a new buffer, same slots per leaf
    n_lowered = len(lowered)  # the delete's own update programs are not the stage's
    retrieve_workload(snap, wl, max_leaves=clusters.k, delta=log.buffer)
    assert engine._verify_stage._cache_size() == size
    assert len(lowered) == n_lowered, lowered[n_lowered:]
    assert out["ids"].shape[0] == 8
