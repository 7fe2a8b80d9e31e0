"""Sharded-vs-single-device serving parity (DESIGN.md §3.4).

The data-parallel front doors (``serve_sharded`` / ``serve_knn_sharded``)
shard_map the REAL hierarchical engine -- frontier SKR descent and
distance-bounded kNN descent -- over the mesh's data axes with the
``IndexSnapshot`` replicated. They must be *id-sequence- and
counter-identical* to the single-device engine, including ragged
(non-divisible) batch sizes, inert pad queries, width-cache growth across
shards, and ``max_leaves`` overflow.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
multi-device lane) these tests exercise true 8-way query sharding; on a
single device they still pin the shard_map path against the plain engine.

Also here: the regression for the flat leaf-sharded fallback's two-stage
verification, whose ``stage2_cap`` overflow used to be silently discarded
(``counts + 0 * overflow``) -- it is now psum'd over ``model`` and returned.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.query import execute_serial, sharded_bucket
from repro.data.synth import make_dataset
from repro.data.workloads import make_workload
from repro.launch.mesh import make_host_mesh
from repro.launch.wisk_serve import (
    OBJ_PER_LEAF,
    TOP_LEAVES_LOCAL,
    default_serving_mesh,
    mesh_dp_size,
    serve_knn_sharded,
    serve_sharded,
    wisk_serve_step,
)
from repro.serve.engine import IndexSnapshot, retrieve_knn, retrieve_workload
from repro.serve.plan import PlanCache
from jax import shard_map

from test_query_parity import _build_index, _grid_clusters, flat_index


SKR_KEYS = ("ids", "counts", "nodes_checked", "nodes_scanned", "verified", "overflow")
KNN_KEYS = ("ids", "dist2", "nodes_checked", "verified", "leaves_verified", "pruned")


def _points_from(wl) -> np.ndarray:
    return np.stack(
        [(wl.rects[:, 0] + wl.rects[:, 2]) / 2, (wl.rects[:, 1] + wl.rects[:, 3]) / 2], 1
    ).astype(np.float32)


def _assert_same(single, sharded, keys):
    for k in keys:
        np.testing.assert_array_equal(single[k], sharded[k], err_msg=k)
    np.testing.assert_array_equal(
        single["frontier_widths"], sharded["frontier_widths"], err_msg="frontier_widths"
    )


def test_serving_mesh_uses_all_devices():
    """The default serving mesh puts every local device on the data axis --
    under the CI 8-device CPU platform the parity tests below genuinely
    exercise 8-way query sharding."""
    mesh = default_serving_mesh()
    assert mesh_dp_size(mesh) == len(jax.devices())
    assert sharded_bucket(13, 8) == 64 and sharded_bucket(16, 1) == 16


@pytest.mark.parametrize("seed,levels,m", [(0, 2, 13), (2, 3, 20), (3, 1, 5)])
def test_skr_sharded_matches_single_device(seed, levels, m):
    """Identical ids and Eq.1 counters, including ragged batches that do not
    divide by the shard count and hierarchies of different heights."""
    ds = make_dataset("fs", n=1500, seed=seed)
    if levels == 1:
        index, clusters = flat_index(ds, _grid_clusters(ds, 5)), _grid_clusters(ds, 5)
    else:
        index, clusters = _build_index(ds, g=6, levels=levels)
    wl = make_workload(ds, m=m, dist="MIX", seed=seed + 10)
    snap = IndexSnapshot.build(index, ds)
    single = retrieve_workload(snap, wl, max_leaves=clusters.k, plan_cache=PlanCache())
    sharded = serve_sharded(
        snap, wl.rects, wl.kw_bitmap, max_leaves=clusters.k, plan_cache=PlanCache()
    )
    assert sharded["ids"].shape[0] == m  # padding sliced back off
    _assert_same(single, sharded, SKR_KEYS)
    st = execute_serial(index, ds, wl)
    np.testing.assert_array_equal(sharded["nodes_checked"], st.nodes_accessed)
    np.testing.assert_array_equal(sharded["counts"], [len(r) for r in st.results])


def test_skr_sharded_width_growth_and_overflow_parity():
    """Wide queries force the seeded widths to grow through the
    grow-and-redescend loop, and small ``max_leaves`` forces leaf spill:
    converged widths, dropped leaves, and overflow counters must all match
    the single-device engine exactly."""
    ds = make_dataset("fs", n=2500, seed=5)
    index, clusters = _build_index(ds, g=8, levels=3)
    wl = make_workload(ds, m=16, dist="UNI", region_frac=0.2, n_keywords=4, seed=9)
    snap = IndexSnapshot.build(index, ds)
    for max_leaves in (2, clusters.k):
        single = retrieve_workload(
            snap, wl, max_leaves=max_leaves, plan_cache=PlanCache()
        )
        cache = PlanCache()
        sharded = serve_sharded(
            snap, wl.rects, wl.kw_bitmap, max_leaves=max_leaves, plan_cache=cache
        )
        _assert_same(single, sharded, SKR_KEYS)
        # the sharded loop converged to the exact-mode widths
        n_links = snap.n_levels - 1
        assert cache.seeded_plan("skr", n_links).widths == tuple(
            single["frontier_widths"][1:]
        )
    assert serve_sharded(
        snap, wl.rects, wl.kw_bitmap, max_leaves=2, plan_cache=PlanCache()
    )["overflow"].sum() > 0


def test_skr_sharded_reuses_learned_widths():
    """A warm PlanCache serves sharded batches without re-descending: the
    second call must hit the fixed point on its first shard_map dispatch
    (observed maxima never exceed the cached widths)."""
    ds = make_dataset("fs", n=1500, seed=1)
    index, clusters = _build_index(ds, g=6, levels=2)
    wl = make_workload(ds, m=24, dist="MIX", seed=11)
    snap = IndexSnapshot.build(index, ds)
    cache = PlanCache()
    first = serve_sharded(
        snap, wl.rects, wl.kw_bitmap, max_leaves=clusters.k, plan_cache=cache
    )
    learned = dict(cache.widths)
    again = serve_sharded(
        snap, wl.rects, wl.kw_bitmap, max_leaves=clusters.k, plan_cache=cache
    )
    assert dict(cache.widths) == learned
    _assert_same(first, again, SKR_KEYS)


@pytest.mark.parametrize("seed,levels,k,m", [(0, 2, 1, 13), (1, 3, 10, 16), (3, 1, 5, 6)])
def test_knn_sharded_matches_single_device(seed, levels, k, m):
    """kNN twin: identical id sequences, distances, and counters across the
    sharded and single-device bounded descents, ragged batches included."""
    ds = make_dataset("fs", n=1500, seed=seed)
    if levels == 1:
        index = flat_index(ds, _grid_clusters(ds, 5))
    else:
        index, _ = _build_index(ds, g=6, levels=levels)
    wl = make_workload(ds, m=m, dist="MIX", seed=seed + 20)
    points = _points_from(wl)
    snap = IndexSnapshot.build(index, ds)
    single = retrieve_knn(snap, points, wl.kw_bitmap, k, plan_cache=PlanCache())
    sharded = serve_knn_sharded(
        snap, points, wl.kw_bitmap, k, plan_cache=PlanCache()
    )
    assert sharded["ids"].shape == (m, k)
    for key in KNN_KEYS:
        np.testing.assert_array_equal(single[key], sharded[key], err_msg=key)
    # k <= 0 degenerates identically too
    assert serve_knn_sharded(snap, points, wl.kw_bitmap, 0)["ids"].shape == (m, 0)


def test_sharded_pad_queries_are_inert():
    """Padding to n_shards power-of-two buckets (sharded_bucket) must not
    perturb real queries: a 3-query batch padded up to the full mesh width
    returns exactly the unpadded engine's results."""
    ds = make_dataset("fs", n=1200, seed=12)
    index, clusters = _build_index(ds, g=5, levels=2)
    snap = IndexSnapshot.build(index, ds)
    wl = make_workload(ds, m=3, dist="MIX", seed=13)
    single = retrieve_workload(snap, wl, max_leaves=clusters.k, plan_cache=PlanCache())
    sharded = serve_sharded(
        snap, wl.rects, wl.kw_bitmap, max_leaves=clusters.k, plan_cache=PlanCache()
    )
    _assert_same(single, sharded, SKR_KEYS)


# ------------------------- flat leaf-sharded fallback: overflow regression
def _fallback_mesh():
    return make_host_mesh(data=2, model=4)


def _run_fallback(mesh, q_rects, q_bm, leaf_mbrs, leaf_bm, obj, two_stage, cap):
    from functools import partial

    from repro.sharding.rules import default_rules, dp_axes, spec_for
    from jax.sharding import PartitionSpec as P

    rules = default_rules(mesh)
    dp = dp_axes(mesh)
    qspec = spec_for(("query", None), rules)
    lspec = spec_for(("leaf", None), rules)
    ospec = spec_for(("leaf", "obj_slot", "word"), rules)
    fn = shard_map(
        partial(wisk_serve_step, two_stage=two_stage, stage2_cap=cap),
        mesh=mesh,
        in_specs=(qspec, qspec, lspec, lspec, lspec, lspec, ospec, lspec),
        out_specs=(P(dp), P(dp), P(dp)),
        check_vma=False,
    )
    ox, oy, obm, oval = obj
    return jax.jit(fn)(q_rects, q_bm, leaf_mbrs, leaf_bm, ox, oy, obm, oval)


def test_two_stage_overflow_is_surfaced_not_discarded():
    """Regression: ``wisk_serve_step``'s two-stage verify used to drop every
    match beyond ``stage2_cap`` silently (``counts + 0 * overflow``). The
    psum'd overflow is now a first-class output: with every object in-rect
    and keyword-matching, ``counts + overflow`` must reconcile with the
    exhaustive single-stage counts, and the overflow must actually fire."""
    mesh = _fallback_mesh()
    n_model = mesh.shape["model"]
    M = 8 * max(mesh_dp_size(mesh) // 8, 1)
    K = TOP_LEAVES_LOCAL * n_model  # every device keeps TOP_LEAVES_LOCAL leaves
    W = 2
    q_rects = np.tile(np.array([[0.0, 0.0, 1.0, 1.0]], np.float32), (M, 1))
    q_bm = np.ones((M, W), np.uint32)
    leaf_mbrs = np.tile(np.array([[0.0, 0.0, 1.0, 1.0]], np.float32), (K, 1))
    leaf_bm = np.ones((K, W), np.uint32)
    rng = np.random.default_rng(0)
    ox = rng.uniform(0.1, 0.9, (K, OBJ_PER_LEAF)).astype(np.float32)
    oy = rng.uniform(0.1, 0.9, (K, OBJ_PER_LEAF)).astype(np.float32)
    obm = np.ones((K, OBJ_PER_LEAF, W), np.uint32)
    oval = np.ones((K, OBJ_PER_LEAF), np.int8)
    obj = (ox, oy, obm, oval)

    cap = 8
    counts2, scanned2, over2 = map(
        np.asarray, _run_fallback(mesh, q_rects, q_bm, leaf_mbrs, leaf_bm, obj, True, cap)
    )
    counts1, scanned1, over1 = map(
        np.asarray, _run_fallback(mesh, q_rects, q_bm, leaf_mbrs, leaf_bm, obj, False, cap)
    )
    per_dev_total = TOP_LEAVES_LOCAL * OBJ_PER_LEAF
    np.testing.assert_array_equal(counts1, np.full(M, per_dev_total * n_model))
    assert (over2 > 0).all()  # the capacity bound genuinely fired
    np.testing.assert_array_equal(counts2 + over2, counts1)  # nothing silent
    np.testing.assert_array_equal(over1, np.zeros(M, over1.dtype))
    np.testing.assert_array_equal(scanned1, scanned2)


def test_lower_wisk_serve_surfaces_overflow_output():
    """The dry-run lowering of the fallback now exposes three outputs
    (counts, scanned, overflow), all sharded over the data axes."""
    from repro.configs.wisk import WiskServeConfig
    from repro.launch.wisk_serve import lower_wisk_serve

    mesh = _fallback_mesh()
    cfg = WiskServeConfig(n_queries=32, n_nodes=64, vocab=64)
    lowered = lower_wisk_serve(mesh, cfg, two_stage=True)
    compiled = lowered.compile()
    assert len(compiled.output_shardings) == 3
