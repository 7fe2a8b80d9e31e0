"""End-to-end smoke run of the LiveIndex serving path on a TPU.

    python chip_smoke.py             # one chip: SKR, kNN, live updates, geofences
    python chip_smoke.py --chips 4   # four chips: index- and query-parallel serving

One process; it exits non-zero (and prints no result line) unless JAX's
first device is a TPU. Data comes from ``--seed``: the ``osm`` synthetic
profile at its own size (120,000 objects, an 8,192-term vocabulary = 256
bitmap words, up to 5 keywords per object), a 256-query MIX training
workload, and held-out MIX query batches. The index is built through
``LiveIndex`` with the benchmark's bounded build settings
(``benchmarks.common.small_build_config``).

One chip: SKR and Boolean-kNN batches before and after a live update
(inserts + deletes) with standing geofence subscriptions, every result
compared id for id with the plain host references (``execute_serial``,
``knn_query``, ``match_subscriptions_bruteforce``). Four chips
(``--chips 4``): ``LiveIndex(index_shards=4)`` on a 1x4 (data, index) mesh
and ``serve_sharded`` / ``serve_knn_sharded`` on a 4x1 mesh, compared with
single-device ``retrieve`` / ``retrieve_knn`` on device 0.

Every phase prints its wall time with compile time kept apart; any
mismatch or exception ends the run with a non-zero exit. The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_FOUR_CHIP = 30_000  # object count of the four-chip check (see _four_chip)
BATCH = 64  # queries per served batch
N_BATCHES = 3
KNN_K = 10
N_SUBS = 32
N_INSERT = 256
N_DELETE = 128


_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(RuntimeError):
    """A served result disagreed with its reference."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


class CompileClock:
    """Seconds JAX spent lowering and compiling programs, from its own
    monitoring events -- subtracted from a phase's wall time to give the
    steady time. (Tracing events nest, one per inner ``jit``, so they are
    left out rather than counted twice.)"""

    def __init__(self) -> None:
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.total += duration


class Phase:
    """Context manager printing a phase's wall, compile and steady seconds."""

    def __init__(self, clock: CompileClock, name: str, **fields) -> None:
        self.clock, self.name, self.fields = clock, name, fields

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, *exc) -> None:
        if exc[0] is None:
            wall = time.perf_counter() - self.t0
            comp = self.clock.total - self.c0
            say(self.name, wall_s=f"{wall:.3f}", compile_s=f"{comp:.3f}",
                steady_s=f"{max(wall - comp, 0.0):.3f}", **self.fields)


# ----------------------------------------------------------------- data
def make_data(seed: int, n):
    """Dataset, training workload and held-out test workload from ``seed``."""
    from repro.data.synth import make_dataset
    from repro.data.workloads import make_workload

    ds = make_dataset("osm", n=n, seed=seed)
    train = make_workload(ds, m=256, dist="MIX", seed=seed + 1)
    test = make_workload(ds, m=BATCH * N_BATCHES, dist="MIX", seed=seed + 2)
    return ds, train, test


def centers(rects):
    import numpy as np

    return np.stack(
        [(rects[:, 0] + rects[:, 2]) / 2, (rects[:, 1] + rects[:, 3]) / 2], 1
    ).astype(np.float32)


def batches(m: int):
    return [slice(b, min(b + BATCH, m)) for b in range(0, m, BATCH)]


# ------------------------------------------------------------ references
def skr_reference(index, ds, wl):
    from repro.core.query import execute_serial

    return execute_serial(index, ds, wl).results


def knn_reference(index, ds, points, bms, k):
    from repro.core.query import knn_query

    return [knn_query(index, ds, points[i], bms[i], k).ids for i in range(len(points))]


def compare_skr(tag, out, want, rows):
    import numpy as np

    check(int(np.max(out["overflow"], initial=0)) == 0, f"{tag}: a query spilled max_leaves")
    for j, qi in enumerate(range(rows.start, rows.stop)):
        got = np.sort(out["ids"][j][out["ids"][j] >= 0])
        check(np.array_equal(got, np.sort(want[qi])), f"{tag}: query {qi} ids differ from reference")
        check(int(out["counts"][j]) == got.size, f"{tag}: query {qi} count != ids")
    return rows.stop - rows.start


def compare_knn(tag, out, want, rows):
    import numpy as np

    for j, qi in enumerate(range(rows.start, rows.stop)):
        got = out["ids"][j][out["ids"][j] >= 0]
        check(np.array_equal(got, want[qi]), f"{tag}: query {qi} kNN ids differ from reference")
    return rows.stop - rows.start


# ------------------------------------------------------------ one chip
def _one_chip(seed: int, clock: CompileClock, n=None) -> None:
    import numpy as np

    from benchmarks.common import small_build_config
    from repro.core.index import flat_index
    from repro.core.query import match_subscriptions_bruteforce
    from repro.core.types import ClusterSet
    from repro.data.workloads import make_workload
    from repro.kernels import ops
    from repro.launch.wisk_serve import LiveIndex

    with Phase(clock, "data", profile="osm", seed=seed):
        ds, train, test = make_data(seed, n)
    say("data_shape", objects=ds.n, vocab=ds.vocab_size, words=ds.words,
        train_queries=train.m, test_queries=test.m)

    with Phase(clock, "build"):
        live = LiveIndex(ds, train, build_config=small_build_config())
    gen = live.generation
    say("build_timings", **{k: f"{v:.3f}" for k, v in gen.artifacts.timings.items()})
    snap = gen.snapshot
    K, OBJ = snap.n_leaves, snap.obj_per_leaf
    compact = snap.has_compact_bank
    n_words = snap.n_compact_words if compact else snap.n_words
    variant = ops.pick_fused_variant(K, OBJ, n_words, compact)
    say("index", levels=snap.n_levels, leaves=K, obj_per_leaf=OBJ, words=snap.n_words,
        compact_words=snap.n_compact_words if compact else "none",
        narrow_planes=snap.has_narrow_planes)
    say("fused_verify", variant=variant, compact=compact,
        leaf_bank_bytes=ops.leaf_bank_bytes(K, OBJ, snap.n_words),
        compact_leaf_bank_bytes=(ops.compact_leaf_bank_bytes(K, OBJ, snap.n_compact_words)
                                 if compact else "none"),
        resident_vmem_bytes=ops.resident_bank_vmem_bytes(K, OBJ, n_words, 4 if compact else 3),
        vmem_cutoff=ops.FUSED_VMEM_BANK_BYTES)

    pts = centers(test.rects)
    with Phase(clock, "reference_static", queries=test.m):
        skr_want = skr_reference(gen.artifacts.index, ds, test)
        knn_want = knn_reference(gen.artifacts.index, ds, pts, test.kw_bitmap, KNN_K)

    n_ok = 0
    for b, rows in enumerate(batches(test.m)):
        with Phase(clock, f"skr_batch{b}", queries=rows.stop - rows.start):
            out = live.serve(test.rects[rows], test.kw_bitmap[rows], max_leaves=K)
        n_ok += compare_skr("skr", out, skr_want, rows)
    say("parity_skr", exact=n_ok, of=test.m)
    n_ok = 0
    for b, rows in enumerate(batches(test.m)):
        with Phase(clock, f"knn_batch{b}", queries=rows.stop - rows.start, k=KNN_K):
            out = live.serve_knn(pts[rows], test.kw_bitmap[rows], KNN_K)
        n_ok += compare_knn("knn", out, knn_want, rows)
    say("parity_knn", exact=n_ok, of=test.m)

    # live updates: jittered copies of existing objects arrive while
    # geofences around some of them stand; then some objects are deleted
    rng = np.random.default_rng(seed + 3)
    src = rng.choice(ds.n, N_INSERT, replace=False)
    locs = np.clip(ds.locs[src] + rng.normal(0, 0.01, (N_INSERT, 2)), 0, 1).astype(np.float32)
    kw = ds.kw_ids[src]
    sub_rects, sub_kws = [], []
    for s in range(N_SUBS):
        c = locs[rng.integers(N_INSERT)]
        half = rng.uniform(0.01, 0.08)
        sub_rects.append(np.clip([c[0] - half, c[1] - half, c[0] + half, c[1] + half], 0, 1))
        pick = kw[rng.integers(N_INSERT)]
        sub_kws.append(pick[pick >= 0][: 1 + s % 2])
    sub_rects = np.asarray(sub_rects, np.float32)
    with Phase(clock, "subscribe", subscriptions=N_SUBS):
        sids = [live.subscribe(sub_rects[s], sub_kws[s]) for s in range(N_SUBS)]
    with Phase(clock, "insert", objects=N_INSERT):
        new_ids = live.insert(locs, kw)
    dels = np.concatenate([rng.choice(ds.n, N_DELETE, replace=False), new_ids[:8]])
    with Phase(clock, "delete", objects=dels.size):
        n_del = live.delete(dels)
    check(n_del == dels.size, f"delete: {n_del} of {dels.size} ids deleted")
    with Phase(clock, "drain"):
        notes = live.drain_notifications()
    match = match_subscriptions_bruteforce(locs, kw, sub_rects, sub_kws)
    oi, sj = np.nonzero(match)
    want = np.stack([new_ids[oi], np.asarray(sids, np.int64)[sj]], 1).reshape(-1, 2)
    want = want[np.lexsort((want[:, 1], want[:, 0]))]
    check(np.array_equal(np.asarray(notes, np.int64), want),
          f"notifications: {len(notes)} drained, {len(want)} expected, or pairs differ")
    say("parity_notifications", exact=len(want), of=len(want))

    log = live.generation.delta_log
    merged = log.merged_dataset()
    flat = flat_index(merged, ClusterSet.from_assignment(merged, log.merged_assignment()))
    live_wl = make_workload(merged, m=BATCH * N_BATCHES, dist="MIX", seed=seed + 4)
    live_pts = centers(live_wl.rects)
    with Phase(clock, "reference_live", queries=live_wl.m):
        skr_want = skr_reference(flat, merged, live_wl)
        knn_want = knn_reference(flat, merged, live_pts, live_wl.kw_bitmap, KNN_K)
    n_ok = 0
    for b, rows in enumerate(batches(live_wl.m)):
        with Phase(clock, f"skr_live_batch{b}", queries=rows.stop - rows.start):
            out = live.serve(live_wl.rects[rows], live_wl.kw_bitmap[rows], max_leaves=K)
        n_ok += compare_skr("skr_live", out, skr_want, rows)
    say("parity_skr_live", exact=n_ok, of=live_wl.m)
    n_ok = 0
    for b, rows in enumerate(batches(live_wl.m)):
        with Phase(clock, f"knn_live_batch{b}", queries=rows.stop - rows.start, k=KNN_K):
            out = live.serve_knn(live_pts[rows], live_wl.kw_bitmap[rows], KNN_K)
        n_ok += compare_knn("knn_live", out, knn_want, rows)
    say("parity_knn_live", exact=n_ok, of=live_wl.m)


# ----------------------------------------------------------- four chips
def _device_sets(tree) -> str:
    """min/max ``sharding.device_set`` size over a placed pytree's arrays."""
    import jax

    sizes = [len(x.sharding.device_set) for x in jax.tree_util.tree_leaves(tree)]
    return f"{min(sizes)}..{max(sizes)}/{len(sizes)}arrays"


def _four_chip(seed: int, clock: CompileClock, n=None) -> None:
    import jax
    import numpy as np

    from benchmarks.common import small_build_config
    from repro.core.build import build_wisk
    from repro.launch import wisk_serve as ws
    from repro.serve.engine import retrieve, retrieve_knn
    from repro.serve.plan import PlanCache

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, JAX found {len(jax.devices())}")
    n = N_FOUR_CHIP if n is None else n
    say("scale_cut", objects=n, of=120_000,
        reason="the four-chip check compares layouts and its chip time bills four-fold")
    with Phase(clock, "data", profile="osm", seed=seed):
        ds, train, test = make_data(seed, n)
    # stop packing levels while the root forest still has several nodes:
    # index-parallel serving cuts the roots into one subtree group per shard
    cfg = small_build_config()
    cfg.packing = dataclasses.replace(cfg.packing, min_nodes=8)
    with Phase(clock, "build", packing_min_nodes=8):
        art = build_wisk(ds, train, cfg)
        live = ws.LiveIndex(ds, train, artifacts=art, index_shards=4)
    snap = live.generation.snapshot
    K = snap.n_leaves
    pts = centers(test.rects)
    dev0 = jax.devices()[0]
    with Phase(clock, "single_device"):
        snap0 = jax.device_put(snap, dev0)
        ref_skr = retrieve(snap0, test.rects, test.kw_bitmap, max_leaves=K, plan_cache=PlanCache())
        ref_knn = retrieve_knn(snap0, pts, test.kw_bitmap, KNN_K, plan_cache=PlanCache())
    say("single_device_placement", devices=_device_sets(snap0))

    def same(tag, out, ref, keys, ids_as_sets):
        for key in keys:
            check(np.array_equal(np.asarray(out[key]), np.asarray(ref[key])), f"{tag}: {key} differs")
        for qi in range(test.m):
            got, want = out["ids"][qi], ref["ids"][qi]
            got, want = got[got >= 0], want[want >= 0]
            if ids_as_sets:
                got, want = np.sort(got), np.sort(want)
            check(np.array_equal(got, want), f"{tag}: query {qi} ids differ from one device")
        say(f"parity_{tag}", exact=test.m, of=test.m, counters=",".join(keys))

    skr_keys = ("counts", "nodes_checked", "verified", "overflow")
    knn_keys = ("dist2", "nodes_checked", "verified", "leaves_verified", "pruned")
    with Phase(clock, "index_parallel_skr", mesh="1x4(data,index)"):
        out = live.serve(test.rects, test.kw_bitmap, max_leaves=K)
    mesh = ws.default_index_mesh(4)
    say("index_parallel_placement", mesh=dict(mesh.shape),
        partition=_device_sets(ws._placed(live.generation.partitioned, mesh)))
    same("index_parallel_skr", out, ref_skr, skr_keys, ids_as_sets=True)
    with Phase(clock, "index_parallel_knn", mesh="1x4(data,index)"):
        out = live.serve_knn(pts, test.kw_bitmap, KNN_K)
    same("index_parallel_knn", out, ref_knn, knn_keys, ids_as_sets=False)

    qmesh = ws.default_serving_mesh()
    with Phase(clock, "query_parallel_skr", mesh="4x1(data,model)"):
        out = ws.serve_sharded(snap, test.rects, test.kw_bitmap, max_leaves=K,
                               mesh=qmesh, plan_cache=PlanCache())
    say("query_parallel_placement", mesh=dict(qmesh.shape),
        snapshot=_device_sets(ws._replicated(snap, qmesh)))
    same("query_parallel_skr", out, ref_skr, skr_keys, ids_as_sets=False)
    with Phase(clock, "query_parallel_knn", mesh="4x1(data,model)"):
        out = ws.serve_knn_sharded(snap, pts, test.kw_bitmap, KNN_K, mesh=qmesh,
                                   plan_cache=PlanCache())
    same("query_parallel_knn", out, ref_knn, knn_keys, ids_as_sets=False)


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of the data and workloads")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: the sharded paths only")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is {dev.platform}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import place_compile_cache

    cache = Path(place_compile_cache())
    say("device", platform=dev.platform, kind=repr(dev.device_kind), count=len(jax.devices()),
        jax=jax.__version__, compile_cache=cache,
        cache_entries_at_start=len(list(cache.iterdir())) if cache.is_dir() else 0)
    clock = CompileClock()
    t0 = time.perf_counter()
    (_four_chip if args.chips == 4 else _one_chip)(args.seed, clock)
    stats = dev.memory_stats() or {}
    say("total", wall_s=f"{time.perf_counter() - t0:.3f}", compile_s=f"{clock.total:.3f}",
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
