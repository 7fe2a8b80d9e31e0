"""A cell's set-up: its files, its data, the program under test, warm-up.

Everything here is found by name from ``BENCHMARK.json``: the workload
entry names a configuration (``configs`` entry -> its file) and a traffic
mix (``traffic/<mix>.json``). Nothing in this module is specific to one
cell.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

import store
from gen.synth import make_objects
from gen.traffic import DELETE, INSERT, Objects, check, geofences, inserts, queries, substream
from gen.workloads import make_queries

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


@dataclasses.dataclass
class Spec:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    bench: Dict


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = root / configs[cell["config"]]["file"]
    traffic_path = root / CHIP_DIR.relative_to(ROOT) / "traffic" / f"{cell['traffic']}.json"
    traffic = check(json.loads(traffic_path.read_text()), cell["traffic"])
    return Spec(workload, int(cell["chips"]), json.loads(cfg_path.read_text()), traffic, bench)


def make_collection(config: Dict) -> Objects:
    locs, kw = make_objects(config["data"], int(config["data_seed"]))
    return Objects(locs, kw, int(config["data"]["vocab"]))


# ------------------------------------------------------------------ program
def _build_config(b: Dict):
    from repro.core.build import BuildConfig
    from repro.core.dqn import DQNConfig
    from repro.core.packing import PackingConfig
    from repro.core.partition import PartitionConfig

    cfg = BuildConfig(
        partition=PartitionConfig(**b["partition"]),
        packing=PackingConfig(dqn=DQNConfig(), **b["packing"]),
    )
    for k, v in b.items():
        if k not in ("partition", "packing"):
            setattr(cfg, k, v)
    return cfg


def index_artifacts(spec: Spec, objs: Objects, say: Callable, store_dir: Path = store.STORE_DIR):
    """The program's ``BuildArtifacts`` for this configuration: loaded from
    the store, or built (and stored) on a checkout's first run. Either way
    the served layout is the one restored from the stored assignment."""
    from repro.core.build import BuildArtifacts, build_wisk
    from repro.core.index import assemble_index
    from repro.core.packing import HierarchyResult
    from repro.core.types import ClusterSet, GeoTextDataset, Workload

    t0 = time.perf_counter()
    ds = GeoTextDataset.from_ids(objs.locs, objs.kw_ids, objs.vocab)
    key = store.entry_key(spec.config, store.tree_hash(ROOT / "src" / "repro"))
    path = store_dir / f"{spec.config['name']}-{key}.npz"
    got = store.load(path)
    built = got is None
    if built:
        tw = spec.config["train_workload"]
        rects, kw = make_queries(
            objs.locs, objs.kw_ids, objs.vocab, int(tw["m"]), dist=tw["dist"],
            region_frac=tw["region_frac"], n_keywords=tw["n_keywords"], seed=int(tw["seed"]),
        )
        art = build_wisk(ds, Workload.from_ids(rects, kw, objs.vocab), _build_config(spec.config["build"]))
        parents = list(art.hierarchy.parents) if art.hierarchy is not None else []
        info = {"timings": art.timings, "counters": art.counters}
        store.save(path, art.index.clusters.assign, parents, info)
        got = (art.index.clusters.assign, parents, info)
    assign, parents, info = got
    say("index_store", entry=path.name, built=built,
        build_s=f"{info['timings'].get('total', 0.0):.3f}" if built else "stored",
        t_s=f"{time.perf_counter() - t0:.3f}")
    clusters = ClusterSet.from_assignment(ds, assign)
    hier = HierarchyResult(parents=list(parents), level_labels=[], packs=[]) if parents else None
    index = assemble_index(ds, clusters, hier, meta={"restored": True})
    art = BuildArtifacts(index=index, bank=None, partition=None, hierarchy=hier,
                         timings=info["timings"], counters=info["counters"])
    say("index_restored", t_s=f"{time.perf_counter() - t0:.3f}")
    return ds, art, built, float(info["timings"].get("total", 0.0))


class ProgramServer:
    """The system under test: ``LiveIndex`` on one chip."""

    def __init__(self, spec: Spec, objs: Objects, say: Callable,
                 store_dir: Path = store.STORE_DIR) -> None:
        from repro.launch.wisk_serve import LiveIndex

        ds, art, self.built, self.build_s = index_artifacts(spec, objs, say, store_dir)
        t0 = time.perf_counter()
        self.live = LiveIndex(ds, None, artifacts=art,
                              slots_per_leaf=int(spec.config["serving"]["slots_per_leaf"]))
        say("live_index", t_s=f"{time.perf_counter() - t0:.3f}")
        self.max_leaves = int(spec.traffic["max_leaves"])
        self.vocab = objs.vocab

    def bitmaps(self, kw_ids: np.ndarray) -> np.ndarray:
        from repro.core.types import ids_to_bitmap

        return ids_to_bitmap(np.asarray(kw_ids, np.int32), self.vocab)

    def serve_skr(self, rects, bms):
        return self.live.serve(rects, bms, max_leaves=self.max_leaves)

    def skr_rows(self, out, m: int):
        from loop import skr_rows

        return skr_rows(out["ids"], m)

    def serve_knn(self, points, bms, k: int):
        return self.live.serve_knn(points, bms, k)

    def insert(self, locs, kw):
        return self.live.insert(locs, kw)

    def delete(self, ids):
        return self.live.delete(ids)

    def drain(self):
        return self.live.drain_notifications()

    def subscribe(self, rect, kw) -> int:
        return self.live.subscribe(rect, kw)

    def describe(self) -> Dict:
        from repro.kernels import ops

        gen = self.live.generation
        snap = gen.snapshot
        compact = snap.has_compact_bank
        words = snap.n_compact_words if compact else snap.n_words
        delta = gen.delta()
        return dict(
            levels=[int(m.shape[0]) for m in snap.level_mbrs], leaves=snap.n_leaves,
            obj_per_leaf=snap.obj_per_leaf, words=snap.n_words,
            compact_words=snap.n_compact_words if compact else "none",
            narrow_planes=snap.has_narrow_planes,
            fused_verify=ops.pick_fused_variant(snap.n_leaves, snap.obj_per_leaf, words, compact),
            delta_slots=delta.slots_per_leaf if delta is not None else "none",
            delta_compact=gen.delta_log.compact_ok,
            delta_fill_max=int(gen.delta_log._fill.max()) if gen.delta_log._fill.size else 0,
            plan_widths={f"{t}{l}": w for (t, l), w in sorted(gen.plan_cache.widths.items())},
        )


# ------------------------------------------------------------------ set-up
@dataclasses.dataclass
class Setup:
    """What set-up did to the served state before the window: the geofences
    it registered and the updates it applied, in order."""

    fence_rects: np.ndarray
    fence_kws: List[np.ndarray]
    fence_ids: List[int]
    updates: List  # ("insert", locs, kw, acked id, notes) / ("delete", id, acked count)
    inserted: int = 0
    deleted: set = dataclasses.field(default_factory=set)
    live_inserted: List[int] = dataclasses.field(default_factory=list)


def prepare(server, spec: Spec, objs: Objects, seed: int, seconds: float, say=None) -> Setup:
    """Register the run's geofences (from ``seed``) and preload the delta
    backlog (from the configuration's ``data_seed``: the same served state
    on every seed), one update per call as the window sends them."""
    from gen.traffic import backlog_size, split_counts

    t0 = time.perf_counter()
    rects, kws = geofences(objs, spec.traffic, substream(seed, 6))
    ids = [int(server.subscribe(rects[s], kws[s])) for s in range(rects.shape[0])]
    st = Setup(rects, kws, ids, [])
    n_back = backlog_size(spec.traffic, seconds)
    if n_back == 0:
        return st
    n_ins, n_del = split_counts(2 * n_back, {"skr": 0, "knn": 0, "insert": 0.5, "delete": 0.5})[INSERT:]
    bseed = substream(int(spec.config["data_seed"]), 7)
    locs, kw = inserts(objs, spec.traffic, n_ins, bseed)
    rng = np.random.default_rng(bseed)
    order = np.repeat([INSERT, DELETE], [n_ins, n_del])
    rng.shuffle(order)
    live_ins = st.live_inserted
    share = float(spec.traffic["delete_inserted_share"])
    for k in order:
        if k == INSERT:
            i = st.inserted
            got = int(np.asarray(server.insert(locs[i : i + 1], kw[i : i + 1])).reshape(-1)[0])
            notes = np.asarray(server.drain(), np.int64).reshape(-1, 2)
            st.updates.append(("insert", locs[i], kw[i], got, notes))
            live_ins.append(objs.n + i)
            st.inserted += 1
        else:
            if live_ins and rng.uniform() < share:
                oid = live_ins.pop(int(rng.integers(len(live_ins))))
            else:
                oid = int(rng.integers(objs.n))
                while oid in st.deleted:
                    oid = int(rng.integers(objs.n))
            st.deleted.add(oid)
            st.updates.append(("delete", oid, int(server.delete(np.array([oid])))))
    if say:
        say("backlog", updates=len(st.updates), t_s=f"{time.perf_counter() - t0:.3f}")
    return st


def warm_up(server, objs: Objects, spec: Spec, kinds: List[str], compiles: Callable,
            say: Callable, max_rounds: int = 4) -> int:
    """Serve warm-up queries (from the configuration's ``data_seed``, never
    the run's seed) until a whole round compiles nothing. A round serves,
    for each kind the traffic sends, batches of each size 64/32/16/8/1 in
    each class of packed keyword words (the engine buckets a batch's widest
    query), after ``warmup_batches`` full batches that let the frontier
    width cache settle. Returns the rounds served."""
    t = spec.traffic
    k = int(t["knn_k"])
    mb = int(t["max_batch"])
    wseed = substream(int(spec.config["data_seed"]), 8)
    n_warm = int(t["warmup_batches"]) * mb
    rects, kw = queries(objs, t, n_warm, wseed)
    bms = server.bitmaps(kw)
    pts = np.stack([(rects[:, 0] + rects[:, 2]) / 2, (rects[:, 1] + rects[:, 3]) / 2], 1)
    pts = pts.astype(np.float32)
    nnz = (bms != 0).sum(axis=1)
    cls = np.maximum(4, 2 ** np.ceil(np.log2(np.maximum(nnz, 1)))).astype(int)

    def call(kind, idx):
        if kind == "skr":
            server.serve_skr(rects[idx], bms[idx])
        else:
            server.serve_knn(pts[idx], bms[idx], k)

    t0 = time.perf_counter()
    for kind in kinds:
        for b in range(0, n_warm, mb):
            call(kind, np.arange(b, b + mb))
    say("warmup_full_batches", batches=n_warm // mb, t_s=f"{time.perf_counter() - t0:.3f}")
    sizes = [s for s in (64, 32, 16, 8, 1) if s <= mb]
    for rnd in range(1, max_rounds + 1):
        before = compiles()
        for kind in kinds:
            for c in np.unique(cls):
                low = np.flatnonzero(cls < c)
                top = np.flatnonzero(cls == c)
                for s in sizes:
                    idx = np.concatenate([top[:1], low[: s - 1]])
                    if idx.size < s:
                        idx = np.concatenate([idx, top[1 : 1 + s - idx.size]])
                    call(kind, idx)
        new = compiles() - before
        say("warmup_round", round=rnd, programs_lowered=new, t_s=f"{time.perf_counter() - t0:.3f}")
        if new == 0:
            return rnd
    return max_rounds
