"""WISK serving on the production mesh (DESIGN.md §3.4).

Three distribution regimes share this front door:

* **Query-parallel, replicated index** (``serve_sharded`` /
  ``serve_knn_sharded``) -- the default and the throughput-scaling path.
  The ``IndexSnapshot`` pytree is replicated over the mesh with one
  ``device_put`` (``snapshot.replicate``); the query batch is padded to
  per-shard power-of-two buckets and sharded over the data axes; and the
  REAL hierarchical engine -- the frontier SKR descent and the
  distance-bounded kNN descent of serve/engine.py -- runs per shard inside
  ``shard_map``, returning per-query result ids and Eq.1 cost counters
  (identical to the single-device engine, pinned by
  tests/test_sharded_parity.py). Frontier widths cannot block on per-level
  host syncs inside a traced region, so the sharded path runs at
  ``PlanCache.seeded_plan`` widths, cross-shard-maxes the observed per-level
  child counts (``lax.pmax``), and loops grow-and-redescend to the fixed
  point -- lossless for the same reason the §3.2 overflow retry is, and
  sync-free in steady state.

* **Index-parallel, partitioned hierarchy** (``serve_index_sharded`` /
  ``serve_knn_index_sharded``) -- the big-index path. A
  ``PartitionedSnapshot`` (serve/snapshot.py) cuts the root forest into
  balanced shard-local sub-hierarchies placed over the serving mesh's
  ``index`` axis (~1/S of the index bytes per device); each shard runs the
  same engine descent from its masked local root frontier, and per-query
  results are combined by collectives -- an id-union + psum'd Eq.1 counters
  for SKR, a global top-k merge with bound exchange for kNN. Composes with
  query parallelism on the 2D ``(query, index)`` mesh
  (``mesh.make_serving_mesh``); exact id/counter parity with the
  single-device engine is pinned by tests/test_index_sharded_parity.py.

* **Legacy flat fallback** (launch/flat_legacy.py; ``wisk_serve_step`` /
  ``lower_wisk_serve`` re-exported here) -- the retired hierarchy-free
  leaf-sharded scan, kept as the dry-run/roofline lowering surface and the
  A/B floor.

On top of these regimes sits the incremental-maintenance front door
(DESIGN.md §7): ``LiveIndex`` buffers object inserts/deletes in a
``DeltaBuffer`` merged into every descent (routed to the owning shards in
the index-parallel regime via ``delta.partition_delta``), watches workload
drift through the observed Eq.1 counters, and atomically swaps in
warm-start rebuilds as new ``ServingGeneration``s while in-flight batches
finish on the old one. ``LiveIndex`` also fronts the continuous-filter
pub-sub subsystem (DESIGN.md §8, serve/subscribe.py): standing
spatio-textual subscriptions compiled into a device-resident block, every
insert batch matched against it in the same step, notifications drained
exactly once -- subscription state survives generation swaps. Every front
door here is host-side orchestration around the jit-traced engine paths of
serve/engine.py.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from .. import obs
from ..kernels import ops
from ..serve.delta import DeltaBuffer, DeltaLog, partition_delta
from ..serve.engine import (
    IndexSnapshot,
    _descend_frontier,
    _descend_knn,
    _descend_knn_indexed,
    _local_root_frontier,
    _select_leaves_frontier,
    _select_leaves_indexed,
    _snap_cbank,
    _verify_leaves,
    retrieve,
    retrieve_knn,
    round_up_bucket,
)
from ..serve.plan import (
    ExecutionPlan,
    PlanCache,
    default_plan_cache,
    pad_knn_queries_to_bucket,  # noqa: F401  (re-export: historical home)
    pad_queries_to_bucket,  # noqa: F401  (re-export: historical home)
)
from ..serve.snapshot import PartitionedSnapshot
from ..serve.subscribe import SubscriptionIndex
from ..sharding.rules import default_rules, dp_axes, spec_for
from .mesh import make_host_mesh, make_serving_mesh


# --------------------------------------------------- single-device front door
def serve_batch(
    snap: IndexSnapshot,
    q_rects,
    q_bm,
    max_leaves: int = 32,
    mode: str = "frontier",
    minimum_bucket: int = 8,
    plan_cache: Optional[PlanCache] = None,
    delta: Optional[DeltaBuffer] = None,
    fused: Optional[bool] = None,
    compact: Optional[bool] = None,
):
    """Bucketed front door for the batched SKR engine (host-side wrapper).

    Args:
        snap: the served ``IndexSnapshot``.
        q_rects: (m, 4) f32 query rectangles ``(xlo, ylo, xhi, yhi)``.
        q_bm: (m, W) u32 query keyword bitmaps.
        max_leaves: per-query verification capacity (spill -> ``overflow``).
        mode: ``"frontier"`` (sparse descent) or ``"dense"`` (A/B scan).
        minimum_bucket: smallest power-of-two batch bucket.
        plan_cache: frontier width state (None: per-snapshot default).
        delta: optional ``DeltaBuffer`` of buffered inserts/deletes merged
            on the fly (DESIGN.md §7).
        fused: leaf verification path -- None (default) runs the fused
            gather+verify kernel on the base leaf blocks even with a live
            delta (only the insert-buffer slots take the unfused merge);
            False forces the wholesale unfused baseline (DESIGN.md §3.5).
        compact: leaf verification width -- None (default) verifies on the
            leaf-local compact vocabulary bank when the snapshot carries
            one; False forces the global full-width slab (DESIGN.md §3.5).

    Pads the batch to its power-of-two bucket with inert pad queries, runs
    the jit-traced ``retrieve`` descent, and slices the pads back off the
    per-query outputs. Returns ``retrieve``'s dict (``ids`` (m, C) i32 with
    ``-1`` fill, ``counts``, Eq.1 counters); only the pad/slice runs on
    host.
    """
    with obs.span("wisk.prep"):
        rects, bms, m = pad_queries_to_bucket(q_rects, q_bm, minimum_bucket)
    obs.count("skr.rows", m)
    obs.count("skr.pad_rows", rects.shape[0] - m)
    out = retrieve(
        snap, rects, bms, max_leaves, mode=mode,
        plan_cache=plan_cache, delta=delta, fused=fused, compact=compact,
    )
    per_query = ("ids", "counts", "nodes_checked", "nodes_scanned", "verified", "overflow")
    return {k: (v[:m] if k in per_query else v) for k, v in out.items()}


def serve_knn_batch(
    snap: IndexSnapshot,
    points,
    q_bm,
    k: int,
    minimum_bucket: int = 8,
    plan_cache: Optional[PlanCache] = None,
    delta: Optional[DeltaBuffer] = None,
    knn_dtype: str = "f32",
    compact: Optional[bool] = None,
):
    """Bucketed front door for batched Boolean kNN: pad -> retrieve -> slice.

    Args:
        snap: the served ``IndexSnapshot``.
        points: (m, 2) f32 query points in the unit square.
        q_bm: (m, W) u32 query keyword bitmaps.
        k: neighbors per query -- a *static* argument (each served k
            compiles its own descent; the workload classes of LIST-style
            top-k serving are few and fixed).
        minimum_bucket: smallest power-of-two batch bucket.
        plan_cache: frontier width state (None: per-snapshot default).
        delta: optional ``DeltaBuffer`` merged on the fly (DESIGN.md §7).
        knn_dtype: ``"f32"`` (exact) or ``"bf16"`` -- reduced-precision
            bounded-sweep pruning with a conservative exact-f32 retry; ids
            are always identical to f32 (see ``retrieve_knn``).
        compact: leaf keyword-test width -- None (default) uses the compact
            leaf bank when available; False forces full width (§3.5).

    Returns ``retrieve_knn``'s dict: ``ids``/``dist2`` (m, k) ascending by
    (dist^2, id) with ``-1`` fill, plus Eq.1 counters, pads sliced off.
    Host-side wrapper around the jit-traced descent.
    """
    with obs.span("wisk.prep"):
        pts, bms, m = pad_knn_queries_to_bucket(points, q_bm, minimum_bucket)
    out = retrieve_knn(
        snap, pts, bms, k, plan_cache=plan_cache,
        delta=delta, knn_dtype=knn_dtype, compact=compact,
    )
    per_query = ("ids", "dist2", "nodes_checked", "verified", "leaves_verified", "pruned")
    return {key: (v[:m] if key in per_query else v) for key, v in out.items()}


# ------------------------------- micro-batching + hot-query cache (§3.5)
class HotQueryCache:
    """LRU result cache for repeated ("hot") SKR queries (DESIGN.md §3.5).

    Keys are ``(rect quantized to a 1/quant grid, bitmap bytes)``: real query
    streams repeat popular (region, keyword) probes near-verbatim, and
    quantizing the rectangle folds jittered re-issues of the same probe onto
    one entry. Quantization only affects the KEY -- the cached value is the
    engine's exact output for the first query that produced it, so hits are
    exact for re-issues that quantize identically. ``hits``/``misses``
    counters feed capacity tuning; ``invalidate()`` drops everything and
    must be called whenever served state changes (delta update, generation
    swap) -- ``LiveIndex`` does this automatically.
    """

    def __init__(self, maxsize: int = 1024, quant: float = 4096.0) -> None:
        from collections import OrderedDict

        self.maxsize = int(maxsize)
        self.quant = float(quant)
        self._entries: "OrderedDict" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.invalidations = 0

    def key(self, rect, bm) -> bytes:
        q = np.rint(np.asarray(rect, np.float64) * self.quant).astype(np.int64)
        return q.tobytes() + np.asarray(bm, np.uint32).tobytes()

    def get(self, rect, bm):
        """The cached per-query result dict, or None (counts a hit/miss)."""
        got = self._entries.get(self.key(rect, bm))
        if got is None:
            self.misses += 1
            return None
        self._entries.move_to_end(self.key(rect, bm))
        self.hits += 1
        return got

    def put(self, rect, bm, result) -> None:
        k = self.key(rect, bm)
        self._entries[k] = result
        self._entries.move_to_end(k)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)

    def invalidate(self) -> None:
        """Drop every entry (served state changed: delta update or swap)."""
        self._entries.clear()
        self.invalidations += 1

    def __len__(self) -> int:
        return len(self._entries)


_PER_QUERY_SKR = ("ids", "counts", "nodes_checked", "nodes_scanned", "verified", "overflow")


def serve_batch_cached(
    snap: IndexSnapshot,
    q_rects,
    q_bm,
    cache: HotQueryCache,
    max_leaves: int = 32,
    **serve_kw,
) -> Dict[str, np.ndarray]:
    """``serve_batch`` behind a ``HotQueryCache``: serve only the misses.

    Looks every query up in ``cache``, runs ONE ``serve_batch`` over the
    misses, fills the cache with their per-query rows, and reassembles the
    batch in submission order. Returns ``serve_batch``'s dict plus a
    ``cached`` (m,) bool mask (True = row came from the cache -- callers
    feeding observed-cost telemetry, e.g. the drift monitor, must restrict
    to ``~cached`` rows or hot traffic looks free). ``ids`` rows are padded
    to the batch's widest capacity with ``-1`` (capacity can grow between
    batches as the plan cache learns)."""
    rects = np.asarray(q_rects, np.float32).reshape(-1, 4)
    bms = np.asarray(q_bm, np.uint32).reshape(len(rects), -1)
    m = len(rects)
    entries = [cache.get(rects[i], bms[i]) for i in range(m)]
    cached = np.array([e is not None for e in entries], bool)
    miss = np.flatnonzero(~cached)
    if miss.size:
        out = serve_batch(snap, rects[miss], bms[miss], max_leaves, **serve_kw)
        for j, i in enumerate(miss):
            entry = {k: np.asarray(out[k])[j] for k in _PER_QUERY_SKR}
            cache.put(rects[i], bms[i], entry)
            entries[i] = entry
    width = max((e["ids"].shape[0] for e in entries), default=0)

    def _row(e, k):
        v = e[k]
        if k == "ids" and v.shape[0] < width:
            v = np.concatenate([v, np.full(width - v.shape[0], -1, v.dtype)])
        return v

    result = {k: np.stack([_row(e, k) for e in entries]) for k in _PER_QUERY_SKR}
    result["cached"] = cached
    return result


class MicroBatcher:
    """Deadline-free micro-batching for the SKR front door (DESIGN.md §3.5).

    Coalesces singleton queries into one bucketed ``serve_batch`` dispatch.
    There is NO timer and NO deadline: ``submit`` enqueues and returns a
    ticket; the batch runs when the caller calls ``flush()`` (or
    automatically once ``flush_at`` queries are pending -- the knob). That
    keeps the policy in the caller's event loop, where the repo's serving
    stack keeps all control flow, instead of hiding a latency/throughput
    trade behind a background thread.

    ``result(ticket)`` returns (and drops) one query's row dict, flushing
    first if the ticket is still pending. With a ``cache`` the flush goes
    through ``serve_batch_cached`` and rows carry the ``cached`` flag.
    ``flushes``/``served`` counters expose the achieved batching factor
    (served/flushes -- the scoreboard's micro-batching gain).
    """

    def __init__(
        self,
        snap: IndexSnapshot,
        max_leaves: int = 32,
        flush_at: int = 8,
        cache: Optional[HotQueryCache] = None,
        **serve_kw,
    ) -> None:
        if flush_at < 1:
            raise ValueError(f"flush_at must be >= 1, got {flush_at}")
        self.snap = snap
        self.max_leaves = max_leaves
        self.flush_at = int(flush_at)
        self.cache = cache
        self.serve_kw = serve_kw
        self._pending: list = []  # [(ticket, rect, bm)]
        self._done: dict = {}
        self._next = 0
        self.flushes = 0
        self.served = 0

    @property
    def pending(self) -> int:
        return len(self._pending)

    def submit(self, rect, bm) -> int:
        """Enqueue one query; returns its ticket. Auto-flushes at
        ``flush_at`` pending queries."""
        t = self._next
        self._next += 1
        self._pending.append(
            (t, np.asarray(rect, np.float32).reshape(4),
             np.asarray(bm, np.uint32).reshape(-1))
        )
        if len(self._pending) >= self.flush_at:
            self.flush()
        return t

    def flush(self) -> int:
        """Serve every pending query in one dispatch; returns how many."""
        if not self._pending:
            return 0
        tickets = [t for t, _, _ in self._pending]
        rects = np.stack([r for _, r, _ in self._pending])
        bms = np.stack([b for _, _, b in self._pending])
        self._pending = []
        if self.cache is not None:
            out = serve_batch_cached(
                self.snap, rects, bms, self.cache, self.max_leaves, **self.serve_kw
            )
            keys = _PER_QUERY_SKR + ("cached",)
        else:
            out = serve_batch(self.snap, rects, bms, self.max_leaves, **self.serve_kw)
            keys = _PER_QUERY_SKR
        for j, t in enumerate(tickets):
            self._done[t] = {k: np.asarray(out[k])[j] for k in keys}
        self.flushes += 1
        self.served += len(tickets)
        return len(tickets)

    def result(self, ticket: int) -> Dict[str, np.ndarray]:
        """One query's result row (popped); flushes if still pending."""
        if ticket not in self._done:
            self.flush()
        return self._done.pop(ticket)


# ------------------------------------- query-parallel sharded serving (§3.4)
def default_serving_mesh() -> Mesh:
    """All local devices on the data axis (query-parallel serving)."""
    return make_host_mesh(data=len(jax.devices()), model=1)


def mesh_dp_size(mesh: Mesh) -> int:
    """Number of query shards: the product of the mesh's data axes."""
    dp = dp_axes(mesh)
    return int(np.prod([mesh.shape[a] for a in dp])) if dp else 1


# Replicated-snapshot memo: broadcasting a production-scale index to every
# mesh device is the expensive part of the query-parallel path, so it must
# happen once per (snapshot, mesh), not once per served batch. Weakly keyed
# like plan.default_plan_cache: dropping the snapshot drops its replicas.
_REPLICATED: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _replicated(snap: IndexSnapshot, mesh: Mesh) -> IndexSnapshot:
    per_mesh = _REPLICATED.get(snap)
    if per_mesh is None:
        per_mesh = {}
        _REPLICATED[snap] = per_mesh
    got = per_mesh.get(mesh)
    if got is None:
        got = snap.replicate(mesh)
        per_mesh[mesh] = got
    return got


def _converge_widths(snap: IndexSnapshot, cache: PlanCache, tag: str, run):
    """Shared grow-and-redescend driver of the sharded front doors: descend
    at the cache's seeded widths, max the observed per-level child counts
    across shards, grow the cache, and repeat until nothing overflowed --
    lossless for the same reason the §3.2 overflow retry is (a descent that
    finishes without overflow dropped no children), and convergent because
    widths grow monotonically in power-of-two steps. ``run(widths)`` must
    return a tuple whose LAST element is the pmax'd per-level maxima."""
    n_links = snap.n_levels - 1
    while True:
        widths = cache.seeded_plan(tag, n_links).widths
        out = run(widths)
        maxima = np.asarray(jax.device_get(out[-1]))
        cache.observe(tag, maxima)
        if not n_links or not np.any(maxima > np.asarray(widths)):
            return widths, out


def _shard_queries(mesh: Mesh, *arrays):
    qspec = spec_for(("query", None), default_rules(mesh))
    sharding = NamedSharding(mesh, qspec)
    return tuple(jax.device_put(jnp.asarray(a), sharding) for a in arrays)


def _pmax_needs(needs, dp):
    """Stack per-level observed child-count maxima and max them across the
    query shards: the plan cache must learn widths that fit EVERY shard."""
    if not needs:
        return jnp.zeros((0,), jnp.int32)
    arr = jnp.stack(list(needs)).astype(jnp.int32)
    return jax.lax.pmax(arr, dp) if dp else arr


def _skr_shard_body(
    snap, delta, q_rects, q_bm, wids, bits, *, widths, take, dp, narrow, compact,
):
    """Per-shard SKR serving: the real frontier descent on the local query
    shard against the replicated snapshot (and replicated delta, when one
    is live; no cross-shard collectives except the width-maxima pmax).
    ``narrow`` (static) routes the descent through the bandwidth-lean planes
    using the pre-sharded packed query words (``wids``/``bits`` -- packed
    before ``shard_map`` so every shard agrees on the static Wp).
    ``compact`` (static) controls the leaf-local compact verify bank."""
    plan = ExecutionPlan(tag="skr", widths=widths)
    frontier, surv, nodes_checked, _, needs = _descend_frontier(
        snap, q_rects, q_bm, plan, delta, (wids, bits) if narrow else None
    )
    top_leaf, leaf_ok, overflow = _select_leaves_frontier(
        frontier, surv, take, snap.n_leaves
    )
    ids, counts, kw_scanned = _verify_leaves(
        snap, q_rects, q_bm, top_leaf, leaf_ok, delta, compact=compact
    )
    return ids, counts, nodes_checked, kw_scanned, overflow, _pmax_needs(needs, dp)


@functools.partial(
    jax.jit, static_argnames=("mesh", "widths", "take", "narrow", "compact")
)
def _skr_sharded_exec(
    snap, delta, q_rects, q_bm, wids, bits, mesh, widths, take, narrow, compact,
):
    dp = dp_axes(mesh)
    body = functools.partial(
        _skr_shard_body, widths=widths, take=take, dp=dp, narrow=narrow,
        compact=compact,
    )
    fn = shard_map(
        body,
        mesh=mesh,
        # snapshot + delta replicated (P() prefix; delta=None is an empty
        # pytree, so the same spec covers the no-delta fast path); queries
        # and their packed words sharded on the data axes
        in_specs=(P(), P(), P(dp, None), P(dp, None), P(dp, None), P(dp, None)),
        out_specs=(P(dp, None), P(dp), P(dp), P(dp), P(dp), P()),
        check_vma=False,
    )
    return fn(snap, delta, q_rects, q_bm, wids, bits)


def serve_sharded(
    snap: IndexSnapshot,
    q_rects,
    q_bm,
    max_leaves: int = 32,
    mesh: Optional[Mesh] = None,
    plan_cache: Optional[PlanCache] = None,
    minimum_bucket: int = 8,
    delta: Optional[DeltaBuffer] = None,
    compact: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """Data-parallel SKR serving of the real hierarchical engine.

    Args:
        snap: the served ``IndexSnapshot`` (replicated over ``mesh``).
        q_rects: (m, 4) f32 query rectangles; ``q_bm``: (m, W) u32 bitmaps.
        max_leaves: per-query verification capacity (spill -> ``overflow``).
        mesh: serving mesh (None: all local devices on the data axis).
        plan_cache: frontier width state (None: per-snapshot default).
        minimum_bucket: smallest per-shard power-of-two batch bucket.
        delta: optional ``DeltaBuffer`` of buffered updates, replicated like
            the snapshot and merged per shard (DESIGN.md §7).
        compact: leaf verification width -- None (default) auto-uses the
            compact leaf bank; False forces full width (DESIGN.md §3.5).

    Pads the batch to ``n_shards`` equal power-of-two buckets, replicates the
    snapshot, shard_maps the frontier descent over the mesh's data axes, and
    converges the plan cache by grow-and-redescend (see module docstring).
    Host-side driver around the jit-traced shard_map body. Returns the same
    per-query dict as the single-device ``retrieve`` -- identical ids and
    counters (tests/test_sharded_parity.py).
    """
    mesh = mesh if mesh is not None else default_serving_mesh()
    cache = plan_cache if plan_cache is not None else default_plan_cache(snap)
    rects, bms, m = pad_queries_to_bucket(
        q_rects, q_bm, minimum_bucket, shards=mesh_dp_size(mesh)
    )
    # pack the padded batch's query words before sharding (static Wp shared
    # by every shard; pad rows are all-zero bitmaps, so their words are 0)
    narrow = delta is None and snap.has_narrow_planes
    wids, bits = ops.pack_query_words(bms)
    rects, bms, wids, bits = _shard_queries(mesh, rects, bms, wids, bits)
    snap_r = _replicated(snap, mesh)
    delta_r = _replicated(delta, mesh) if delta is not None else None

    def run(widths):
        leaf_width = widths[-1] if widths else snap.root_width()
        take = min(max_leaves, snap.n_leaves, leaf_width)
        return _skr_sharded_exec(
            snap_r, delta_r, rects, bms, wids, bits, mesh, widths, take, narrow,
            compact,
        )

    widths, out = _converge_widths(snap, cache, "skr", run)
    ids, counts, nodes_checked, kw_scanned, overflow, _ = out
    used = [snap.root_width(), *widths]
    return dict(
        ids=np.asarray(ids)[:m],
        counts=np.asarray(counts)[:m],
        nodes_checked=np.asarray(nodes_checked, np.int64)[:m],
        nodes_scanned=np.full((m,), sum(used), np.int64),
        verified=np.asarray(kw_scanned)[:m],
        overflow=np.asarray(overflow)[:m],
        frontier_widths=np.asarray(used, np.int32),
    )


def _knn_shard_body(
    snap, delta, points, q_bm, wids, bits, *, widths, k, kb, dp, narrow, compact,
):
    """Per-shard Boolean kNN: the real distance-bounded descent on the local
    query shard against the replicated snapshot (and replicated delta).
    ``narrow`` (static) routes the level filters through the bandwidth-lean
    planes with the pre-sharded packed query words; ``compact`` (static)
    controls the compact leaf keyword-test bank."""
    plan = ExecutionPlan(tag="knn", widths=widths)
    result, needs = _descend_knn(
        snap, points, q_bm, k, kb, plan, delta, (wids, bits) if narrow else None,
        cbank=_snap_cbank(snap, compact),
    )
    top_d, top_id, nodes_checked, verified, leaves_verified, pruned, _, _, _ = result
    fin = jnp.isfinite(top_d[:, :k])
    ids = jnp.where(fin, top_id[:, :k], -1)
    return (
        ids, top_d[:, :k], nodes_checked, verified, leaves_verified, pruned,
        _pmax_needs(needs, dp),
    )


@functools.partial(
    jax.jit, static_argnames=("mesh", "widths", "k", "kb", "narrow", "compact")
)
def _knn_sharded_exec(
    snap, delta, points, q_bm, wids, bits, mesh, widths, k, kb, narrow, compact,
):
    dp = dp_axes(mesh)
    body = functools.partial(
        _knn_shard_body, widths=widths, k=k, kb=kb, dp=dp, narrow=narrow,
        compact=compact,
    )
    fn = shard_map(
        body,
        mesh=mesh,
        # snapshot + delta replicated (P() prefix; None delta = empty pytree)
        in_specs=(P(), P(), P(dp, None), P(dp, None), P(dp, None), P(dp, None)),
        out_specs=(
            P(dp, None), P(dp, None), P(dp), P(dp), P(dp), P(dp), P(),
        ),
        check_vma=False,
    )
    return fn(snap, delta, points, q_bm, wids, bits)


def serve_knn_sharded(
    snap: IndexSnapshot,
    points,
    q_bm,
    k: int,
    mesh: Optional[Mesh] = None,
    plan_cache: Optional[PlanCache] = None,
    minimum_bucket: int = 8,
    min_topk_bucket: int = 8,
    delta: Optional[DeltaBuffer] = None,
    compact: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """Data-parallel Boolean kNN serving of the real bounded descent.

    Args:
        snap: the served ``IndexSnapshot`` (replicated over ``mesh``).
        points: (m, 2) f32 query points; ``q_bm``: (m, W) u32 bitmaps.
        k: neighbors per query (static; each k compiles its own descent).
        mesh: serving mesh (None: all local devices on the data axis).
        plan_cache: frontier width state (None: per-snapshot default).
        minimum_bucket / min_topk_bucket: power-of-two bucket floors for
            the per-shard batch and the on-device top-k buffer.
        delta: optional ``DeltaBuffer`` of buffered updates, replicated like
            the snapshot and merged per shard (DESIGN.md §7).

    Same regime as ``serve_sharded``: replicated snapshot, query batch
    sharded over the data axes, seeded-width descent with grow-and-redescend
    convergence. Host-side driver around the jit-traced shard_map body.
    Identical ids/dist2/counters to ``retrieve_knn``.
    """
    if k <= 0:  # delegate: one source of truth for the degenerate shape
        return retrieve_knn(snap, points, q_bm, k, delta=delta, compact=compact)
    mesh = mesh if mesh is not None else default_serving_mesh()
    cache = plan_cache if plan_cache is not None else default_plan_cache(snap)
    pts, bms, m = pad_knn_queries_to_bucket(
        points, q_bm, minimum_bucket, shards=mesh_dp_size(mesh)
    )
    narrow = delta is None and snap.has_narrow_planes
    wids, bits = ops.pack_query_words(bms)
    pts, bms, wids, bits = _shard_queries(mesh, pts, bms, wids, bits)
    snap_r = _replicated(snap, mesh)
    delta_r = _replicated(delta, mesh) if delta is not None else None
    kb = round_up_bucket(k, min_topk_bucket)

    widths, out = _converge_widths(
        snap, cache, "knn",
        lambda widths: _knn_sharded_exec(
            snap_r, delta_r, pts, bms, wids, bits, mesh, widths, k, kb, narrow,
            compact,
        ),
    )
    ids, dist2, nodes_checked, verified, leaves_verified, pruned, _ = out
    used = [snap.root_width(), *widths]
    return dict(
        ids=np.asarray(ids)[:m],
        dist2=np.asarray(dist2)[:m],
        nodes_checked=np.asarray(nodes_checked, np.int64)[:m],
        verified=np.asarray(verified, np.int64)[:m],
        leaves_verified=np.asarray(leaves_verified, np.int64)[:m],
        pruned=np.asarray(pruned, np.int64)[:m],
        frontier_widths=np.asarray(used, np.int32),
    )


# --------------------------------- index-parallel sharded serving (§3.4)
def mesh_index_size(mesh: Mesh) -> int:
    """Number of index shards: the size of the mesh's ``index`` axis."""
    return int(mesh.shape["index"]) if "index" in mesh.axis_names else 1


def default_index_mesh(n_shards: int) -> Mesh:
    """All local devices as a (query, index) serving mesh with ``n_shards``
    index shards (the remaining factor goes to query parallelism)."""
    n = len(jax.devices())
    if n % n_shards:
        raise ValueError(f"{n} devices not divisible into {n_shards} index shards")
    return make_serving_mesh(query=n // n_shards, index=n_shards)


# Placement memos, mirroring _REPLICATED: sharding a production-scale
# partition (or a delta routed to its shards) must happen once per
# (object, mesh), not once per served batch.
_PLACED: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _placed(psnap: PartitionedSnapshot, mesh: Mesh) -> PartitionedSnapshot:
    per_mesh = _PLACED.get(psnap)
    if per_mesh is None:
        per_mesh = {}
        _PLACED[psnap] = per_mesh
    got = per_mesh.get(mesh)
    if got is None:
        got = psnap.shard(mesh)
        per_mesh[mesh] = got
    return got


# Keyed by the (immutable) DeltaBuffer: every LiveIndex update produces a
# NEW buffer, so a fresh buffer is partitioned -- routed to its owning
# shards -- exactly once, on its first served batch.
_PARTITIONED_DELTA: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _partitioned_delta(delta: DeltaBuffer, psnap: PartitionedSnapshot, mesh: Mesh):
    per_key = _PARTITIONED_DELTA.get(delta)
    if per_key is None:
        per_key = {}
        _PARTITIONED_DELTA[delta] = per_key
    got = per_key.get((mesh, psnap.part))
    if got is None:
        got = jax.device_put(
            partition_delta(delta, psnap.part),
            NamedSharding(mesh, P("index")),
        )
        per_key[(mesh, psnap.part)] = got
    return got


def _converge_widths_indexed(cache: PlanCache, tag: str, n_shards: int, n_links: int, run):
    """Index-sharded twin of ``_converge_widths``: the observed per-level
    child-count maxima come back as an (S, n_links) matrix (each index
    shard's own hierarchy has its own fan-outs), the cache learns per-shard
    sub-tags, and every shard of the next descent traces at the max width
    over shards (``seeded_shard_plan`` -- SPMD needs one static shape)."""
    while True:
        widths = cache.seeded_shard_plan(tag, n_shards, n_links).widths
        out = run(widths)
        maxima = np.asarray(jax.device_get(out[-1])).reshape(n_shards, -1)
        cache.observe_shards(tag, maxima)
        if not n_links or not np.any(maxima.max(axis=0) > np.asarray(widths)):
            return widths, out


def _ix_skr_body(
    psnap, delta, q_rects, q_bm, wids, bits,
    *, widths, take_g, take_loc, n_shards, dp, narrow, compact,
):
    """Per-(query shard, index shard) SKR body: the unchanged engine descent
    on this device's sub-hierarchy from its masked local root frontier, then
    two collectives over ``index`` -- the global smallest-gid leaf selection
    (``_select_leaves_indexed``: one bound exchange + psum'd overflow) and
    the psum of the Eq.1 counters. Result ids stay local (the out_spec
    concatenates the per-shard id unions); counters leave the body already
    global, exactly matching the single-device descent."""
    snap = psnap.local_view()
    M = q_rects.shape[0]
    n_root_local = psnap.level_counts[0, 0]
    plan = ExecutionPlan(tag="skr_ix", widths=widths)
    root = _local_root_frontier(snap.root_width(), n_root_local, M)
    frontier, surv, nodes_checked, _, needs = _descend_frontier(
        snap, q_rects, q_bm, plan, delta, (wids, bits) if narrow else None,
        root=root,
    )
    top_leaf, leaf_ok, overflow = _select_leaves_indexed(
        frontier, surv, psnap.leaf_gid, take_g, take_loc, n_shards, "index"
    )
    ids, counts, kw_scanned = _verify_leaves(
        snap, q_rects, q_bm, top_leaf, leaf_ok, delta, compact=compact
    )
    counts = jax.lax.psum(counts, "index")
    nodes_checked = jax.lax.psum(nodes_checked, "index")
    kw_scanned = jax.lax.psum(kw_scanned, "index")
    needs_all = jax.lax.all_gather(_pmax_needs(needs, dp), "index")  # (S, links)
    return ids, counts, nodes_checked, kw_scanned, overflow, needs_all


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "widths", "take_g", "take_loc", "n_shards", "narrow", "compact",
    ),
)
def _ix_skr_exec(
    psnap, delta, q_rects, q_bm, wids, bits, mesh, widths, take_g, take_loc,
    n_shards, narrow, compact,
):
    dp = dp_axes(mesh)
    body = functools.partial(
        _ix_skr_body, widths=widths, take_g=take_g, take_loc=take_loc,
        n_shards=n_shards, dp=dp, narrow=narrow, compact=compact,
    )
    fn = shard_map(
        body,
        mesh=mesh,
        # partition + routed delta sharded over "index" (single prefix spec
        # over the whole pytree; None delta is an empty pytree); queries and
        # packed words sharded over the data axes, replicated over "index"
        in_specs=(
            P("index"), P("index"), P(dp, None), P(dp, None), P(dp, None), P(dp, None),
        ),
        # ids: concat of the per-shard id unions; counters already psum'd
        out_specs=(P(dp, "index"), P(dp), P(dp), P(dp), P(dp), P()),
        check_vma=False,
    )
    return fn(psnap, delta, q_rects, q_bm, wids, bits)


def serve_index_sharded(
    psnap: PartitionedSnapshot,
    q_rects,
    q_bm,
    max_leaves: int = 32,
    mesh: Optional[Mesh] = None,
    plan_cache: Optional[PlanCache] = None,
    minimum_bucket: int = 8,
    delta: Optional[DeltaBuffer] = None,
    compact: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """Index-parallel SKR serving: the hierarchy itself sharded (§3.4).

    Args:
        psnap: a ``PartitionedSnapshot`` (``PartitionedSnapshot.build``);
            each device holds only its ~1/S slab after placement.
        q_rects: (m, 4) f32 query rectangles; ``q_bm``: (m, W) u32 bitmaps.
        max_leaves: per-query verification capacity (global: the selection
            keeps the ``max_leaves`` smallest-id surviving leaves ACROSS
            shards, exactly like the single-device engine; spill ->
            ``overflow``).
        mesh: a serving mesh with an ``index`` axis of size
            ``psnap.n_shards`` (None: all local devices, query x index).
        plan_cache: frontier width state (None: per-partition default);
            learns per-shard sub-tags (``PlanCache.seeded_shard_plan``).
        minimum_bucket: smallest per-query-shard power-of-two batch bucket.
        delta: optional ``DeltaBuffer`` in the ordinary global layout --
            routed to the owning shards (``delta.partition_delta``, memoized
            per buffer) and merged shard-locally.

    Returns the ``retrieve`` dict: ``counts``/``nodes_checked``/``verified``
    /``overflow`` exactly equal to the single-device engine, ``ids`` the
    same id SET per query (order is shard-concatenation order, not the
    single-device capacity order). ``nodes_scanned`` sums every shard's
    frontier slots -- the only counter that is layout-dependent by design
    (see tests/test_index_sharded_parity.py).
    """
    S = psnap.n_shards
    mesh = mesh if mesh is not None else default_index_mesh(S)
    if mesh_index_size(mesh) != S:
        raise ValueError(
            f"mesh index axis {mesh_index_size(mesh)} != partition shards {S}"
        )
    cache = plan_cache if plan_cache is not None else default_plan_cache(psnap)
    rects, bms, m = pad_queries_to_bucket(
        q_rects, q_bm, minimum_bucket, shards=mesh_dp_size(mesh)
    )
    narrow = delta is None and psnap.has_narrow_planes
    wids, bits = ops.pack_query_words(bms)
    rects, bms, wids, bits = _shard_queries(mesh, rects, bms, wids, bits)
    psnap_s = _placed(psnap, mesh)
    delta_s = _partitioned_delta(delta, psnap, mesh) if delta is not None else None
    n_links = psnap.n_levels - 1

    def run(widths):
        leaf_width = widths[-1] if widths else psnap.local_root_width()
        take_g = min(max_leaves, psnap.n_leaves_global)
        take_loc = min(take_g, leaf_width)
        return _ix_skr_exec(
            psnap_s, delta_s, rects, bms, wids, bits, mesh, widths,
            take_g, take_loc, S, narrow, compact,
        )

    widths, out = _converge_widths_indexed(cache, "skr_ix", S, n_links, run)
    with obs.span("wisk.fetch"):
        ids, counts, nodes_checked, kw_scanned, overflow = (np.asarray(a) for a in out[:5])
    used = [psnap.local_root_width(), *widths]
    return dict(
        ids=ids[:m],
        counts=counts[:m],
        nodes_checked=nodes_checked[:m].astype(np.int64),
        nodes_scanned=np.full((m,), sum(used) * S, np.int64),
        verified=kw_scanned[:m],
        overflow=overflow[:m],
        frontier_widths=np.asarray(used, np.int32),
    )


def _ix_knn_body(
    psnap, delta, points, q_bm, wids, bits,
    *, widths, k, kb, n_shards, dp, narrow, compact,
):
    """Per-(query shard, index shard) kNN body: ``_descend_knn_indexed``
    (canonical-probe election, shard-local bounded sweep, global-rank leaf
    phase) plus the counter psums. The top-k buffers leave the descent
    already replicated across shards (the leaf phase ends on a global
    merge), so the out_spec just takes one copy."""
    snap = psnap.local_view()
    n_root_local = psnap.level_counts[0, 0]
    plan = ExecutionPlan(tag="knn_ix", widths=widths)
    result, needs = _descend_knn_indexed(
        snap, psnap.root_gid, psnap.leaf_gid, n_root_local, points, q_bm,
        k, kb, plan, n_shards, "index", delta, (wids, bits) if narrow else None,
        cbank=_snap_cbank(snap, compact),
    )
    top_d, top_id, nodes_checked, verified, leaves_verified, pruned, _ = result
    nodes_checked = jax.lax.psum(nodes_checked, "index")
    verified = jax.lax.psum(verified, "index")
    leaves_verified = jax.lax.psum(leaves_verified, "index")
    pruned = jax.lax.psum(pruned, "index")
    fin = jnp.isfinite(top_d[:, :k])
    ids = jnp.where(fin, top_id[:, :k], -1)
    needs_all = jax.lax.all_gather(_pmax_needs(needs, dp), "index")  # (S, links)
    return (
        ids, top_d[:, :k], nodes_checked, verified, leaves_verified, pruned,
        needs_all,
    )


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "widths", "k", "kb", "n_shards", "narrow", "compact"),
)
def _ix_knn_exec(
    psnap, delta, points, q_bm, wids, bits, mesh, widths, k, kb, n_shards,
    narrow, compact,
):
    dp = dp_axes(mesh)
    body = functools.partial(
        _ix_knn_body, widths=widths, k=k, kb=kb, n_shards=n_shards, dp=dp,
        narrow=narrow, compact=compact,
    )
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("index"), P("index"), P(dp, None), P(dp, None), P(dp, None), P(dp, None),
        ),
        # top-k buffers are replicated over "index" after the final merge
        out_specs=(
            P(dp, None), P(dp, None), P(dp), P(dp), P(dp), P(dp), P(),
        ),
        check_vma=False,
    )
    return fn(psnap, delta, points, q_bm, wids, bits)


def serve_knn_index_sharded(
    psnap: PartitionedSnapshot,
    points,
    q_bm,
    k: int,
    mesh: Optional[Mesh] = None,
    plan_cache: Optional[PlanCache] = None,
    minimum_bucket: int = 8,
    min_topk_bucket: int = 8,
    delta: Optional[DeltaBuffer] = None,
    compact: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """Index-parallel Boolean kNN serving: the hierarchy itself sharded.

    Same contract as ``serve_knn_sharded`` but over a
    ``PartitionedSnapshot``: ids/dist2 AND every counter except
    ``frontier_widths`` are exactly equal to the single-device
    ``retrieve_knn`` (the bound-exchange collectives in
    ``_descend_knn_indexed`` reproduce the same probe chain, prune
    decisions, and chunked leaf order -- tests/test_index_sharded_parity.py).
    ``delta`` arrives in the global layout and is routed to the owning
    shards. Always exact f32 (``knn_dtype`` is a replicated-path flag).
    """
    if k <= 0:  # delegate: one source of truth for the degenerate shape
        M = int(np.asarray(points).reshape(-1, 2).shape[0])
        z = np.zeros(M, np.int64)
        return dict(
            ids=np.zeros((M, 0), np.int32), dist2=np.zeros((M, 0), np.float32),
            nodes_checked=z, verified=z.copy(), leaves_verified=z.copy(),
            pruned=z.copy(), frontier_widths=np.zeros(0, np.int32),
        )
    S = psnap.n_shards
    mesh = mesh if mesh is not None else default_index_mesh(S)
    if mesh_index_size(mesh) != S:
        raise ValueError(
            f"mesh index axis {mesh_index_size(mesh)} != partition shards {S}"
        )
    cache = plan_cache if plan_cache is not None else default_plan_cache(psnap)
    pts, bms, m = pad_knn_queries_to_bucket(
        points, q_bm, minimum_bucket, shards=mesh_dp_size(mesh)
    )
    narrow = delta is None and psnap.has_narrow_planes
    wids, bits = ops.pack_query_words(bms)
    pts, bms, wids, bits = _shard_queries(mesh, pts, bms, wids, bits)
    psnap_s = _placed(psnap, mesh)
    delta_s = _partitioned_delta(delta, psnap, mesh) if delta is not None else None
    kb = round_up_bucket(k, min_topk_bucket)
    n_links = psnap.n_levels - 1

    widths, out = _converge_widths_indexed(
        cache, "knn_ix", S, n_links,
        lambda widths: _ix_knn_exec(
            psnap_s, delta_s, pts, bms, wids, bits, mesh, widths, k, kb, S,
            narrow, compact,
        ),
    )
    with obs.span("wisk.fetch"):
        ids, dist2, *counters = (np.asarray(a)[:m] for a in out[:6])
    nodes_checked, verified, leaves_verified, pruned = (c.astype(np.int64) for c in counters)
    used = [psnap.local_root_width(), *widths]
    return dict(
        ids=ids,
        dist2=dist2,
        nodes_checked=nodes_checked,
        verified=verified,
        leaves_verified=leaves_verified,
        pruned=pruned,
        frontier_widths=np.asarray(used, np.int32),
    )


# ------------------------------- incremental maintenance front door (§7)
@dataclasses.dataclass(frozen=True)
class ServingGeneration:
    """One immutable serving epoch (DESIGN.md §7).

    Everything a request touches -- snapshot, delta log, plan cache, the
    backing dataset and artifacts -- is bundled so replacing a generation is
    ONE reference store (``LiveIndex._gen = new``): an in-flight batch that
    grabbed the old generation keeps serving a consistent view; the next
    batch sees the new one. ``seq`` increments per swap.
    """

    artifacts: object  # core.build.BuildArtifacts
    dataset: object  # core.types.GeoTextDataset
    snapshot: IndexSnapshot
    delta_log: DeltaLog
    plan_cache: PlanCache
    seq: int = 0
    # index-parallel regime: the snapshot's partition, rebuilt per
    # generation (a rebuild re-cuts the fresh hierarchy); None = replicated
    partitioned: Optional[PartitionedSnapshot] = None

    def delta(self) -> Optional[DeltaBuffer]:
        """The live delta, or None when no updates are buffered (the
        executors' zero-overhead fast path)."""
        return self.delta_log.buffer if self.delta_log.n_updates() else None


class LiveIndex:
    """Serving front door that survives live traffic (DESIGN.md §7).

    Ties the incremental subsystem together: object updates land in the
    current generation's ``DeltaLog`` and are merged into every query on
    the fly; a ``DriftMonitor`` watches the observed per-query Eq.1 cost;
    and ``maybe_rebuild()`` reacts to a trip by warm-start rebuilding on
    the recently observed workload and atomically swapping in the fresh
    ``IndexSnapshot`` -- serving never blocks on a rebuild, in-flight
    batches finish on the generation they started on.

    All methods are host-side control plane; the descents they drive are
    the jit-traced engine paths. Single-writer discipline: updates and
    swaps are expected from one maintenance thread; readers may hold
    ``generation`` freely.
    """

    def __init__(
        self,
        dataset,
        workload,
        build_config=None,
        drift_config=None,
        artifacts=None,
        max_recent: int = 512,
        slots_per_leaf: int = 8,
        result_cache: Optional[HotQueryCache] = None,
        index_shards: int = 1,
        index_mesh: Optional[Mesh] = None,
    ) -> None:
        from ..core.build import BuildConfig, build_wisk
        from ..core.drift import DriftMonitor

        self.build_config = build_config or BuildConfig()
        self._slots_per_leaf = slots_per_leaf
        # index-parallel serving (§3.4): partition every generation's
        # snapshot into this many shard-local sub-hierarchies and serve over
        # the (query, index) mesh; updates keep landing in the global-layout
        # DeltaLog and are routed to their owning shards per served batch
        # (memoized per buffer -- see _partitioned_delta)
        self.index_shards = int(index_shards)
        self.index_mesh = index_mesh
        if self.index_mesh is not None and self.index_shards == 1:
            self.index_shards = mesh_index_size(self.index_mesh)
        # hot-query result cache (§3.5): exact results keyed on the current
        # served state, so every state change below must invalidate it
        self.result_cache = result_cache
        if artifacts is None:
            artifacts = build_wisk(dataset, workload, self.build_config)
        self._gen = self._make_generation(artifacts, dataset, seq=0)
        # continuous-filter pub-sub (DESIGN.md §8): the standing-subscription
        # index + notification log live on the front door, NOT on a
        # generation -- subscriptions, queued notifications, and the
        # exactly-once high-water mark (global object ids are monotonic
        # across rebuilds) all survive maybe_rebuild() swaps untouched
        self.subscriptions = SubscriptionIndex(dataset.vocab_size)
        # baseline learned from the warmup window of observed traffic (see
        # core/drift.py: a trained-workload prediction undershoots steady
        # state by the generalization gap)
        self.monitor = DriftMonitor(None, drift_config)
        self.max_recent = max_recent
        self._recent_rects: list = []
        self._recent_bms: list = []
        self.swaps = 0

    def _make_generation(self, artifacts, dataset, seq: int) -> ServingGeneration:
        snapshot = IndexSnapshot.build(artifacts.index, dataset)
        partitioned = (
            PartitionedSnapshot.build(snapshot, self.index_shards)
            if self.index_shards > 1 else None
        )
        return ServingGeneration(
            artifacts=artifacts,
            dataset=dataset,
            snapshot=snapshot,
            delta_log=DeltaLog(artifacts.index, dataset, snapshot, self._slots_per_leaf),
            plan_cache=PlanCache(),
            seq=seq,
            partitioned=partitioned,
        )

    @property
    def generation(self) -> ServingGeneration:
        """The current generation; grab once per batch for a stable view."""
        return self._gen

    # ------------------------------------------------------------- serving
    def _record(self, rects, bms) -> None:
        self._recent_rects.extend(np.asarray(rects, np.float32).reshape(-1, 4))
        self._recent_bms.extend(np.asarray(bms, np.uint32).reshape(len(rects), -1))
        drop = len(self._recent_rects) - self.max_recent
        if drop > 0:
            del self._recent_rects[:drop]
            del self._recent_bms[:drop]

    def serve(self, q_rects, q_bm, max_leaves: int = 32) -> Dict[str, np.ndarray]:
        """Delta-merged SKR batch through the current generation; feeds the
        drift monitor with the observed Eq.1 counters.

        With a ``result_cache`` the batch goes through ``serve_batch_cached``
        and only MISS rows feed the monitor -- cache hits cost nothing, and
        counting them would mask drift in exactly the hot traffic a rebuild
        should follow.

        In the index-parallel regime (``index_shards > 1``) the batch goes
        through ``serve_index_sharded`` over the partitioned snapshot, with
        the live delta routed to its owning shards; the result cache is
        bypassed (counters are identical either way, so the monitor feed is
        unchanged)."""
        gen = self._gen
        with obs.span("wisk.serve"):
            fresh = slice(None)
            if gen.partitioned is not None:
                out = serve_index_sharded(
                    gen.partitioned, q_rects, q_bm, max_leaves,
                    mesh=self.index_mesh, plan_cache=gen.plan_cache,
                    delta=gen.delta(),
                )
            elif self.result_cache is not None:
                out = serve_batch_cached(
                    gen.snapshot, q_rects, q_bm, self.result_cache, max_leaves,
                    plan_cache=gen.plan_cache, delta=gen.delta(),
                )
                fresh = ~out["cached"]
            else:
                out = serve_batch(
                    gen.snapshot, q_rects, q_bm, max_leaves,
                    plan_cache=gen.plan_cache, delta=gen.delta(),
                )
            with obs.span("wisk.observe"):
                self._record(q_rects, q_bm)
                nc = np.asarray(out["nodes_checked"])[fresh]
                if nc.size:  # an all-hit batch observed no real descents
                    self.monitor.observe_counters(nc, np.asarray(out["verified"])[fresh])
        return out

    def serve_knn(self, points, q_bm, k: int) -> Dict[str, np.ndarray]:
        """Delta-merged Boolean kNN batch through the current generation.

        kNN traffic enters the recent-traffic window as zero-area point
        rects, so kNN-driven drift both trips the monitor AND steers the
        rebuild's training workload toward the traffic that tripped it."""
        gen = self._gen
        with obs.span("wisk.serve_knn"):
            if gen.partitioned is not None:
                out = serve_knn_index_sharded(
                    gen.partitioned, points, q_bm, k,
                    mesh=self.index_mesh, plan_cache=gen.plan_cache,
                    delta=gen.delta(),
                )
            else:
                out = serve_knn_batch(
                    gen.snapshot, points, q_bm, k,
                    plan_cache=gen.plan_cache, delta=gen.delta(),
                )
            with obs.span("wisk.observe"):
                pts = np.asarray(points, np.float32).reshape(-1, 2)
                self._record(np.concatenate([pts, pts], axis=1), q_bm)
                self.monitor.observe_counters(out["nodes_checked"], out["verified"])
        return out

    # ------------------------------------------------------------- updates
    def insert(self, locs, kw_ids) -> np.ndarray:
        """Buffer new objects into the current generation's delta log;
        visible to the very next query. Returns the assigned global ids.

        In the same step, the arrivals are matched on device against the
        compiled subscription block (DESIGN.md §8): any standing filter they
        satisfy queues an (object_id, subscription_id) notification for
        ``drain_notifications()``."""
        with obs.span("wisk.insert"):
            if self.result_cache is not None:
                self.result_cache.invalidate()
            with obs.span("wisk.delta_insert"):
                ids = self._gen.delta_log.insert(locs, kw_ids)
            with obs.span("wisk.geofence_match"):
                self.subscriptions.match_arrivals(ids, locs, kw_ids=kw_ids)
        return ids

    def delete(self, ids) -> int:
        """Mask objects out of serving immediately; returns #newly deleted.

        Deletion never retracts a queued notification -- the object *did*
        arrive while the matching subscriptions were live (§8 contract)."""
        with obs.span("wisk.delete"):
            if self.result_cache is not None:
                self.result_cache.invalidate()
            with obs.span("wisk.delta_delete"):
                return self._gen.delta_log.delete(ids)

    # -------------------------------------------- continuous filters (§8)
    def subscribe(self, rect, kw_ids) -> int:
        """Register a standing spatio-textual filter (geofence); returns its
        subscription id. Matches objects inserted from now on: each
        ``insert`` batch is matched on device against the compiled
        subscription block in the same step it enters the delta log."""
        return self.subscriptions.subscribe(rect, kw_ids)

    def unsubscribe(self, sub_id: int) -> bool:
        """Retire a standing filter; already-queued notifications survive."""
        return self.subscriptions.unsubscribe(sub_id)

    def drain_notifications(self) -> np.ndarray:
        """All queued (object_id, subscription_id) notifications, exactly
        once -- across buffer growth, freed-slot reuse, deletes, and
        rebuild swaps (the subscription state lives on the front door, and
        the exactly-once mark rides the monotonic global id space, which a
        swap continues rather than restarts)."""
        with obs.span("wisk.drain"):
            return self.subscriptions.drain()

    # ------------------------------------------------------------ operator
    @staticmethod
    def stats() -> Dict[str, int]:
        """The program's counters since the process started, summed over
        every index in it (``repro.obs.totals``; README "Operating an
        index")."""
        return obs.totals()

    # ------------------------------------------------------------- rebuild
    def observed_workload(self):
        """The recent-traffic window as a trainable ``Workload``."""
        from ..core.drift import observed_workload

        gen = self._gen
        return observed_workload(
            np.asarray(self._recent_rects, np.float32),
            np.asarray(self._recent_bms, np.uint32),
            gen.dataset.vocab_size,
        )

    def maybe_rebuild(self, force: bool = False, min_observed: int = 16) -> bool:
        """Warm-start rebuild + atomic swap when the drift monitor tripped
        (or ``force``). Returns True when a swap happened.

        The rebuild runs on the *merged* dataset (base + buffered inserts,
        deletes tombstoned) and the recently observed workload; the old
        generation keeps serving until the single reference store at the
        end -- the atomicity contract pinned by
        tests/test_delta_maintenance.py.
        """
        from ..core.build import warm_start_rebuild

        if not (force or self.monitor.triggered):
            return False
        if len(self._recent_rects) < min_observed:
            return False
        gen = self._gen
        merged = gen.delta_log.merged_dataset()
        wl = self.observed_workload()
        artifacts = warm_start_rebuild(
            merged, wl, gen.artifacts, self.build_config,
            assign=gen.delta_log.merged_assignment(),
        )
        new_gen = self._make_generation(artifacts, merged, seq=gen.seq + 1)
        self._gen = new_gen  # THE swap: one reference store
        if self.result_cache is not None:
            self.result_cache.invalidate()  # cached rows belong to the old gen
        self.monitor.rearm()  # back to warmup: re-learn the baseline
        self.swaps += 1
        return True


# ------------------------------------ legacy flat fallback (retired, §3.4)
# The hierarchy-free leaf-sharded scan now lives in launch/flat_legacy.py as
# a documented legacy path (dry-run/roofline surface + A/B floor); these
# re-exports keep historical imports working.
from .flat_legacy import (  # noqa: E402,F401
    OBJ_PER_LEAF as OBJ_PER_LEAF,
    TOP_LEAVES_LOCAL as TOP_LEAVES_LOCAL,
    lower_wisk_serve,
    make_inputs,
    wisk_serve_step,
)
