"""Executor layer: batched WISK retrieval over an ``IndexSnapshot``.

The serving stack is four explicit layers (DESIGN.md §3.4, §7):

* **snapshot** (serve/snapshot.py) -- the immutable pytree of device-resident
  index arrays,
* **plan** (serve/plan.py) -- batch bucketing plus the monotone frontier
  width cache, handed to descents as per-call ``ExecutionPlan``s,
* **delta** (serve/delta.py) -- optional device-resident insert/delete
  buffers merged into every descent (DESIGN.md §7),
* **executors** (this module) -- the jitted descent/verify pipelines that
  consume ``(snapshot, plan, delta)`` and return exact results + Eq.1
  counters.

Two range-query traversal modes share the leaf verification stage:

* ``mode="frontier"`` (default) -- sparse frontier descent: each query
  carries a padded int32 frontier of candidate node ids; per level the
  Pallas frontier kernel filters the gathered frontier tile (MBR intersect
  + bitmap AND) and survivors' children are expanded through device-resident
  CSR child arrays into the next frontier, compacted with a prefix-sum
  scatter. Per-level work is O(M * frontier_width), so the learned
  hierarchy's pruning shows up as wall-clock, not just as a counter.
* ``mode="dense"`` -- the original level-synchronous path kept for A/B
  benchmarking: an (M, n_level) active mask and dense (n_up, n_down) int8
  child matrices; per-level work is O(M * n_level) regardless of
  selectivity.

Frontier expansion widths come from the caller's ``PlanCache`` (default: a
per-snapshot cache, ``plan.default_plan_cache``): the descent runs at cached
per-level widths and fetches every level's actual child-count maximum in ONE
batched device->host sync at the end; if any level overflowed its cached
width the (rare, at most log2(level width) times ever) lossless retry
re-descends with exact per-level syncs and grows the cache. Steady state
therefore has no per-level blocking syncs (DESIGN.md §3.2).

``retrieve_knn`` is the third execution path (DESIGN.md §6): Boolean kNN as
a distance-bounded frontier descent. Each query carries a padded on-device
top-k buffer of (dist^2, object id) pairs; a beam-1 probe descent seeds the
buffer, the bounded sweep prunes frontier nodes whose squared MBR
min-distance (Pallas ``knn_filter`` kernel) exceeds the current k-th best
before expansion, and surviving leaves are verified in ascending
min-distance chunks inside one ``lax.scan``, re-tightening the bound after
every chunk until the remaining leaves are bounded out.

All modes return exact results (validated against core.query in
tests/test_query_parity.py and tests/test_knn_parity.py) plus Eq.1-style
cost counters:

* ``nodes_checked`` -- nodes whose MBR/bitmap were examined for the query
  (frontier-resident nodes only; matches ``execute_serial``'s
  ``nodes_accessed``),
* ``nodes_scanned`` -- slots the kernels actually touched (padded frontier
  widths, or full level widths in dense mode) -- the honest device-work
  measure the benchmark compares,
* ``verified``/``overflow`` -- Eq.1 verification cost and ``max_leaves``
  spill accounting (kNN: ``verified``/``leaves_verified``/``pruned``).

Bandwidth-lean descent (DESIGN.md §3.5): when the snapshot carries narrow
planes (int16 rank-coded shadow MBRs + coordinate dictionaries,
serve/snapshot.py:encode_mbr_planes) and no delta is live, the frontier and
kNN level filters run on those planes plus per-query *packed* bitmap words
(ops.pack_query_words), moving ~F*8 + F*Wp*4 bytes per (query, level)
instead of F*16 + F*W*4. Dequantization happens inside the kernels via the
dictionaries, so survivors/distances are bit-identical to the f32 path --
the ``quantized`` knob on ``retrieve``/``retrieve_knn`` exists only for A/B.

Incremental serving (DESIGN.md §7): every executor takes an optional
``delta`` (serve/delta.py:DeltaBuffer). When present, descents filter
against the delta's *augmented* per-level MBR/bitmap arrays (widened by
buffered inserts, so no level can prune a node whose subtree holds a
buffered match), the verify stages check each selected leaf's insert-buffer
slots alongside its snapshot object block, and deleted objects are masked
out of verification and the kNN top-k merge. ``delta=None`` (an empty
pytree) is the static fast path -- zero merge overhead.

The data-parallel distributed front doors (``serve_sharded`` /
``serve_knn_sharded``) live in launch/wisk_serve.py; they shard_map the
same per-level steps over the mesh's data axes with the snapshot (and any
delta) replicated.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs

# round_up_bucket lives in core.query so construction (core.partition) can
# share the exact same bucket discipline; re-exported here for callers
# (launch.wisk_serve, tests) that address it through the serving engine.
from ..core.query import round_up_bucket  # noqa: F401
from ..core.types import Workload
from ..kernels import ops
from .delta import DeltaBuffer
from .plan import ExecutionPlan, PlanCache, default_plan_cache
from .snapshot import IndexSnapshot  # noqa: F401  (re-export)


def _level_arrays(snap: IndexSnapshot, delta: Optional[DeltaBuffer], li: int):
    """The (mbrs, bitmaps) a descent filters level ``li`` against: the
    delta's insert-widened arrays when a delta is live, else the frozen
    snapshot arrays."""
    if delta is not None:
        return delta.aug_mbrs[li], delta.aug_bms[li]
    return snap.level_mbrs[li], snap.level_bms[li]


def _narrow_words(q_bm, delta, snap: IndexSnapshot, quantized: Optional[bool]):
    """The packed query words driving the bandwidth-lean descent, or None.

    ``quantized=None`` (auto) packs whenever the snapshot carries narrow
    planes and no delta is live (a live delta's insert-widened MBRs are not
    in the snapshot's coordinate dictionaries, so the descent falls back to
    the f32 planes -- DESIGN.md §3.5). ``quantized=False`` forces the f32
    full-width A/B baseline. Host-side: Wp must be a static shape.
    """
    if quantized is False or delta is not None or not snap.has_narrow_planes:
        return None
    return ops.pack_query_words(np.asarray(q_bm))


# ------------------------------------------------------------ frontier steps
@jax.jit
def _filter_frontier_level(mbrs, bms, q_rects, q_bm, frontier):
    """Gather frontier node tiles and run the Pallas frontier kernel."""
    with jax.named_scope("filter"):
        valid = frontier >= 0
        safe = jnp.clip(frontier, 0, mbrs.shape[0] - 1)
        surv = ops.filter_frontier(
            q_rects, q_bm, mbrs[safe], bms[safe], valid.astype(jnp.int8)
        )
        return surv, jnp.sum(valid, axis=1).astype(jnp.int32)


@jax.jit
def _filter_frontier_level_narrow(codes, bms, dict_x, dict_y, q_rects, wids, bits, frontier):
    """Bandwidth-lean twin of ``_filter_frontier_level``: gathers int16 MBR
    rank codes and only the query's packed bitmap word planes (the (M, F, W)
    slab shrinks to (M, F, Wp)), then runs the narrow Pallas kernel --
    bit-identical survivors (tests/test_query_parity.py)."""
    with jax.named_scope("filter"):
        valid = frontier >= 0
        safe = jnp.clip(frontier, 0, codes.shape[0] - 1)
        f_bm = bms[safe[:, :, None], wids[:, None, :]]  # (M, F, Wp)
        surv = ops.filter_frontier_narrow(
            q_rects, bits, codes[safe], f_bm, valid.astype(jnp.int8), dict_x, dict_y
        )
        return surv, jnp.sum(valid, axis=1).astype(jnp.int32)


@jax.jit
def _frontier_child_counts(child_counts, frontier, surv):
    """Per-query number of children the surviving frontier will expand to."""
    with jax.named_scope("expand"):
        safe = jnp.clip(frontier, 0, child_counts.shape[0] - 1)
        return jnp.sum(jnp.where(surv > 0, child_counts[safe], 0), axis=1)


@functools.partial(jax.jit, static_argnames=("f_next",))
def _expand_frontier(child_table, frontier, surv, f_next: int):
    """CSR gather of survivors' children + prefix-sum compaction.

    The hierarchy is a tree, so gathered child rows are disjoint and the
    compacted frontier has no duplicates. ``f_next`` must be >= the max
    per-query child count (guaranteed by the caller's planning), so the
    descent is lossless.
    """
    M, F = frontier.shape
    with jax.named_scope("expand"):
        safe = jnp.clip(frontier, 0, child_table.shape[0] - 1)
        cand = jnp.where((surv > 0)[:, :, None], child_table[safe], -1).reshape(M, -1)
        validc = cand >= 0
        pos = jnp.cumsum(validc, axis=1) - 1
        pos = jnp.where(validc & (pos < f_next), pos, f_next)  # f_next = trash slot
        nxt = jnp.full((M, f_next + 1), -1, jnp.int32)
        nxt = nxt.at[jnp.arange(M)[:, None], pos].set(cand, mode="drop")
        return nxt[:, :f_next]


@functools.partial(jax.jit, static_argnames=("take", "n_leaf"))
def _select_leaves_frontier(frontier, surv, take: int, n_leaf: int):
    """Up to ``take`` surviving leaves per query, smallest leaf id first.

    Keying top-k by ``n_leaf - leaf_id`` reproduces the dense path's
    tie-break (top_k prefers lower indices), so dense and frontier modes
    drop the *same* leaves under ``max_leaves`` overflow.
    """
    key = jnp.where(surv > 0, n_leaf - frontier, 0)
    val, _ = jax.lax.top_k(key, take)
    leaf_ok = val > 0
    top_leaf = jnp.where(leaf_ok, n_leaf - val, 0)
    overflow = jnp.maximum(jnp.sum((surv > 0).astype(jnp.int32), axis=1) - take, 0)
    return top_leaf, leaf_ok, overflow


# -------------------------------------- index-sharded collectives (DESIGN §3.4)
def _gather_cat(x, index_axis: str):
    """all_gather over the ``index`` mesh axis, shards concatenated along
    axis 1: the (M, F) per-shard view becomes the (M, S*F) global view.
    Traced inside shard_map bodies only."""
    g = jax.lax.all_gather(x, index_axis)  # (S, M, ...)
    return jnp.moveaxis(g, 0, 1).reshape(x.shape[0], -1)


def _select_leaves_indexed(
    frontier, surv, leaf_gid, take_g: int, take_loc: int, n_shards: int,
    index_axis: str,
):
    """Index-sharded twin of ``_select_leaves_frontier``: keep the globally
    ``take_g`` smallest-GLOBAL-id surviving leaves, exactly matching the
    single-device selection (and therefore its ``overflow`` drops).

    One bound exchange: each shard gathers its ``take_loc`` smallest
    surviving global leaf ids, the all-gathered (S*take_loc) candidates are
    sorted, and the ``take_g``-th smallest becomes the keep threshold. A
    shard can contribute at most ``take_loc`` (>= its survivor count, the
    caller passes its leaf frontier width) of the global winners, so the
    threshold is exact. ``overflow`` is the psum'd global survivor count
    beyond ``take_g`` -- identical per query to the single-device counter.
    """
    K = leaf_gid.shape[0]
    ok = (surv > 0) & (frontier >= 0)
    gid = jnp.where(ok, leaf_gid[jnp.clip(frontier, 0, K - 1)], _ID_SENTINEL)
    neg, _ = jax.lax.top_k(_ID_SENTINEL - gid, take_loc)
    small = _ID_SENTINEL - neg  # ascending local minima, sentinel-padded
    g = jax.lax.all_gather(small, index_axis)  # (S, M, take_loc)
    g = jnp.sort(jnp.moveaxis(g, 0, 1).reshape(small.shape[0], -1), axis=1)
    thr = g[:, min(take_g, n_shards * take_loc) - 1]
    keep = (ok & (gid <= thr[:, None])).astype(jnp.int8)
    top_leaf, leaf_ok, _ = _select_leaves_frontier(frontier, keep, take_loc, K)
    total = jax.lax.psum(jnp.sum(ok.astype(jnp.int32), axis=1), index_axis)
    overflow = jnp.maximum(total - take_g, 0)
    return top_leaf, leaf_ok, overflow


def _snap_cbank(snap: IndexSnapshot, compact: Optional[bool]):
    """The snapshot's compact leaf bank as a ``(leaf_terms, obj_cbm,
    obj_sig)`` triple, or None. ``compact=None`` (auto) uses it whenever the
    snapshot carries one; ``False`` forces the full-width A/B baseline."""
    if compact is False or not snap.has_compact_bank:
        return None
    return (snap.leaf_terms, snap.leaf_obj_cbm, snap.leaf_obj_sig)


def _verify_delta_slots(q_rects, q_bm, top_leaf, leaf_ok, delta, q_cbm, q_sig):
    """Verify the selected leaves' delta insert-buffer slots (DESIGN.md §7).

    The fused-with-delta merge (below): the fused kernel covers the base
    leaf blocks only, so the buffered inserts are gathered and verified
    here, through the compact kernel when the delta carries remapped slot
    bitmaps (``ins_cbm``; exact -- DeltaLog drops them the moment any
    buffered term falls outside its leaf's dictionary) and through the
    full-width ``verify_candidates`` otherwise. Returns ``(ids, counts,
    kw_scanned)`` for the delta slots alone.
    """
    M = q_rects.shape[0]
    B = delta.slots_per_leaf
    ix = delta.ins_x[top_leaf].reshape(M, -1)
    iy = delta.ins_y[top_leaf].reshape(M, -1)
    iid = delta.ins_id[top_leaf].reshape(M, -1)
    ival = (iid >= 0) & jnp.repeat(leaf_ok, B, axis=1)
    if q_cbm is not None and delta.ins_cbm is not None:
        Wl = delta.ins_cbm.shape[2]
        icbm = delta.ins_cbm[top_leaf].reshape(M, -1, Wl)
        isig = delta.ins_sig[top_leaf].reshape(M, -1)
        match = ops.verify_candidates_compact(
            q_rects, q_cbm, q_sig, ix, iy, icbm, isig, ival.astype(jnp.int8)
        )
        kw = ((isig & jnp.repeat(q_sig, B, axis=1)) != 0) & jnp.any(
            (icbm & jnp.repeat(q_cbm, B, axis=1)) != 0, axis=-1
        )
    else:
        ibm = delta.ins_bm[top_leaf].reshape(M, -1, q_bm.shape[1])
        match = ops.verify_candidates(
            q_rects, q_bm, ix, iy, ibm, ival.astype(jnp.int8)
        )
        kw = jnp.any(ibm & q_bm[:, None, :] != 0, axis=-1)
    ids = jnp.where(match > 0, iid, -1)
    counts = jnp.sum(match.astype(jnp.int32), axis=1)
    kw_scanned = jnp.sum(kw & ival, axis=1)
    return ids, counts, kw_scanned


class _LeafBank(NamedTuple):
    """The snapshot arrays the leaf verify stage reads, as one pytree: the
    (K, OBJ) object coordinates and ids, the full-width (K, OBJ, W) bitmap
    slab, and the compact bank triple from ``_snap_cbank`` (None when the
    stage verifies on the full-width slab)."""

    obj_x: jnp.ndarray
    obj_y: jnp.ndarray
    obj_id: jnp.ndarray
    obj_bm: jnp.ndarray
    cbank: Optional[tuple]


def _stage_inputs(snap: IndexSnapshot, fused, fused_variant: Optional[str],
                  compact: Optional[bool]):
    """The verify stage's ``_LeafBank`` on ``snap`` and its ``fused`` flag
    and fused kernel variant with the callers' None (auto) resolved; an
    ``"auto"`` variant is priced by the ops layer from the bank's shapes."""
    bank = _LeafBank(
        snap.leaf_obj_x, snap.leaf_obj_y, snap.leaf_obj_id, snap.leaf_obj_bm,
        _snap_cbank(snap, compact),
    )
    return bank, fused is None or bool(fused), fused_variant or "auto"


def _verify_leaves(
    snap: IndexSnapshot, q_rects, q_bm, top_leaf, leaf_ok, delta=None, fused=None,
    fused_variant: Optional[str] = None, compact: Optional[bool] = None,
):
    """Capacity-bounded verification of the selected leaves (shared by modes).

    ``fused=None`` (auto) now ALWAYS routes the base leaf blocks through the
    fused gather+verify Pallas kernels (DESIGN.md §3.5): the selected
    leaves' object blocks are gathered and verified inside one kernel, so
    the ``(M, T*OBJ, W)`` candidate bitmap plane never round-trips HBM
    between the gather and ``skr_verify``. With a live ``delta`` the fused
    kernel sees an id bank masked by ``base_alive`` (deleted objects behave
    exactly like pad slots) and only the delta's insert-buffer slots go
    through the unfused ``_verify_delta_slots`` merge -- candidate order
    stays [base blocks, delta slots], identical to the wholesale unfused
    pipeline. ``fused=False`` forces that unfused pipeline (the A/B
    baseline); every combination returns identical ids/counters
    (tests/test_query_parity.py).

    ``compact=None`` (auto) verifies on the snapshot's leaf-local compact
    bank when it exists (``has_compact_bank``): queries are remapped into
    each selected leaf's vocabulary (``ops.remap_query_words``) and the
    kernels test a one-word signature before the ``Wl``-word plane --
    bit-identical ids and Eq.1 counters, ~W/Wl fewer verify bytes.
    ``compact=False`` forces the full-width slab.

    ``fused_variant`` picks the fused kernel: None (auto) compares the leaf
    bank's VMEM footprint (the compact bank's when it is in play) against
    ``ops.FUSED_VMEM_BANK_BYTES`` -- the VMEM-resident kernel below the
    cutoff, the kernel that DMAs only the selected leaf tiles above it -- so banks
    beyond VMEM keep the fused path instead of falling back to the unfused
    HBM round-trip. ``"vmem"``/``"prefetch"`` force a kernel (A/B rows,
    beyond-VMEM tests).

    Traced inside the sharded bodies; the single-device executors run the
    same math through the compiled ``_verify_stage``.
    """
    bank, fused, variant = _stage_inputs(snap, fused, fused_variant, compact)
    return _verify_bank(bank, q_rects, q_bm, top_leaf, leaf_ok, delta, fused, variant)


def _verify_bank(bank: _LeafBank, q_rects, q_bm, top_leaf, leaf_ok, delta, fused: bool,
                 variant: str):
    """``_verify_leaves``' body on a ``_LeafBank``, with ``fused`` and the
    fused kernel ``variant`` resolved by ``_stage_inputs``."""
    cbank = bank.cbank
    q_cbm = q_sig = None
    if cbank is not None:
        q_cbm, q_sig = ops.remap_query_words(q_bm, cbank[0], top_leaf)
    if fused:
        base_id = bank.obj_id
        if delta is not None:
            # deleted objects become pad slots for the fused base pass
            base_id = jnp.where(delta.base_alive > 0, bank.obj_id, -1)
        if cbank is not None:
            ids, kwv = ops.fused_gather_verify_compact(
                q_rects, q_cbm, q_sig, top_leaf, leaf_ok.astype(jnp.int8),
                bank.obj_x, bank.obj_y, cbank[1], cbank[2], base_id,
                variant=variant,
            )
        else:
            ids, kwv = ops.fused_gather_verify(
                q_rects, q_bm, top_leaf, leaf_ok.astype(jnp.int8),
                bank.obj_x, bank.obj_y, bank.obj_bm, base_id,
                variant=variant,
            )
        counts = jnp.sum((ids >= 0).astype(jnp.int32), axis=1)
        kw_scanned = jnp.sum(kwv, axis=1)
        if delta is not None:
            d_ids, d_counts, d_kw = _verify_delta_slots(
                q_rects, q_bm, top_leaf, leaf_ok, delta, q_cbm, q_sig
            )
            ids = jnp.concatenate([ids, d_ids], axis=1)
            counts = counts + d_counts
            kw_scanned = kw_scanned + d_kw
        return ids, counts, kw_scanned
    M = q_rects.shape[0]
    OBJ = bank.obj_x.shape[1]
    cx = bank.obj_x[top_leaf].reshape(M, -1)
    cy = bank.obj_y[top_leaf].reshape(M, -1)
    cid = bank.obj_id[top_leaf].reshape(M, -1)
    cval = (cid >= 0) & jnp.repeat(leaf_ok, OBJ, axis=1)
    if delta is not None:
        alive = delta.base_alive[top_leaf].reshape(M, -1)
        cval = cval & (alive > 0)
    if cbank is not None:
        Wl = cbank[1].shape[2]
        ccbm = cbank[1][top_leaf].reshape(M, -1, Wl)
        csig = cbank[2][top_leaf].reshape(M, -1)
        match = ops.verify_candidates_compact(
            q_rects, q_cbm, q_sig, cx, cy, ccbm, csig, cval.astype(jnp.int8)
        )
        kw = ((csig & jnp.repeat(q_sig, OBJ, axis=1)) != 0) & jnp.any(
            (ccbm & jnp.repeat(q_cbm, OBJ, axis=1)) != 0, axis=-1
        )
        counts = jnp.sum(match.astype(jnp.int32), axis=1)
        kw_scanned = jnp.sum(kw & cval, axis=1)
        ids = jnp.where(match > 0, cid, -1)
        if delta is not None:
            d_ids, d_counts, d_kw = _verify_delta_slots(
                q_rects, q_bm, top_leaf, leaf_ok, delta, q_cbm, q_sig
            )
            ids = jnp.concatenate([ids, d_ids], axis=1)
            counts = counts + d_counts
            kw_scanned = kw_scanned + d_kw
        return ids, counts, kw_scanned
    cbm = bank.obj_bm[top_leaf].reshape(M, -1, q_bm.shape[1])
    if delta is not None:
        B = delta.slots_per_leaf
        ix = delta.ins_x[top_leaf].reshape(M, -1)
        iy = delta.ins_y[top_leaf].reshape(M, -1)
        ibm = delta.ins_bm[top_leaf].reshape(M, -1, q_bm.shape[1])
        iid = delta.ins_id[top_leaf].reshape(M, -1)
        ival = (iid >= 0) & jnp.repeat(leaf_ok, B, axis=1)
        cx = jnp.concatenate([cx, ix], axis=1)
        cy = jnp.concatenate([cy, iy], axis=1)
        cbm = jnp.concatenate([cbm, ibm], axis=1)
        cid = jnp.concatenate([cid, iid], axis=1)
        cval = jnp.concatenate([cval, ival], axis=1)
    match = ops.verify_candidates(q_rects, q_bm, cx, cy, cbm, cval.astype(jnp.int8))
    counts = jnp.sum(match.astype(jnp.int32), axis=1)
    # keyword-matching candidates scanned (Eq.1 verification cost)
    kw_scanned = jnp.sum(
        (jnp.any(cbm & q_bm[:, None, :] != 0, axis=-1) & cval), axis=1
    )
    ids = jnp.where(match > 0, cid, -1)
    return ids, counts, kw_scanned


@functools.partial(jax.jit, static_argnames=("take", "n_leaf", "fused", "variant"))
def _verify_stage(
    bank: _LeafBank, q_rects, q_bm, frontier, surv, delta, take: int, n_leaf: int,
    fused: bool, variant: str,
):
    """The whole single-device verify stage as one program: leaf selection
    (``_select_leaves_frontier``), the query-word remap, the ``base_alive``
    mask, the fused kernel, the delta-slot verify and merge, and the counts.

    One program per shape: ``bank.cbank`` and ``delta`` (None, with or
    without ``ins_cbm``) enter as pytrees, so their structure keys the
    compile cache alongside the static ``take``/``n_leaf``/``fused``/
    ``variant``. Returns ``(ids, counts, kw_scanned, overflow)``.
    """
    top_leaf, leaf_ok, overflow = _select_leaves_frontier(frontier, surv, take, n_leaf)
    ids, counts, kw_scanned = _verify_bank(
        bank, q_rects, q_bm, top_leaf, leaf_ok, delta, fused, variant
    )
    return ids, counts, kw_scanned, overflow


def _run_verify_stage(
    snap: IndexSnapshot, q_rects, q_bm, frontier, surv, take: int, delta, fused,
    fused_variant: Optional[str], compact: Optional[bool],
):
    """``_verify_stage`` on ``snap`` with the caller's knobs resolved."""
    bank, fused, variant = _stage_inputs(snap, fused, fused_variant, compact)
    return _verify_stage(
        bank, q_rects, q_bm, frontier, surv, delta, take=take, n_leaf=snap.n_leaves,
        fused=fused, variant=variant,
    )


def _root_frontier(snap: IndexSnapshot, M: int) -> jnp.ndarray:
    n_root = int(snap.level_mbrs[0].shape[0])
    root = np.full((snap.root_width(),), -1, np.int32)
    root[:n_root] = np.arange(n_root, dtype=np.int32)
    return jnp.tile(jnp.asarray(root)[None, :], (M, 1))


def _local_root_frontier(width: int, n_root_local, M: int) -> jnp.ndarray:
    """Shard-local root frontier for the index-sharded descent: the first
    ``n_root_local`` (a per-shard device scalar -- shards own different
    numbers of root subtrees) slots hold local root ids, the rest are ``-1``
    pads. Masking by the REAL local count keeps psum'd ``nodes_checked``
    exactly equal to the single-device root scan."""
    slot = jnp.arange(width, dtype=jnp.int32)
    root = jnp.where(slot < n_root_local, slot, -1)
    return jnp.tile(root[None, :], (M, 1))


def _descend_frontier(
    snap: IndexSnapshot, q_rects, q_bm, plan: ExecutionPlan, delta=None, words=None,
    root=None,
):
    """Shared range-query frontier descent.

    ``plan.widths=None``: exact mode -- bucket each next frontier on the
    batch's actual occupancy, one blocking host sync per level (first descent
    and overflow retries). ``plan.widths=(...)``: cached mode -- no per-level
    syncs; per-level child-count maxima are returned as device scalars for
    the caller's single batched overflow check. ``delta`` swaps in the
    insert-widened level arrays (DESIGN.md §7). ``words`` (the
    ``(wids, bits)`` pair from ``ops.pack_query_words``) switches the level
    filters to the bandwidth-lean narrow planes -- int16 MBR rank codes and
    packed bitmap word planes, bit-identical survivors (DESIGN.md §3.5);
    requires ``snap.has_narrow_planes`` and no live delta. ``root`` overrides
    the level-0 frontier -- the index-sharded path starts each shard from its
    masked local root frontier (``_local_root_frontier``) instead of the full
    forest.
    """
    M = q_rects.shape[0]
    narrow = words is not None and delta is None and snap.has_narrow_planes
    frontier = root if root is not None else _root_frontier(snap, M)
    nodes_checked = jnp.zeros((M,), jnp.int32)
    used: List[int] = []
    needs: List = []
    surv = None
    for li in range(snap.n_levels):
        used.append(int(frontier.shape[1]))
        if narrow:
            surv, n_valid = _filter_frontier_level_narrow(
                snap.level_mbr_codes[li], snap.level_bms[li],
                snap.level_dict_x[li], snap.level_dict_y[li],
                q_rects, words[0], words[1], frontier,
            )
        else:
            mbrs, bms = _level_arrays(snap, delta, li)
            surv, n_valid = _filter_frontier_level(mbrs, bms, q_rects, q_bm, frontier)
        nodes_checked = nodes_checked + n_valid
        if li < snap.n_levels - 1:
            need = _frontier_child_counts(snap.child_counts[li], frontier, surv)
            f_next = plan.pick_width(need, li, needs)
            frontier = _expand_frontier(snap.child_table[li], frontier, surv, f_next)
    return frontier, surv, nodes_checked, used, needs


def _retrieve_frontier(
    snap: IndexSnapshot,
    q_rects: jnp.ndarray,
    q_bm: jnp.ndarray,
    max_leaves: int,
    cache: PlanCache,
    delta=None,
    fused=None,
    words=None,
    fused_variant: Optional[str] = None,
    compact: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    M = q_rects.shape[0]
    plan = cache.plan("skr", snap.n_levels - 1)
    descend = lambda p: _descend_frontier(snap, q_rects, q_bm, p, delta, words)
    with obs.span("wisk.descend"):
        out = descend(plan)
    retried = cache.check_and_retry(plan, out[-1], descend)
    frontier, surv, nodes_checked, used, _ = retried or out

    take = min(max_leaves, snap.n_leaves, int(frontier.shape[1]))
    with obs.span("wisk.verify"):
        ids, counts, kw_scanned, overflow = _run_verify_stage(
            snap, q_rects, q_bm, frontier, surv, take, delta, fused, fused_variant, compact
        )
    with obs.span("wisk.fetch"):
        ids, counts, nodes_checked, kw_scanned, overflow = fetched = [
            np.asarray(a) for a in (ids, counts, nodes_checked, kw_scanned, overflow)
        ]
    obs.count("skr.d2h_bytes", sum(a.nbytes for a in fetched))
    return dict(
        ids=ids,
        counts=counts,
        nodes_checked=nodes_checked.astype(np.int64),
        nodes_scanned=np.full((M,), sum(used), np.int64),
        verified=kw_scanned,
        overflow=overflow,
        frontier_widths=np.asarray(used, np.int32),
    )


# ------------------------------------------------------- kNN (Boolean, §6)
_ID_SENTINEL = np.int32(np.iinfo(np.int32).max)

# bf16 carries an 8-bit mantissa: rounding a finite f32 distance to bf16
# perturbs it by at most 2^-9 relative. The retry guard below divides by a
# 2^-6 margin -- comfortably conservative -- to lower-bound what the true
# f32 distance of a bf16-pruned node could have been.
_BF16_RISK_TOL = 2.0 ** -6


def _quantize_dist(d, knn_dtype: str):
    """Model reduced-precision distance math in the bounded sweep: round the
    kernel's f32 squared distances to bf16 (``knn_dtype="bf16"``). On TPU
    the cast moves into the kernel (halving the distance-plane bytes); the
    rounding here is the same numerics, so the retry contract is identical.
    """
    if knn_dtype == "bf16":
        return d.astype(jnp.bfloat16).astype(jnp.float32)
    return d


def _merge_topk(top_d, top_id, cand_d, cand_id, kb: int):
    """Merge candidates into the padded top-k buffer: lexicographic sort on
    (dist^2, object id) keeps equal-distance ties smallest-id-first -- the
    convention shared with the host paths (core.query)."""
    d_all = jnp.concatenate([top_d, cand_d], axis=1)
    id_all = jnp.concatenate([top_id, cand_id], axis=1)
    d_s, id_s = jax.lax.sort((d_all, id_all), dimension=1, num_keys=2)
    return d_s[:, :kb], id_s[:, :kb]


@jax.jit
def _knn_dist_level(mbrs, bms, points, q_bm, frontier):
    """Gather frontier node tiles and run the Pallas kNN distance kernel."""
    valid = frontier >= 0
    safe = jnp.clip(frontier, 0, mbrs.shape[0] - 1)
    d = ops.knn_frontier_dist(points, q_bm, mbrs[safe], bms[safe], valid.astype(jnp.int8))
    return d, jnp.sum(valid, axis=1).astype(jnp.int32)


@jax.jit
def _knn_dist_level_narrow(codes, bms, dict_x, dict_y, points, wids, bits, frontier):
    """Bandwidth-lean twin of ``_knn_dist_level`` (int16 rank codes +
    packed word planes; bit-identical distances)."""
    valid = frontier >= 0
    safe = jnp.clip(frontier, 0, codes.shape[0] - 1)
    f_bm = bms[safe[:, :, None], wids[:, None, :]]  # (M, F, Wp)
    d = ops.knn_frontier_dist_narrow(
        points, bits, codes[safe], f_bm, valid.astype(jnp.int8), dict_x, dict_y
    )
    return d, jnp.sum(valid, axis=1).astype(jnp.int32)


@jax.jit
def _probe_children(child_table, cur):
    safe = jnp.clip(cur, 0, child_table.shape[0] - 1)
    return jnp.where(cur[:, None] >= 0, child_table[safe], -1)


@jax.jit
def _probe_select(d, cand):
    best = jnp.argmin(d, axis=1)  # ties: lowest slot == smallest node id
    bd = jnp.take_along_axis(d, best[:, None], axis=1)[:, 0]
    nxt = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]
    return jnp.where(jnp.isfinite(bd), nxt, -1)


def _chunk_kw(q_bm, obj_bm, delta, cbank, leaves2d):
    """Keyword-overlap of each query against gathered leaf blocks.

    ``leaves2d`` is ``(M, T)`` leaf ids (clipped in here; invalid slots are
    masked by the callers' validity logic). Returns ``(kw_base (M, T, OBJ),
    kw_ins (M, T, B) or None)``. With ``cbank=(leaf_terms, obj_cbm,
    obj_sig)`` the test runs on the leaf-local compact plane -- queries are
    remapped once per (query, leaf slot) and a one-word signature gates the
    ``Wl``-word AND -- bit-identical to the full-width test (DESIGN.md
    §3.5). Delta insert slots use the delta's remapped ``ins_cbm`` when it
    carries one (exact: DeltaLog drops it on any out-of-dictionary term)
    and the full-width ``ins_bm`` otherwise.
    """
    if cbank is None:
        K = obj_bm.shape[0]
        safe = jnp.clip(leaves2d, 0, K - 1)
        kw = jnp.any((obj_bm[safe] & q_bm[:, None, None, :]) != 0, axis=-1)
        ikw = None
        if delta is not None:
            ikw = jnp.any(
                (delta.ins_bm[safe] & q_bm[:, None, None, :]) != 0, axis=-1
            )
        return kw, ikw
    leaf_terms, obj_cbm, obj_sig = cbank
    K = obj_cbm.shape[0]
    safe = jnp.clip(leaves2d, 0, K - 1)
    q_cbm, q_sig = ops.remap_query_words(q_bm, leaf_terms, leaves2d)
    sig_hit = (obj_sig[safe] & q_sig[:, :, None]) != 0
    kw = sig_hit & jnp.any((obj_cbm[safe] & q_cbm[:, :, None, :]) != 0, axis=-1)
    ikw = None
    if delta is not None:
        if delta.ins_cbm is not None:
            isig_hit = (delta.ins_sig[safe] & q_sig[:, :, None]) != 0
            ikw = isig_hit & jnp.any(
                (delta.ins_cbm[safe] & q_cbm[:, :, None, :]) != 0, axis=-1
            )
        else:
            ikw = jnp.any(
                (delta.ins_bm[safe] & q_bm[:, None, None, :]) != 0, axis=-1
            )
    return kw, ikw


@functools.partial(jax.jit, static_argnames=("kb",))
def _knn_probe_verify(
    points, q_bm, obj_x, obj_y, obj_bm, obj_id, leaf, top_d, top_id, kb: int,
    delta=None, cbank=None,
):
    """Verify the probe leaf's object block and seed the top-k buffer.

    With a live ``delta``, the probe leaf's insert-buffer slots join the
    candidate set and deleted snapshot objects are masked (a deleted object
    must not occupy a top-k slot or tighten the bound). ``cbank`` routes the
    keyword test through the compact leaf bank (``_chunk_kw``)."""
    safe = jnp.clip(leaf, 0, obj_x.shape[0] - 1)
    ox, oy = obj_x[safe], obj_y[safe]  # (M, OBJ)
    oid = obj_id[safe]
    kw2, ikw2 = _chunk_kw(q_bm, obj_bm, delta, cbank, safe[:, None])
    kw = kw2[:, 0]  # (M, OBJ)
    base_ok = oid >= 0
    if delta is not None:
        base_ok = base_ok & (delta.base_alive[safe] > 0)
        ox = jnp.concatenate([ox, delta.ins_x[safe]], axis=1)
        oy = jnp.concatenate([oy, delta.ins_y[safe]], axis=1)
        oid = jnp.concatenate([oid, delta.ins_id[safe]], axis=1)
        kw = jnp.concatenate([kw, ikw2[:, 0]], axis=1)
        base_ok = jnp.concatenate([base_ok, delta.ins_id[safe] >= 0], axis=1)
    dx = ox - points[:, 0:1]
    dy = oy - points[:, 1:2]
    od2 = dx * dx + dy * dy
    valid = base_ok & kw & (leaf >= 0)[:, None]
    cd = jnp.where(valid, od2, jnp.inf)
    cid = jnp.where(valid, oid, _ID_SENTINEL)
    top_d, top_id = _merge_topk(top_d, top_id, cd, cid, kb)
    return top_d, top_id, jnp.sum(valid, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def _bound_prune(d, top_d, k: int):
    """Frontier slots that survive the current k-th-best bound. ``<=`` keeps
    nodes at exactly the bound: they may hold an equal-distance object with
    a smaller id (the tie-break can still swap it in)."""
    bound = top_d[:, k - 1]
    alive = jnp.isfinite(d) & (d <= bound[:, None])
    pruned = jnp.sum(jnp.isfinite(d) & ~alive, axis=1).astype(jnp.int32)
    return alive.astype(jnp.int8), pruned


@functools.partial(jax.jit, static_argnames=("k", "kb", "ch"))
def _knn_leaf_phase(
    points, q_bm, leaf_d, frontier, probe_leaf,
    obj_x, obj_y, obj_bm, obj_id, top_d, top_id, k: int, kb: int, ch: int,
    delta=None, cbank=None,
):
    """Distance-ordered chunked leaf verification in one lax.scan.

    Leaves are sorted ascending by (min-dist, leaf id); each chunk of ``ch``
    leaves is re-checked against the bound as tightened by every previous
    chunk, so later (farther) chunks are usually bounded out entirely. The
    probe leaf is masked to +inf -- its objects are already in the buffer.
    With a live ``delta``, every chunk leaf's insert-buffer slots are
    verified alongside its snapshot block and deleted objects are masked
    out of the top-k merge.

    Also returns ``rm``, the per-query minimum over bounded-out chunk slots
    of ``dc * (1 - _BF16_RISK_TOL)`` -- the bf16 retry guard's conservative
    lower bound on what a pruned leaf could still contain (inf under f32
    serving or when nothing was pruned; see ``retrieve_knn``'s ``knn_dtype``),
    and ``live``, the number of chunks in which any (query, leaf) pair was
    still within its bound (the rest did no useful work).
    """
    M, F = leaf_d.shape
    d = jnp.where(frontier == probe_leaf[:, None], jnp.inf, leaf_d)
    d_s, leaf_s = jax.lax.sort((d, frontier), dimension=1, num_keys=2)
    nch = F // ch  # callers pick ch dividing F (power-of-two bucket widths)
    d_ch = jnp.moveaxis(d_s.reshape(M, nch, ch), 1, 0)
    l_ch = jnp.moveaxis(leaf_s.reshape(M, nch, ch), 1, 0)

    def step(carry, inp):
        top_d, top_id, lv, ver, pr, rm, live = carry
        dc, lc = inp  # (M, ch)
        bound = top_d[:, k - 1]
        active = jnp.isfinite(dc) & (dc <= bound[:, None])
        with jax.named_scope("gather"):
            safe = jnp.clip(lc, 0, obj_x.shape[0] - 1)
            ox, oy = obj_x[safe], obj_y[safe]  # (M, ch, OBJ)
            oid = obj_id[safe]
        with jax.named_scope("keyword"):
            kw, ikw = _chunk_kw(q_bm, obj_bm, delta, cbank, safe)
        base_ok = oid >= 0
        if delta is not None:
            with jax.named_scope("gather"):
                base_ok = base_ok & (delta.base_alive[safe] > 0)
                ox = jnp.concatenate([ox, delta.ins_x[safe]], axis=2)
                oy = jnp.concatenate([oy, delta.ins_y[safe]], axis=2)
                oid = jnp.concatenate([oid, delta.ins_id[safe]], axis=2)
                kw = jnp.concatenate([kw, ikw], axis=2)
                base_ok = jnp.concatenate([base_ok, delta.ins_id[safe] >= 0], axis=2)
        with jax.named_scope("distance"):
            dx = ox - points[:, 0][:, None, None]
            dy = oy - points[:, 1][:, None, None]
            od2 = dx * dx + dy * dy
            valid = base_ok & kw & active[:, :, None]
            cd = jnp.where(valid, od2, jnp.inf).reshape(M, -1)
            cid = jnp.where(valid, oid, _ID_SENTINEL).reshape(M, -1)
        with jax.named_scope("merge_topk"):
            top_d2, top_id2 = _merge_topk(top_d, top_id, cd, cid, kb)
        lv = lv + jnp.sum(active, axis=1).astype(jnp.int32)
        ver = ver + jnp.sum(valid, axis=(1, 2)).astype(jnp.int32)
        pr = pr + jnp.sum(jnp.isfinite(dc) & ~active, axis=1).astype(jnp.int32)
        lower = jnp.where(
            jnp.isfinite(dc) & ~active, dc * (1.0 - _BF16_RISK_TOL), jnp.inf
        )
        rm = jnp.minimum(rm, jnp.min(lower, axis=1))
        live = live + jnp.any(active).astype(jnp.int32)
        return (top_d2, top_id2, lv, ver, pr, rm, live), None

    z = jnp.zeros((M,), jnp.int32)
    rm0 = jnp.full((M,), jnp.inf, jnp.float32)
    (top_d, top_id, lv, ver, pr, rm, live), _ = jax.lax.scan(
        step, (top_d, top_id, z, z, z, rm0, jnp.int32(0)), (d_ch, l_ch)
    )
    return top_d, top_id, lv, ver, pr, rm, live


def _descend_knn(
    snap: IndexSnapshot, points, q_bm, k: int, kb: int, plan: ExecutionPlan, delta=None,
    words=None, knn_dtype: str = "f32", cbank=None,
):
    """Distance-bounded kNN descent (probe -> bounded sweep -> leaf chunks).

    Width discipline is identical to ``_descend_frontier``: exact mode syncs
    per level, cached mode runs sync-free and returns device maxima for the
    caller's batched overflow check. ``delta`` swaps in the insert-widened
    level arrays and merges buffered inserts / masks deletes in the verify
    stages (DESIGN.md §7). ``words`` switches the probe and sweep level
    filters to the bandwidth-lean narrow planes (bit-identical distances;
    leaf scoring stays on the exact f32 object bank either way).

    ``knn_dtype="bf16"`` rounds the bounded sweep's node distances to bf16
    before pruning and tracks ``risk`` -- the minimum conservative lower
    bound over everything pruned; the caller retries in exact f32 whenever
    ``risk`` reaches the final bound (``retrieve_knn``). Object distances in
    the verify stages stay exact f32 either way, so a descent whose risk
    stays above the final bound is already id-exact.
    """
    M = int(points.shape[0])
    L = snap.n_levels
    narrow = words is not None and delta is None and snap.has_narrow_planes

    def dist_level(li, fr):
        if narrow:
            return _knn_dist_level_narrow(
                snap.level_mbr_codes[li], snap.level_bms[li],
                snap.level_dict_x[li], snap.level_dict_y[li],
                points, words[0], words[1], fr,
            )
        mbrs, bms = _level_arrays(snap, delta, li)
        return _knn_dist_level(mbrs, bms, points, q_bm, fr)

    top_d = jnp.full((M, kb), jnp.inf, jnp.float32)
    top_id = jnp.full((M, kb), _ID_SENTINEL, jnp.int32)
    nodes_checked = jnp.zeros((M,), jnp.int32)
    pruned = jnp.zeros((M,), jnp.int32)

    # probe: beam-1 greedy descent to a leaf seeds the buffer, so the sweep
    # below starts with a finite bound and can prune before expansion
    cand = _root_frontier(snap, M)
    cur = None
    for li in range(L):
        if li > 0:
            cand = _probe_children(snap.child_table[li - 1], cur)
        d, nv = dist_level(li, cand)
        nodes_checked = nodes_checked + nv
        cur = _probe_select(d, cand)
    probe_leaf = cur
    top_d, top_id, ver0 = _knn_probe_verify(
        points, q_bm, snap.leaf_obj_x, snap.leaf_obj_y, snap.leaf_obj_bm, snap.leaf_obj_id,
        probe_leaf, top_d, top_id, kb, delta, cbank,
    )
    verified = ver0
    leaves_verified = (probe_leaf >= 0).astype(jnp.int32)

    # bounded sweep: full frontier descent, pruning against the k-th best
    frontier = _root_frontier(snap, M)
    used: List[int] = []
    needs: List = []
    leaf_d = None
    risk_min = jnp.full((M,), jnp.inf, jnp.float32)
    for li in range(L):
        used.append(int(frontier.shape[1]))
        d, nv = dist_level(li, frontier)
        d = _quantize_dist(d, knn_dtype)
        nodes_checked = nodes_checked + nv
        if li < L - 1:
            alive, pr = _bound_prune(d, top_d, k)
            pruned = pruned + pr
            lower = jnp.where(
                jnp.isfinite(d) & ~(alive > 0), d * (1.0 - _BF16_RISK_TOL), jnp.inf
            )
            risk_min = jnp.minimum(risk_min, jnp.min(lower, axis=1))
            need = _frontier_child_counts(snap.child_counts[li], frontier, alive)
            f_next = plan.pick_width(need, li, needs)
            frontier = _expand_frontier(snap.child_table[li], frontier, alive, f_next)
        else:
            leaf_d = d

    F = int(frontier.shape[1])
    ch = 4 if F % 4 == 0 else 1
    # a span only where this runs eagerly, not while a shard_map traces it
    traced = isinstance(points, jax.core.Tracer)
    with contextlib.nullcontext() if traced else obs.span("wisk.leaf_phase"):
        top_d, top_id, lv, ver, pr, rm, live = _knn_leaf_phase(
            points, q_bm, leaf_d, frontier, probe_leaf,
            snap.leaf_obj_x, snap.leaf_obj_y, snap.leaf_obj_bm, snap.leaf_obj_id,
            top_d, top_id, k, kb, ch, delta, cbank,
        )
    result = (
        top_d, top_id, nodes_checked, verified + ver,
        leaves_verified + lv, pruned + pr, used,
        jnp.minimum(risk_min, rm), (F // ch, live),
    )
    return result, needs


def _knn_leaf_phase_indexed(
    points, q_bm, leaf_d, frontier, probe_leaf, leaf_gid,
    obj_x, obj_y, obj_bm, obj_id, top_d, top_id, k: int, kb: int, ch: int,
    n_shards: int, index_axis: str, delta=None, cbank=None,
):
    """Index-sharded twin of ``_knn_leaf_phase`` (shard_map bodies only).

    Parity with the single-device leaf phase needs the *global* ascending
    (min-dist, global leaf id) chunk order, because each chunk's bound is
    tightened by every previous chunk. Each shard ranks its local leaves
    against the all-gathered global (dist, gid) key set and scatters them
    into their global-rank slots; slots owned by other shards stay
    ``(inf, -1)`` locally, so every shard walks the same global chunk
    sequence with exactly its own leaves materialized. After each chunk the
    shards exchange their local top-kb candidates and merge into a shared
    buffer -- the truncation is lossless (a chunk contributes at most kb of
    the new top-kb) -- so the bound sequence, and therefore which leaves get
    verified vs bounded out, is identical to the single-device scan.
    Counters are per-shard (each real leaf counted only by its owner); the
    caller psums them over ``index_axis``.
    """
    M, F = leaf_d.shape
    K = obj_x.shape[0]
    d = jnp.where(frontier == probe_leaf[:, None], jnp.inf, leaf_d)
    gid = jnp.where(frontier >= 0, leaf_gid[jnp.clip(frontier, 0, K - 1)], _ID_SENTINEL)
    gid = jnp.where(jnp.isfinite(d), gid, _ID_SENTINEL)
    d_s, gid_s, leaf_s = jax.lax.sort((d, gid, frontier), dimension=1, num_keys=2)

    # global rank of each local leaf under the (dist, gid) total order
    T = n_shards * F
    gd = _gather_cat(d_s, index_axis)  # (M, T)
    gg = _gather_cat(gid_s, index_axis)
    less = (gd[:, None, :] < d_s[:, :, None]) | (
        (gd[:, None, :] == d_s[:, :, None]) & (gg[:, None, :] < gid_s[:, :, None])
    )
    rank = jnp.sum(less, axis=2).astype(jnp.int32)  # (M, F)

    nch = -(-T // ch)
    rows = jnp.arange(M, dtype=jnp.int32)[:, None]
    fin = jnp.isfinite(d_s)
    tgt = jnp.where(fin, rank, nch * ch)  # pads land in the dump slot
    buf_d = jnp.full((M, nch * ch + 1), jnp.inf, jnp.float32)
    buf_l = jnp.full((M, nch * ch + 1), -1, jnp.int32)
    buf_d = buf_d.at[rows, tgt].set(jnp.where(fin, d_s, jnp.inf))
    buf_l = buf_l.at[rows, tgt].set(jnp.where(fin, leaf_s, -1))
    d_ch = jnp.moveaxis(buf_d[:, : nch * ch].reshape(M, nch, ch), 1, 0)
    l_ch = jnp.moveaxis(buf_l[:, : nch * ch].reshape(M, nch, ch), 1, 0)

    def step(carry, inp):
        top_d, top_id, lv, ver, pr, rm = carry
        dc, lc = inp  # (M, ch)
        bound = top_d[:, k - 1]
        active = jnp.isfinite(dc) & (dc <= bound[:, None])
        safe = jnp.clip(lc, 0, K - 1)
        ox, oy = obj_x[safe], obj_y[safe]  # (M, ch, OBJ)
        oid = obj_id[safe]
        kw, ikw = _chunk_kw(q_bm, obj_bm, delta, cbank, safe)
        base_ok = oid >= 0
        if delta is not None:
            base_ok = base_ok & (delta.base_alive[safe] > 0)
            ox = jnp.concatenate([ox, delta.ins_x[safe]], axis=2)
            oy = jnp.concatenate([oy, delta.ins_y[safe]], axis=2)
            oid = jnp.concatenate([oid, delta.ins_id[safe]], axis=2)
            kw = jnp.concatenate([kw, ikw], axis=2)
            base_ok = jnp.concatenate([base_ok, delta.ins_id[safe] >= 0], axis=2)
        dx = ox - points[:, 0][:, None, None]
        dy = oy - points[:, 1][:, None, None]
        od2 = dx * dx + dy * dy
        valid = base_ok & kw & active[:, :, None]
        cd = jnp.where(valid, od2, jnp.inf).reshape(M, -1)
        cid = jnp.where(valid, oid, _ID_SENTINEL).reshape(M, -1)
        loc_d = jnp.full((M, kb), jnp.inf, jnp.float32)
        loc_id = jnp.full((M, kb), _ID_SENTINEL, jnp.int32)
        loc_d, loc_id = _merge_topk(loc_d, loc_id, cd, cid, kb)
        g_d = _gather_cat(loc_d, index_axis)  # (M, S*kb)
        g_id = _gather_cat(loc_id, index_axis)
        top_d2, top_id2 = _merge_topk(top_d, top_id, g_d, g_id, kb)
        lv = lv + jnp.sum(active, axis=1).astype(jnp.int32)
        ver = ver + jnp.sum(valid, axis=(1, 2)).astype(jnp.int32)
        pr = pr + jnp.sum(jnp.isfinite(dc) & ~active, axis=1).astype(jnp.int32)
        lower = jnp.where(
            jnp.isfinite(dc) & ~active, dc * (1.0 - _BF16_RISK_TOL), jnp.inf
        )
        rm = jnp.minimum(rm, jnp.min(lower, axis=1))
        return (top_d2, top_id2, lv, ver, pr, rm), None

    z = jnp.zeros((M,), jnp.int32)
    rm0 = jnp.full((M,), jnp.inf, jnp.float32)
    (top_d, top_id, lv, ver, pr, rm), _ = jax.lax.scan(
        step, (top_d, top_id, z, z, z, rm0), (d_ch, l_ch)
    )
    return top_d, top_id, lv, ver, pr, rm


def _descend_knn_indexed(
    snap: IndexSnapshot, root_gid, leaf_gid, n_root_local, points, q_bm,
    k: int, kb: int, plan: ExecutionPlan, n_shards: int, index_axis: str,
    delta=None, words=None, cbank=None,
):
    """Index-sharded kNN descent (shard_map bodies only; DESIGN.md §3.4).

    ``snap`` is a shard's ``PartitionedSnapshot.local_view()``;
    ``root_gid``/``leaf_gid`` map local slots to global ids and
    ``n_root_local`` is the shard's real root count. Three collective
    exchanges keep exact parity with ``_descend_knn``:

    1. *Probe*: every shard scans its local roots (their psum'd count equals
       the global root scan), then the shards exchange their best
       ``(dist, root gid)`` -- the lexicographic minimum picks the one
       *canonical* shard whose greedy chain matches the single-device
       probe's smallest-id argmin tie-break. Only the canonical shard counts
       sub-root probe levels and verifies its probe leaf; the seeded top-k
       buffer is then shared via an all-gather + sort.
    2. *Sweep*: purely shard-local -- the bound is static during the sweep,
       so per-node prune decisions match the single-device sweep and the
       counters psum exactly.
    3. *Leaf phase*: ``_knn_leaf_phase_indexed`` walks the global
       (dist, gid)-ordered chunk sequence with a shared bound.

    Always exact f32 (``knn_dtype`` stays a single-device/replicated-path
    flag). Returns the 7-tuple result (no risk) plus per-shard ``needs``.
    """
    M = int(points.shape[0])
    L = snap.n_levels
    narrow = words is not None and delta is None and snap.has_narrow_planes

    def dist_level(li, fr):
        if narrow:
            return _knn_dist_level_narrow(
                snap.level_mbr_codes[li], snap.level_bms[li],
                snap.level_dict_x[li], snap.level_dict_y[li],
                points, words[0], words[1], fr,
            )
        mbrs, bms = _level_arrays(snap, delta, li)
        return _knn_dist_level(mbrs, bms, points, q_bm, fr)

    top_d = jnp.full((M, kb), jnp.inf, jnp.float32)
    top_id = jnp.full((M, kb), _ID_SENTINEL, jnp.int32)
    nodes_checked = jnp.zeros((M,), jnp.int32)
    pruned = jnp.zeros((M,), jnp.int32)

    # probe: local root scan, then one (dist, gid) exchange elects the
    # canonical shard that owns the single-device greedy chain
    cand = _local_root_frontier(snap.root_width(), n_root_local, M)
    d0, nv0 = dist_level(0, cand)
    nodes_checked = nodes_checked + nv0
    best = jnp.argmin(d0, axis=1)  # ties: lowest slot == smallest gid
    bd = jnp.take_along_axis(d0, best[:, None], axis=1)[:, 0]
    bslot = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]
    bgid = jnp.where(
        (bslot >= 0) & jnp.isfinite(bd),
        root_gid[jnp.clip(bslot, 0, root_gid.shape[0] - 1)], _ID_SENTINEL,
    )
    g_bd = jax.lax.all_gather(jnp.where(jnp.isfinite(bd), bd, jnp.inf), index_axis)
    g_bg = jax.lax.all_gather(bgid, index_axis)  # (S, M)
    wd, wg = jax.lax.sort((g_bd, g_bg), dimension=0, num_keys=2)
    win_d, win_gid = wd[0], wg[0]
    canonical = jnp.isfinite(bd) & (bd == win_d) & (bgid == win_gid)
    cur = jnp.where(jnp.isfinite(bd), bslot, -1)
    for li in range(1, L):
        cand = _probe_children(snap.child_table[li - 1], cur)
        d, nv = dist_level(li, cand)
        nodes_checked = nodes_checked + jnp.where(canonical, nv, 0)
        cur = _probe_select(d, cand)
    probe_leaf = jnp.where(canonical, cur, -1)
    top_d, top_id, ver0 = _knn_probe_verify(
        points, q_bm, snap.leaf_obj_x, snap.leaf_obj_y, snap.leaf_obj_bm,
        snap.leaf_obj_id, probe_leaf, top_d, top_id, kb, delta, cbank,
    )
    verified = ver0
    leaves_verified = (probe_leaf >= 0).astype(jnp.int32)
    # share the canonical shard's seed so every shard sweeps the same bound
    g_d = _gather_cat(top_d, index_axis)
    g_id = _gather_cat(top_id, index_axis)
    d_sh, id_sh = jax.lax.sort((g_d, g_id), dimension=1, num_keys=2)
    top_d, top_id = d_sh[:, :kb], id_sh[:, :kb]

    # bounded sweep: shard-local (the bound is static until the leaf phase)
    frontier = _local_root_frontier(snap.root_width(), n_root_local, M)
    used: List[int] = []
    needs: List = []
    leaf_d = None
    for li in range(L):
        used.append(int(frontier.shape[1]))
        d, nv = dist_level(li, frontier)
        nodes_checked = nodes_checked + nv
        if li < L - 1:
            alive, pr = _bound_prune(d, top_d, k)
            pruned = pruned + pr
            need = _frontier_child_counts(snap.child_counts[li], frontier, alive)
            f_next = plan.pick_width(need, li, needs)
            frontier = _expand_frontier(snap.child_table[li], frontier, alive, f_next)
        else:
            leaf_d = d

    F = int(frontier.shape[1])
    ch = 4 if F % 4 == 0 else 1
    top_d, top_id, lv, ver, pr, _ = _knn_leaf_phase_indexed(
        points, q_bm, leaf_d, frontier, probe_leaf, leaf_gid,
        snap.leaf_obj_x, snap.leaf_obj_y, snap.leaf_obj_bm, snap.leaf_obj_id,
        top_d, top_id, k, kb, ch, n_shards, index_axis, delta, cbank,
    )
    result = (
        top_d, top_id, nodes_checked, verified + ver,
        leaves_verified + lv, pruned + pr, used,
    )
    return result, needs


def retrieve_knn(
    snap: IndexSnapshot,
    points,
    q_bm,
    k: int,
    min_topk_bucket: int = 8,
    plan_cache: Optional[PlanCache] = None,
    delta: Optional[DeltaBuffer] = None,
    quantized: Optional[bool] = None,
    knn_dtype: str = "f32",
    compact: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """Batched Boolean kNN over the device-resident index (DESIGN.md §6).

    Returns per-query ``ids``/``dist2`` of the exact k nearest keyword-
    matching objects (ascending (dist^2, id); ``-1``-padded when fewer than
    k objects match) plus cost counters: ``nodes_checked``, ``verified``
    (kw-matching objects scored), ``leaves_verified`` (leaf blocks
    verified), and ``pruned`` (kw-matching frontier slots bounded out).
    ``delta`` merges buffered inserts/deletes on the fly (DESIGN.md §7).
    ``quantized=None`` (auto) descends on the snapshot's narrow planes when
    available and no delta is live; ``False`` forces the f32 full-width A/B
    baseline. ``compact=None`` (auto) runs every leaf keyword test on the
    leaf-local compact bank when the snapshot carries one (signature
    prefilter + ``Wl``-word plane; distance math untouched); ``False``
    forces the full-width slab. Results are bit-identical every way
    (DESIGN.md §3.5).

    ``knn_dtype="bf16"`` runs the bounded sweep's node-distance pruning in
    bf16 (ROADMAP item 5). Object distances stay exact f32, so the result
    differs from f32 only when a node was pruned on a rounded-down distance
    that an exact sweep would have expanded; the descent tracks a
    conservative ``risk`` lower bound over everything pruned and retries the
    whole batch in exact f32 whenever that risk reaches the final k-th
    bound. The output dict gains ``knn_dtype_retried`` and ids are always
    identical to the f32 path.
    """
    if knn_dtype not in ("f32", "bf16"):
        raise ValueError(f"knn_dtype must be 'f32' or 'bf16', got {knn_dtype!r}")
    with obs.span("wisk.prep"):
        words = _narrow_words(q_bm, delta, snap, quantized) if k > 0 else None
        points = jnp.asarray(points, jnp.float32)
        q_bm = jnp.asarray(q_bm, jnp.uint32)
    M = int(points.shape[0])
    if k <= 0:
        z = np.zeros(M, np.int64)
        return dict(
            ids=np.zeros((M, 0), np.int32), dist2=np.zeros((M, 0), np.float32),
            nodes_checked=z, verified=z.copy(), leaves_verified=z.copy(),
            pruned=z.copy(), frontier_widths=np.zeros(0, np.int32),
        )
    kb = round_up_bucket(k, min_topk_bucket)
    cache = plan_cache if plan_cache is not None else default_plan_cache(snap)
    cbank = _snap_cbank(snap, compact)
    plan = cache.plan("knn", snap.n_levels - 1)
    descend = lambda p: _descend_knn(
        snap, points, q_bm, k, kb, p, delta, words, knn_dtype=knn_dtype, cbank=cbank
    )
    with obs.span("wisk.descend"):
        out = descend(plan)
    retried = cache.check_and_retry(plan, out[-1], descend)
    (top_d, top_id, nodes_checked, verified, leaves_verified,
     pruned, used, risk, (n_chunks, live_chunks)) = (retried or out)[0]
    if knn_dtype == "bf16":
        bound = np.asarray(top_d[:, k - 1])
        risk_np = np.asarray(risk)
        if bool(np.any(np.isfinite(risk_np) & (risk_np <= bound))):
            exact = retrieve_knn(
                snap, points, q_bm, k, min_topk_bucket=min_topk_bucket,
                plan_cache=cache, delta=delta, quantized=quantized,
                knn_dtype="f32", compact=compact,
            )
            exact["knn_dtype_retried"] = True
            return exact
    fin = jnp.isfinite(top_d[:, :k])
    ids = jnp.where(fin, top_id[:, :k], -1)
    with obs.span("wisk.fetch"):
        ids, dist2, *counters, live_chunks = (np.asarray(a) for a in (
            ids, top_d[:, :k], nodes_checked, verified, leaves_verified, pruned, live_chunks
        ))
    obs.count("knn.chunks", n_chunks)
    obs.count("knn.live_chunks", int(live_chunks))
    nodes_checked, verified, leaves_verified, pruned = (c.astype(np.int64) for c in counters)
    result = dict(
        ids=ids,
        dist2=dist2,
        nodes_checked=nodes_checked,
        verified=verified,
        leaves_verified=leaves_verified,
        pruned=pruned,
        frontier_widths=np.asarray(used, np.int32),
    )
    if knn_dtype == "bf16":
        result["knn_dtype_retried"] = False
    return result


# --------------------------------------------------------------- dense path
def _retrieve_dense(
    snap: IndexSnapshot, q_rects: jnp.ndarray, q_bm: jnp.ndarray, max_leaves: int,
    delta=None, fused=None, compact: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    if len(snap.child_matrix) != len(snap.level_mbrs) - 1:
        raise ValueError("dense mode needs IndexSnapshot.build(..., dense=True)")
    M = q_rects.shape[0]
    active = jnp.ones((M, snap.level_mbrs[0].shape[0]), jnp.int8)
    nodes_checked = jnp.zeros((M,), jnp.int32)
    for li in range(len(snap.level_mbrs)):
        mbrs, bms = _level_arrays(snap, delta, li)
        rel = ops.filter_pairs(q_rects, q_bm, mbrs, bms)
        nodes_checked = nodes_checked + jnp.sum(active > 0, axis=1)
        hit = (rel > 0) & (active > 0)
        if li < len(snap.level_mbrs) - 1:
            active = (hit.astype(jnp.int8) @ snap.child_matrix[li] > 0).astype(jnp.int8)
        else:
            leaf_hit = hit
    # up to max_leaves relevant leaves per query (lowest leaf id first): the
    # frontier stage's selection over a frontier of every leaf
    n_leaf = leaf_hit.shape[1]
    every_leaf = jnp.broadcast_to(jnp.arange(n_leaf, dtype=jnp.int32), (M, n_leaf))
    ids, counts, kw_scanned, overflow = _run_verify_stage(
        snap, q_rects, q_bm, every_leaf, leaf_hit.astype(jnp.int8),
        min(max_leaves, n_leaf), delta, fused, None, compact,
    )
    return dict(
        ids=np.asarray(ids),
        counts=np.asarray(counts),
        nodes_checked=np.asarray(nodes_checked, np.int64),
        # padded (tile-aligned) widths filter_pairs actually scores, so the
        # A/B metric stays symmetric with the frontier path (whose power-of-
        # two buckets are already tile-exact)
        nodes_scanned=np.full(
            (M,),
            sum(ops.padded_tile_len(int(l.shape[0])) for l in snap.level_mbrs),
            np.int64,
        ),
        verified=np.asarray(kw_scanned),
        overflow=np.asarray(overflow),
    )


def retrieve(
    snap: IndexSnapshot,
    q_rects: jnp.ndarray,
    q_bm: jnp.ndarray,
    max_leaves: int = 32,
    mode: str = "frontier",
    plan_cache: Optional[PlanCache] = None,
    delta: Optional[DeltaBuffer] = None,
    fused: Optional[bool] = None,
    quantized: Optional[bool] = None,
    fused_variant: Optional[str] = None,
    compact: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """Batched SKR retrieval. Exact as long as <= max_leaves leaves are
    relevant per query (the spill is counted in ``overflow``).

    ``mode="frontier"`` is the sparse descent; ``mode="dense"`` the original
    full-level scan (kept for A/B benchmarking). ``plan_cache`` carries the
    frontier width state across calls; None uses the per-snapshot default.
    ``delta`` merges buffered inserts/deletes on the fly (DESIGN.md §7).
    ``fused`` picks the leaf verification pipeline (DESIGN.md §3.5): None
    (auto) uses the fused gather+verify kernels on the base leaf blocks --
    with a live delta only the insert-buffer slots take the unfused merge;
    False forces the wholesale unfused A/B baseline. ``fused_variant``
    further picks the fused kernel (None auto-selects by leaf-bank bytes vs
    ``ops.FUSED_VMEM_BANK_BYTES``; ``"vmem"``/``"prefetch"`` force one).
    ``quantized`` controls the bandwidth-lean frontier descent (DESIGN.md
    §3.5): None (auto) uses the snapshot's int16 shadow MBR planes + packed
    bitmap words when available and no delta is live; False forces the f32
    full-width baseline. ``compact`` controls leaf verification width
    (DESIGN.md §3.5): None (auto) verifies on the leaf-local compact
    vocabulary bank (remapped query words + one-word signature prefilter)
    whenever the snapshot carries one; False forces the global full-width
    slab. Every combination is id- and counter-exact.
    """
    with obs.span("wisk.prep"):
        words = _narrow_words(q_bm, delta, snap, quantized) if mode == "frontier" else None
        q_rects = jnp.asarray(q_rects, jnp.float32)
        q_bm = jnp.asarray(q_bm, jnp.uint32)
    if mode == "frontier":
        cache = plan_cache if plan_cache is not None else default_plan_cache(snap)
        return _retrieve_frontier(
            snap, q_rects, q_bm, max_leaves, cache, delta, fused, words,
            fused_variant, compact,
        )
    if mode == "dense":
        # the dense A/B path scores full levels against full-width planes by
        # design; the narrow planes only accelerate the frontier descent
        return _retrieve_dense(snap, q_rects, q_bm, max_leaves, delta, fused, compact)
    raise ValueError(f"unknown retrieve mode {mode!r}")


def retrieve_workload(
    snap: IndexSnapshot,
    workload: Workload,
    max_leaves: int = 32,
    mode: str = "frontier",
    plan_cache: Optional[PlanCache] = None,
    delta: Optional[DeltaBuffer] = None,
    fused: Optional[bool] = None,
    quantized: Optional[bool] = None,
    fused_variant: Optional[str] = None,
    compact: Optional[bool] = None,
):
    return retrieve(
        snap,
        workload.rects,
        workload.kw_bitmap,
        max_leaves,
        mode=mode,
        plan_cache=plan_cache,
        delta=delta,
        fused=fused,
        quantized=quantized,
        fused_variant=fused_variant,
        compact=compact,
    )
