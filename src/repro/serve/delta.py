"""Delta layer: incremental object updates merged into serving on the fly.

The snapshot layer (serve/snapshot.py) is frozen by design -- every object
insert or delete would otherwise force a full ``IndexSnapshot.build``. This
module makes the serving stack *incremental* (DESIGN.md §7):

* ``DeltaBuffer`` -- the device-resident, pytree-registered delta state the
  jitted executors (serve/engine.py) merge into every descent:

  - per-leaf **insert buffers** ``ins_x/ins_y/ins_bm/ins_id`` shaped
    ``(K, B)`` (B = ``slots_per_leaf``, a power-of-two bucket): buffered
    objects are verified alongside the snapshot's leaf object blocks in the
    SKR verify stage and the kNN probe/leaf-chunk stages;
  - a **delete mask** ``base_alive`` shaped ``(K, OBJ)``: deleted snapshot
    objects are masked out of verification and the kNN top-k merge (their
    slots can never match); deleted *buffered* objects simply clear their
    ``ins_id`` slot to ``-1``;
  - per-level **augmented filter arrays** ``aug_mbrs``/``aug_bms``: copies
    of the snapshot's level MBRs/bitmaps widened along the ancestor path of
    every buffered insert, so the frontier/kNN descents cannot prune a node
    whose subtree holds a buffered match. Deletes never *shrink* them
    (conservative and therefore still exact -- filtering only prunes).

  Like the snapshot, a ``DeltaBuffer`` is immutable: updates produce a new
  buffer via functional ``.at[]`` scatters, and the whole buffer rides
  through ``jit``/``shard_map`` as one pytree argument (``None`` means "no
  deltas" and is itself a valid empty pytree).

* ``DeltaLog`` -- the host-side manager that owns the current buffer plus
  the host mirrors a rebuild needs: it routes each insert to its nearest
  leaf, widens the augmented arrays up the parent chain, tracks deleted
  ids, grows full leaf buffers by power-of-two doubling, and materializes
  ``merged_dataset()`` (base + inserts, deletes tombstoned) for the
  warm-start rebuild path (core/build.py:warm_start_rebuild).

Host-only vs traced: every ``DeltaLog`` method runs on host (updates are
serving control plane); the ``DeltaBuffer`` arrays are consumed inside
jitted descents. Id convention: buffered inserts get fresh global ids
``base_n, base_n+1, ...`` in arrival order, so a cold rebuild over
``merged_dataset()`` returns bit-identical result ids
(tests/test_delta_maintenance.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..core.query import _mbr_dist2_f32
from ..core.types import GeoTextDataset, WiskIndex, ids_to_bitmap
from .snapshot import IndexSnapshot

MIN_SLOTS_PER_LEAF = 8


@dataclasses.dataclass(frozen=True, eq=False)
class DeltaBuffer:
    """Immutable device-resident delta state merged by the executors.

    Shapes (K = leaves, B = ``slots_per_leaf``, OBJ = snapshot
    ``obj_per_leaf``, W = bitmap words):

    * ``aug_mbrs``/``aug_bms`` -- per level ``(n, 4)`` f32 / ``(n, W)`` u32,
      the snapshot level arrays widened by buffered inserts;
    * ``ins_x``/``ins_y`` -- ``(K, B)`` f32 buffered insert coordinates;
    * ``ins_bm`` -- ``(K, B, W)`` u32 buffered insert keyword bitmaps;
    * ``ins_id`` -- ``(K, B)`` i32 buffered insert object ids, ``-1`` =
      empty slot (also how a buffered object is deleted);
    * ``base_alive`` -- ``(K, OBJ)`` i8, ``0`` = snapshot object deleted;
    * ``ins_cbm``/``ins_sig`` -- optional ``(K, B, Wl)`` / ``(K, B)`` u32,
      each buffered insert's bitmap remapped into its leaf's compact
      vocabulary plus the OR-fold signature (DESIGN.md §3.5). Present only
      while every buffered term stayed inside its leaf's dictionary
      (``DeltaLog`` drops them -- one retrace -- the moment one does not;
      the executors then verify delta slots on the full-width ``ins_bm``).

    All array fields are pytree leaves; ``slots_per_leaf`` is static aux
    (a compiled-shape parameter). Registered as a pytree so a buffer is ONE
    argument through ``jit``/``shard_map`` and replicates over a mesh with a
    single ``P()`` prefix spec, exactly like the snapshot.
    """

    aug_mbrs: List[jnp.ndarray]
    aug_bms: List[jnp.ndarray]
    ins_x: jnp.ndarray
    ins_y: jnp.ndarray
    ins_bm: jnp.ndarray
    ins_id: jnp.ndarray
    base_alive: jnp.ndarray
    slots_per_leaf: int
    ins_cbm: jnp.ndarray = None  # (K, B, Wl) u32 leaf-local remapped bitmaps
    ins_sig: jnp.ndarray = None  # (K, B) u32 OR-fold signatures

    @property
    def n_levels(self) -> int:
        return len(self.aug_mbrs)

    def n_buffered(self) -> int:
        """Live buffered inserts (host sync; monitoring only)."""
        return int(jnp.sum(self.ins_id >= 0))

    def n_deleted(self) -> int:
        """Deleted snapshot objects (host sync; monitoring only)."""
        masked = jnp.sum(self.base_alive == 0)
        return int(masked)

    def replicate(self, mesh) -> "DeltaBuffer":
        """The buffer fully replicated over ``mesh`` (one ``device_put`` of
        the whole pytree with a single ``P()`` NamedSharding) -- the delta
        twin of ``IndexSnapshot.replicate``."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(self, NamedSharding(mesh, P()))

    @staticmethod
    def empty(snap: IndexSnapshot, slots_per_leaf: int = MIN_SLOTS_PER_LEAF) -> "DeltaBuffer":
        """An all-empty buffer over ``snap``: augmented arrays alias the
        (immutable) snapshot arrays, insert slots are empty, nothing is
        deleted. Serving with an empty buffer returns exactly the plain
        snapshot results."""
        K = snap.n_leaves
        W = snap.n_words
        B = int(slots_per_leaf)
        cbm = sig = None
        if snap.has_compact_bank:
            cbm = jnp.zeros((K, B, snap.n_compact_words), jnp.uint32)
            sig = jnp.zeros((K, B), jnp.uint32)
        return DeltaBuffer(
            aug_mbrs=list(snap.level_mbrs),
            aug_bms=list(snap.level_bms),
            ins_x=jnp.zeros((K, B), jnp.float32),
            ins_y=jnp.zeros((K, B), jnp.float32),
            ins_bm=jnp.zeros((K, B, W), jnp.uint32),
            ins_id=jnp.full((K, B), -1, jnp.int32),
            base_alive=jnp.ones((K, snap.obj_per_leaf), jnp.int8),
            slots_per_leaf=B,
            ins_cbm=cbm,
            ins_sig=sig,
        )

    def grown(self, new_slots: int) -> "DeltaBuffer":
        """The same buffer with the insert capacity padded to ``new_slots``
        (power-of-two growth: compiled shapes stay log-bounded, like every
        other width in the stack)."""
        if new_slots <= self.slots_per_leaf:
            return self
        pad = new_slots - self.slots_per_leaf
        cbm, sig = self.ins_cbm, self.ins_sig
        if cbm is not None:
            cbm = jnp.pad(cbm, ((0, 0), (0, pad), (0, 0)))
            sig = jnp.pad(sig, ((0, 0), (0, pad)))
        return dataclasses.replace(
            self,
            ins_x=jnp.pad(self.ins_x, ((0, 0), (0, pad))),
            ins_y=jnp.pad(self.ins_y, ((0, 0), (0, pad))),
            ins_bm=jnp.pad(self.ins_bm, ((0, 0), (0, pad), (0, 0))),
            ins_id=jnp.pad(self.ins_id, ((0, 0), (0, pad)), constant_values=-1),
            slots_per_leaf=new_slots,
            ins_cbm=cbm,
            ins_sig=sig,
        )


_DELTA_ARRAY_FIELDS = (
    "aug_mbrs",
    "aug_bms",
    "ins_x",
    "ins_y",
    "ins_bm",
    "ins_id",
    "base_alive",
    "ins_cbm",
    "ins_sig",
)


def _delta_flatten(d: DeltaBuffer):
    return tuple(getattr(d, f) for f in _DELTA_ARRAY_FIELDS), (d.slots_per_leaf,)


def _delta_unflatten(aux, children) -> DeltaBuffer:
    kw = dict(zip(_DELTA_ARRAY_FIELDS, children))
    return DeltaBuffer(slots_per_leaf=aux[0], **kw)


jax.tree_util.register_pytree_node(DeltaBuffer, _delta_flatten, _delta_unflatten)


def partition_delta(delta: DeltaBuffer, part) -> DeltaBuffer:
    """Route a replicated ``DeltaBuffer`` to the owning index shards.

    Returns a new ``DeltaBuffer`` whose rows follow the stacked
    ``PartitionedSnapshot`` layout for ``part`` (an ``IndexPartition``):
    level arrays become ``(S*pad_li, ...)`` with each shard's slice holding
    its own nodes' augmented MBRs/bitmaps (pads: never-intersecting rect,
    empty bitmap), insert buffers and the delete mask become ``(S*Kp, ...)``
    with each leaf's buffered inserts and alive mask living only on the
    shard that owns the leaf. Under the shard_map front doors the whole
    buffer shards with the same single ``P("index")`` prefix spec as the
    snapshot, so every shard merges exactly its own deltas (host-only;
    launch/wisk_serve.py memoizes the result per buffer).
    """
    from ..kernels.ops import NEVER_RECT
    from .snapshot import _stack_shard_rows

    L = delta.n_levels
    leaf_ids = part.nodes[L - 1]
    Kp = part.level_pads[L - 1]
    never = np.asarray(NEVER_RECT, np.float32)
    aug_mbrs = []
    aug_bms = []
    for li in range(L):
        mb = np.asarray(delta.aug_mbrs[li])
        bm = np.asarray(delta.aug_bms[li])
        aug_mbrs.append(jnp.asarray(
            _stack_shard_rows(mb, part.nodes[li], part.level_pads[li], never)
        ))
        aug_bms.append(jnp.asarray(
            _stack_shard_rows(bm, part.nodes[li], part.level_pads[li], 0)
        ))
    cbm = sig = None
    if delta.ins_cbm is not None:
        cbm = jnp.asarray(
            _stack_shard_rows(np.asarray(delta.ins_cbm), leaf_ids, Kp, 0)
        )
        sig = jnp.asarray(
            _stack_shard_rows(np.asarray(delta.ins_sig), leaf_ids, Kp, 0)
        )
    return DeltaBuffer(
        aug_mbrs=aug_mbrs,
        aug_bms=aug_bms,
        ins_x=jnp.asarray(_stack_shard_rows(np.asarray(delta.ins_x), leaf_ids, Kp, 0)),
        ins_y=jnp.asarray(_stack_shard_rows(np.asarray(delta.ins_y), leaf_ids, Kp, 0)),
        ins_bm=jnp.asarray(_stack_shard_rows(np.asarray(delta.ins_bm), leaf_ids, Kp, 0)),
        ins_id=jnp.asarray(_stack_shard_rows(np.asarray(delta.ins_id), leaf_ids, Kp, -1)),
        base_alive=jnp.asarray(
            _stack_shard_rows(np.asarray(delta.base_alive), leaf_ids, Kp, 1)
        ),
        slots_per_leaf=delta.slots_per_leaf,
        ins_cbm=cbm,
        ins_sig=sig,
    )


def _remap_insert_bitmap(bm: np.ndarray, terms: np.ndarray):
    """Remap one full-width insert bitmap into a leaf's compact vocabulary.

    ``bm``: (W,) u32; ``terms``: (32*Wl,) i32 sorted leaf dictionary,
    ``-1``-padded. Returns ``(cbm (Wl,), sig, exact)`` where ``exact`` is
    False when the object carries a term missing from the dictionary -- the
    remap would silently drop it, so the caller must fall back to the
    full-width path.
    """
    shifts = np.arange(32, dtype=np.uint32)
    Wl = terms.size // 32
    tpos = np.clip(terms, 0, bm.size * 32 - 1)
    bits = (bm[tpos >> 5] >> (tpos & 31).astype(np.uint32)) & np.uint32(1)
    bits = np.where(terms >= 0, bits, np.uint32(0))
    cbm = np.bitwise_or.reduce(bits.reshape(Wl, 32) << shifts, axis=-1)
    sig = np.bitwise_or.reduce(cbm)
    n_terms = int(np.sum(((bm[:, None] >> shifts) & 1)))
    return cbm, sig, int(bits.sum()) == n_terms


def parent_chains(index: WiskIndex) -> List[np.ndarray]:
    """Per non-root level: ``parents[li][node] = parent id at level li-1``.

    ``parents[0]`` is a placeholder (root nodes have no parent). Host-only;
    computed once per index from the level CSRs and used by ``DeltaLog`` to
    widen the augmented filter arrays along each insert's ancestor path.
    """
    out: List[np.ndarray] = [np.zeros(index.levels[0].n, np.int32)]
    for li in range(len(index.levels) - 1):
        lvl = index.levels[li]
        par = np.zeros(index.levels[li + 1].n, np.int32)
        for u in range(lvl.n):
            par[lvl.child[lvl.child_ptr[u] : lvl.child_ptr[u + 1]]] = u
        out.append(par)
    return out


class DeltaLog:
    """Host-side manager of the incremental update stream over one snapshot.

    Owns the current ``DeltaBuffer`` (``.buffer``), the routing metadata
    (leaf MBRs + parent chains), and the host mirrors (``ins_locs``,
    ``ins_kw_ids``, ``deleted``) that ``merged_dataset()`` feeds to the
    warm-start rebuild. All methods are host-only; every update replaces
    ``.buffer`` with a new immutable pytree (readers holding the old buffer
    keep a consistent view -- the same discipline as the snapshot swap).
    """

    def __init__(
        self,
        index: WiskIndex,
        dataset: GeoTextDataset,
        snapshot: IndexSnapshot,
        slots_per_leaf: int = MIN_SLOTS_PER_LEAF,
    ) -> None:
        self.index = index
        self.dataset = dataset
        self.snapshot = snapshot
        self.buffer: DeltaBuffer = DeltaBuffer.empty(snapshot, slots_per_leaf)
        self._parents = parent_chains(index)
        self._leaf_mbrs = np.asarray(index.levels[-1].mbrs, np.float32)
        # sticky compact-remap flag: flips False (once; one retrace) when a
        # buffered insert carries a term outside its leaf's dictionary
        self.compact_ok = snapshot.has_compact_bank
        self._leaf_terms = (
            np.asarray(snapshot.leaf_terms) if self.compact_ok else None
        )
        # host mirrors of the augmented arrays (updates are host unions; the
        # level arrays are tiny next to the object blocks, so re-uploading a
        # touched level per update batch is cheap and keeps the math simple)
        self._aug_mbrs = [np.asarray(m).copy() for m in snapshot.level_mbrs]
        self._aug_bms = [np.asarray(b).copy() for b in snapshot.level_bms]
        self._fill = np.zeros(snapshot.n_leaves, np.int64)  # high-water slot/leaf
        self._free: Dict[int, List[int]] = {}  # leaf -> reusable (deleted) slots
        # snapshot object id -> (leaf, slot) for delete masking, and the
        # same map for buffered inserts (filled by insert())
        oid = np.asarray(snapshot.leaf_obj_id)
        kk, ss = np.nonzero(oid >= 0)
        self._base_slot: Dict[int, Tuple[int, int]] = {
            int(oid[k, s]): (int(k), int(s)) for k, s in zip(kk, ss)
        }
        self._ins_slot: Dict[int, Tuple[int, int]] = {}
        # host mirrors for merged_dataset / rebuild
        self.ins_locs: List[np.ndarray] = []
        self.ins_kw_ids: List[np.ndarray] = []
        self.ins_leaf: List[int] = []
        self.deleted: set = set()
        self._next_id = dataset.n

    # ------------------------------------------------------------- inserts
    def insert(self, locs: np.ndarray, kw_ids: np.ndarray) -> np.ndarray:
        """Buffer new objects; returns their assigned global ids.

        ``locs``: (n, 2) f32 in the unit square; ``kw_ids``: (n, max_kw)
        i32 padded with ``-1``. Each object is routed to the leaf with the
        smallest point-to-MBR distance (ties: smallest leaf id), its slot is
        scattered into the insert buffers, and the leaf's ancestor chain in
        the augmented MBR/bitmap arrays is widened so every descent can
        reach it. Full leaf buffers grow by doubling (one retrace per
        doubling, bounded like every other width bucket).
        """
        locs = np.asarray(locs, np.float32).reshape(-1, 2)
        kw_ids = np.asarray(kw_ids, np.int32).reshape(locs.shape[0], -1)
        n = locs.shape[0]
        if n == 0:
            return np.zeros(0, np.int64)
        ids = np.arange(self._next_id, self._next_id + n, dtype=np.int64)
        self._next_id += n
        bms = ids_to_bitmap(kw_ids, self.dataset.vocab_size)
        leaf = np.argmin(
            _mbr_dist2_f32(self._leaf_mbrs[None, :, :], locs[:, None, :]), axis=1
        ).astype(np.int64)

        # allocate slots: reuse freed (deleted-buffered) slots first, then
        # extend the high-water mark -- churn does not grow the buffer
        slots = np.zeros(n, np.int64)
        for i, lf in enumerate(leaf):
            free = self._free.get(int(lf))
            if free:
                slots[i] = free.pop()
            else:
                slots[i] = self._fill[lf]
                self._fill[lf] += 1
            self._ins_slot[int(ids[i])] = (int(lf), int(slots[i]))
        max_need = int(self._fill.max()) if self._fill.size else 0
        B = self.buffer.slots_per_leaf
        while B < max_need:
            B *= 2
            obs.count("delta.grows")  # each doubling retraces the descents
        buf = self.buffer.grown(B)
        buf = dataclasses.replace(
            buf,
            ins_x=buf.ins_x.at[(leaf, slots)].set(jnp.asarray(locs[:, 0])),
            ins_y=buf.ins_y.at[(leaf, slots)].set(jnp.asarray(locs[:, 1])),
            ins_bm=buf.ins_bm.at[(leaf, slots)].set(jnp.asarray(bms)),
            ins_id=buf.ins_id.at[(leaf, slots)].set(jnp.asarray(ids, jnp.int32)),
        )
        if self.compact_ok:
            Wl = buf.ins_cbm.shape[2]
            cbms = np.zeros((n, Wl), np.uint32)
            sigs = np.zeros((n,), np.uint32)
            exact = True
            for i in range(n):
                cbms[i], sigs[i], ok = _remap_insert_bitmap(
                    np.asarray(bms[i], np.uint32), self._leaf_terms[int(leaf[i])]
                )
                exact = exact and ok
            if exact:
                buf = dataclasses.replace(
                    buf,
                    ins_cbm=buf.ins_cbm.at[(leaf, slots)].set(jnp.asarray(cbms)),
                    ins_sig=buf.ins_sig.at[(leaf, slots)].set(jnp.asarray(sigs)),
                )
            else:
                # a term this leaf has never seen: compact delta slots would
                # be lossy, so drop them for good (executors fall back to
                # the exact full-width ins_bm path)
                self.compact_ok = False
                obs.count("delta.compact_fallbacks")
                buf = dataclasses.replace(buf, ins_cbm=None, ins_sig=None)

        # widen the ancestor path per touched (level, node)
        touched: Dict[int, set] = {}
        n_levels = len(self._aug_mbrs)
        for i in range(n):
            node = int(leaf[i])
            for li in range(n_levels - 1, -1, -1):
                mb = self._aug_mbrs[li][node]
                x, y = locs[i, 0], locs[i, 1]
                self._aug_mbrs[li][node] = (
                    min(mb[0], x), min(mb[1], y), max(mb[2], x), max(mb[3], y),
                )
                self._aug_bms[li][node] |= bms[i]
                touched.setdefault(li, set()).add(node)
                node = int(self._parents[li][node])
        aug_mbrs = list(buf.aug_mbrs)
        aug_bms = list(buf.aug_bms)
        for li in touched:
            aug_mbrs[li] = jnp.asarray(self._aug_mbrs[li])
            aug_bms[li] = jnp.asarray(self._aug_bms[li])
        self.buffer = dataclasses.replace(buf, aug_mbrs=aug_mbrs, aug_bms=aug_bms)

        self.ins_locs.append(locs)
        self.ins_kw_ids.append(kw_ids)
        self.ins_leaf.extend(int(l) for l in leaf)
        return ids

    # -------------------------------------------------------------- deletes
    def delete(self, ids) -> int:
        """Mark objects deleted; returns how many ids were newly deleted.

        Snapshot objects flip their ``base_alive`` slot to 0; buffered
        objects clear their ``ins_id`` slot to ``-1``. The augmented filter
        arrays are left wide (conservative: filtering only prunes, and the
        verify/top-k stages mask the deleted slots, so results stay exact).
        Unknown ids are ignored.
        """
        ids = [int(i) for i in np.atleast_1d(np.asarray(ids, np.int64))]
        base_kk, base_ss = [], []
        ins_kk, ins_ss = [], []
        n_new = 0
        buf = self.buffer
        for oid in ids:
            if oid in self.deleted:
                continue
            if oid in self._base_slot:
                k, s = self._base_slot[oid]
                base_kk.append(k)
                base_ss.append(s)
                self.deleted.add(oid)
                n_new += 1
            elif oid in self._ins_slot:
                k, s = self._ins_slot.pop(oid)
                ins_kk.append(k)
                ins_ss.append(s)
                self._free.setdefault(k, []).append(s)
                self.deleted.add(oid)
                n_new += 1
        if ins_kk:
            buf = dataclasses.replace(
                buf,
                ins_id=buf.ins_id.at[(np.asarray(ins_kk), np.asarray(ins_ss))].set(-1),
            )
        if base_kk:
            buf = dataclasses.replace(
                buf,
                base_alive=buf.base_alive.at[
                    (np.asarray(base_kk), np.asarray(base_ss))
                ].set(0),
            )
        self.buffer = buf
        return n_new

    # ------------------------------------------------------------- rebuild
    def n_updates(self) -> int:
        return (self._next_id - self.dataset.n) + len(self.deleted)

    def merged_dataset(self) -> GeoTextDataset:
        """Base dataset + buffered inserts, deletes tombstoned.

        Object ids are row indices, so the merge preserves them: base
        objects keep ``0..n-1``, inserts take ``n..`` in arrival order, and
        deleted objects keep their row with an emptied keyword set -- a
        keywordless object can never match an SKR or Boolean-kNN query, so
        tombstones are inert while every live id stays identical to the
        delta-merged serving path (the id-exactness contract of
        tests/test_delta_maintenance.py).
        """
        base = self.dataset
        max_kw = base.kw_ids.shape[1]
        if self.ins_kw_ids:
            max_kw = max(max_kw, max(k.shape[1] for k in self.ins_kw_ids))

        def pad(a: np.ndarray) -> np.ndarray:
            return np.pad(a, ((0, 0), (0, max_kw - a.shape[1])), constant_values=-1)

        locs = np.concatenate([base.locs, *[l for l in self.ins_locs]], 0) if self.ins_locs else base.locs.copy()
        kw = (
            np.concatenate([pad(base.kw_ids), *[pad(k) for k in self.ins_kw_ids]], 0)
            if self.ins_kw_ids
            else base.kw_ids.copy()
        )
        if self.deleted:
            kw[np.fromiter(self.deleted, np.int64)] = -1
        return GeoTextDataset.from_ids(locs, kw, base.vocab_size)

    def merged_assignment(self) -> np.ndarray:
        """(n_merged,) leaf/cluster assignment extending the snapshot's
        clustering with each buffered insert's routed leaf -- the warm-start
        rebuild's starting partition over the merged dataset."""
        extra = np.asarray(self.ins_leaf, np.int32)
        return np.concatenate([self.index.clusters.assign, extra])
