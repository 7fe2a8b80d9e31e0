"""Continuous spatio-textual filter queries: pub-sub over the update stream.

WISK serves request/response SKR traffic; production geo-textual systems
also run the inverse problem (FAST, Mahmood et al.): *standing*
subscriptions -- geofences, alert filters, feed rules -- matched against a
stream of arriving objects. This module is that subsystem (DESIGN.md §8):

* ``SubscriptionBlock`` -- the device-resident compiled subscription index.
  Subscriptions become the indexed set: a padded power-of-two block of
  rects ``(S, 4)``, keyword bitmaps ``(S, W)`` and one-word OR-fold
  signatures ``(S, 1)``, grown by doubling with freed-slot reuse exactly
  like the ``DeltaBuffer`` insert buffers. Empty slots carry NEVER_RECT +
  a zero bitmap and are inert in the match kernel.
* ``SubscriptionIndex`` -- the host-side manager and notification log.
  ``subscribe``/``unsubscribe`` edit host mirrors and recompile the block
  lazily; ``match_arrivals`` matches a batch of arriving objects against
  the block on device (kernels/sub_match.py: packed object word planes +
  signature prefilter, cross-product tiles) and queues
  ``(object_id, subscription_id)`` notifications; ``drain()`` hands them
  out exactly once.

Exactly-once contract (pinned by tests/test_streaming_match.py and the
hypothesis suite): every live object id is matched against the block at
most once, guarded by a high-water mark over the *global object id space*
-- ``DeltaLog`` assigns ids monotonically (``base_n, base_n+1, ...``) and a
rebuild swap continues the same sequence (the merged dataset's row count
IS the old ``_next_id``), so the mark survives buffer growth, freed-slot
reuse (a reused slot holds a fresh, higher id), deletes, and
``LiveIndex.maybe_rebuild`` generation swaps without any per-slot state.
``pump(delta_log)`` -- the full-buffer sweep twin of the incremental
``match_arrivals`` hook -- relies on the same mark, so pumping after
incremental matching emits nothing new and the two paths produce identical
notification streams.

Stream semantics, matching ``core.query.SubscriptionOracle`` verbatim: a
subscription sees exactly the objects that arrive while it is live (no
retroactive delivery); deleting an object never retracts a queued
notification; an empty keyword set matches nothing (the Boolean contract
of an empty SKR query); a zero-area rect matches objects exactly at that
point. Notifications are queued in canonical (object id, subscription id)
order per batch.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..core.types import bitmap_words, ids_to_bitmap
from ..kernels.ops import NEVER_RECT, match_subscriptions

MIN_SUB_SLOTS = 8


@dataclasses.dataclass(frozen=True, eq=False)
class SubscriptionBlock:
    """Immutable device-resident compiled subscription index (§8).

    ``rects`` (S, 4) f32 / ``bm`` (S, W) u32 / ``sig`` (S, 1) u32 with S a
    power-of-two slot bucket; empty slots are NEVER_RECT + zero bitmap
    (signature 0), so the match kernel needs no validity plane. Registered
    as a pytree: the whole block rides through jitted match steps as one
    argument, like the snapshot and the delta buffer.
    """

    rects: jnp.ndarray
    bm: jnp.ndarray
    sig: jnp.ndarray

    @property
    def n_slots(self) -> int:
        return int(self.rects.shape[0])


jax.tree_util.register_pytree_node(
    SubscriptionBlock,
    lambda b: ((b.rects, b.bm, b.sig), None),
    lambda aux, ch: SubscriptionBlock(*ch),
)


class SubscriptionIndex:
    """Host-side manager of the standing-subscription set + notification log.

    Single-writer control plane, like ``DeltaLog``: ``subscribe`` /
    ``unsubscribe`` / ``match_arrivals`` / ``pump`` / ``drain`` are expected
    from one maintenance thread. The device block is compiled lazily and
    cached until the subscription set changes; its slot count only ever
    doubles (power-of-two shape discipline), so jitted match steps see
    O(log S) distinct subscription shapes.
    """

    def __init__(self, vocab_size: int, min_slots: int = MIN_SUB_SLOTS) -> None:
        self.vocab_size = int(vocab_size)
        self.n_words = bitmap_words(self.vocab_size)
        S = int(min_slots)
        self._rects = np.tile(np.asarray(NEVER_RECT, np.float32), (S, 1))
        self._bms = np.zeros((S, self.n_words), np.uint32)
        self._sub_id = np.full(S, -1, np.int32)
        self._slot = {}  # sub_id -> slot
        self._kw = {}  # sub_id -> keyword id array (oracle-comparable mirror)
        self._free: List[int] = []
        self._fill = 0
        self._next_sub = 0
        self._block: Optional[SubscriptionBlock] = None
        # exactly-once high-water mark over the global object id space
        self._seen_max = -1
        self._pending: List[Tuple[int, int]] = []
        self.emitted_total = 0
        self.matched_total = 0

    # ------------------------------------------------------------- editing
    @property
    def n_live(self) -> int:
        return len(self._slot)

    @property
    def n_slots(self) -> int:
        return self._rects.shape[0]

    def subscribe(self, rect, kw_ids) -> int:
        """Register a standing (rect, keyword) filter; returns its id.

        Matches only objects arriving from now on. Slots freed by
        ``unsubscribe`` are reused before the block grows (doubling), the
        same churn discipline as the delta insert buffers.
        """
        rect = np.asarray(rect, np.float32).reshape(4)
        kw = np.asarray(kw_ids, np.int64).reshape(-1)
        bm = ids_to_bitmap(kw.reshape(1, -1).astype(np.int32), self.vocab_size)[0]
        sid = self._next_sub
        self._next_sub += 1
        if self._free:
            slot = self._free.pop()
        else:
            slot = self._fill
            self._fill += 1
            if slot >= self.n_slots:
                obs.count("subscribe.grows")
                grown = self.n_slots * 2
                self._rects = np.concatenate(
                    [self._rects,
                     np.tile(np.asarray(NEVER_RECT, np.float32), (grown - self.n_slots, 1))]
                )
                self._bms = np.concatenate(
                    [self._bms, np.zeros((grown // 2, self.n_words), np.uint32)]
                )
                self._sub_id = np.concatenate(
                    [self._sub_id, np.full(grown // 2, -1, np.int32)]
                )
        self._rects[slot] = rect
        self._bms[slot] = bm
        self._sub_id[slot] = sid
        self._slot[sid] = slot
        self._kw[sid] = kw
        self._block = None
        return sid

    def unsubscribe(self, sub_id: int) -> bool:
        """Retire a subscription; its slot becomes reusable. Notifications
        already queued for it stay queued (they matched while it was live);
        no object arriving after this can match it."""
        slot = self._slot.pop(int(sub_id), None)
        if slot is None:
            return False
        self._kw.pop(int(sub_id), None)
        self._rects[slot] = np.asarray(NEVER_RECT, np.float32)
        self._bms[slot] = 0
        self._sub_id[slot] = -1
        self._free.append(slot)
        self._block = None
        return True

    def block(self) -> SubscriptionBlock:
        """The compiled device block for the current subscription set
        (cached until the set changes)."""
        if self._block is None:
            self._block = SubscriptionBlock(
                rects=jnp.asarray(self._rects),
                bm=jnp.asarray(self._bms),
                sig=jnp.asarray(
                    np.bitwise_or.reduce(self._bms, axis=1).reshape(-1, 1)
                ),
            )
        return self._block

    # ------------------------------------------------------------ matching
    def _match(self, ids: np.ndarray, locs: np.ndarray, bms: np.ndarray) -> int:
        """Device-match pre-filtered arrivals and queue their notifications
        in canonical (object id, subscription id) order; advance the
        exactly-once mark. ``ids`` must all be above the current mark."""
        if ids.size == 0:
            return 0
        self._seen_max = max(self._seen_max, int(ids.max()))
        if not self._slot:
            return 0
        blk = self.block()
        mat = np.asarray(
            match_subscriptions(locs, bms, blk.rects, blk.bm, blk.sig[:, 0])
        )
        oi, sj = np.nonzero(mat)
        if oi.size == 0:
            return 0
        pairs = np.stack([ids[oi], self._sub_id[sj].astype(np.int64)], 1)
        pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
        self._pending.extend((int(o), int(s)) for o, s in pairs)
        self.matched_total += pairs.shape[0]
        return pairs.shape[0]

    def match_arrivals(self, ids, locs, kw_ids=None, bms=None) -> int:
        """Match one batch of arriving objects against the compiled block --
        the per-insert hook ``LiveIndex.insert`` runs in the same step the
        objects enter the ``DeltaLog``. Ids at or below the high-water mark
        were already matched and are skipped (exactly-once); the mark
        advances even when no subscription is live, so a later subscriber
        never retroactively sees these objects. Returns #queued."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        locs = np.asarray(locs, np.float32).reshape(-1, 2)
        if bms is None:
            bms = ids_to_bitmap(
                np.asarray(kw_ids, np.int32).reshape(ids.size, -1), self.vocab_size
            )
        bms = np.asarray(bms, np.uint32).reshape(ids.size, -1)
        keep = ids > self._seen_max
        if not keep.all():
            ids, locs, bms = ids[keep], locs[keep], bms[keep]
        order = np.argsort(ids, kind="stable")
        return self._match(ids[order], locs[order], bms[order])

    def pump(self, delta_log) -> int:
        """Full-buffer sweep: match every live buffered insert that the
        high-water mark has not covered yet. The batch-matching twin of
        ``match_arrivals`` -- after incremental matching it is a no-op, and
        driving a stream exclusively through ``pump`` yields the identical
        notification sequence (the differential harness checks both). Slots
        freed by deletes carry ``ins_id == -1`` and are skipped; buffer
        growth only pads with more ``-1`` slots, so a sweep after growth
        re-emits nothing. Returns #queued."""
        buf = delta_log.buffer
        ids = np.asarray(buf.ins_id, np.int64).reshape(-1)
        live = (ids >= 0) & (ids > self._seen_max)
        if not live.any():
            return 0
        locs = np.stack(
            [np.asarray(buf.ins_x).reshape(-1)[live],
             np.asarray(buf.ins_y).reshape(-1)[live]], 1
        )
        bms = np.asarray(buf.ins_bm).reshape(ids.size, -1)[live]
        ids = ids[live]
        order = np.argsort(ids, kind="stable")
        return self._match(ids[order], locs[order], bms[order])

    # ------------------------------------------------------- notifications
    @property
    def n_pending(self) -> int:
        return len(self._pending)

    def drain(self) -> np.ndarray:
        """All queued (object_id, subscription_id) notifications, exactly
        once: a second drain (with no arrivals in between) returns an empty
        (0, 2) array."""
        out = np.asarray(self._pending, np.int64).reshape(-1, 2)
        self._pending = []
        self.emitted_total += out.shape[0]
        return out
