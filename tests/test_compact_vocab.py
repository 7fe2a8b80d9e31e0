"""Leaf-local vocabulary compression coverage (DESIGN.md §3.5).

Four layers of the compact-verify contract:

* **Kernel sweeps.** The compact Pallas kernels (interpret) vs their jnp
  oracles AND the full-width references -- the remap + one-word signature
  prefilter is exact, so ids/counts must be bit-identical to the global-W
  predicate, not merely to the compact oracle.
* **Remap edge cases.** Single-word leaves (Wl == 1), query terms outside
  every leaf dictionary (signature kill), and the ``cap`` overflow path
  returning the disable-all sentinel.
* **Engine parity.** ``compact=None`` vs ``compact=False`` across fused
  variants and kNN -- identical ids and Eq.1 counters; a snapshot without
  a compact bank transparently serves on the full-width slab.
* **Delta compact.** In-dictionary inserts keep ``compact_ok`` and the
  remapped insert slabs; a term new to its leaf flips the sticky fallback
  to full-width insert verification -- with serving parity either way.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.data.synth import make_dataset
from repro.data.workloads import make_workload
from repro.kernels import ops, ref
from repro.launch.wisk_serve import serve_batch, serve_knn_batch
from repro.serve.delta import DeltaLog
from repro.serve.engine import IndexSnapshot
from repro.serve.snapshot import encode_leaf_vocab

from test_query_parity import _build_index


def _rand_rects(rng, n):
    lo = rng.uniform(0, 0.8, (n, 2)).astype(np.float32)
    hi = lo + rng.uniform(0.01, 0.2, (n, 2)).astype(np.float32)
    return np.concatenate([lo, hi], axis=1)


def _clustered_bank(rng, k, obj, w, pool_size=24, max_kw=6):
    """A leaf bank whose objects draw terms from a small per-leaf pool, so
    the leaf dictionaries genuinely compress (Wl well below W)."""
    nbits = 32 * w
    ob = np.zeros((k, obj, w), np.uint32)
    for c in range(k):
        pool = rng.choice(nbits, size=min(pool_size, nbits), replace=False)
        for o in range(obj):
            picks = pool[: rng.integers(0, min(max_kw, pool.size) + 1)]
            np.bitwise_or.at(
                ob[c, o], picks >> 5, np.uint32(1) << (picks & 31).astype(np.uint32)
            )
    return ob


def _compact_case(rng, m, t, k, obj, w, **bank_kw):
    """Full-width fused-verify operands (dirty leaf ids, -1 pads, invalid
    slots) plus their compact encoding and per-slot remapped query words."""
    qr = _rand_rects(rng, m)
    qb = (rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, w), dtype=np.uint32))
    tl = rng.integers(-1, k + 2, (m, t)).astype(np.int32)  # deliberately dirty
    ok = rng.integers(0, 2, (m, t)).astype(np.int8)
    ox = rng.uniform(0, 1, (k, obj)).astype(np.float32)
    oy = rng.uniform(0, 1, (k, obj)).astype(np.float32)
    ob = _clustered_bank(rng, k, obj, w, **bank_kw)
    oid = np.where(rng.integers(0, 4, (k, obj)) > 0,
                   rng.integers(0, 10 * k * obj, (k, obj)), -1).astype(np.int32)
    lt, cbm, sig = encode_leaf_vocab(ob)
    assert lt is not None, "clustered pools must never overflow the cap"
    q_cbm, q_sig = ops.remap_query_words(jnp.asarray(qb), lt, jnp.asarray(tl))
    full = (qr, qb, tl, ok, ox, oy, ob, oid)
    compact = (qr, q_cbm, q_sig, tl, ok, ox, oy, cbm, sig, oid)
    return full, compact


_SWEEP = [
    (1, 1, 1, 1, 1),    # fully degenerate
    (5, 3, 9, 16, 3),   # nothing tile-aligned
    (9, 8, 36, 64, 15), # the fs-profile word width
    (33, 4, 17, 32, 8), # queries past the default bm tile
]


@pytest.mark.parametrize("m,t,k,obj,w", _SWEEP)
def test_skr_verify_compact_sweep(m, t, k, obj, w):
    """Unfused compact verify kernel (interpret) vs its jnp oracle AND the
    full-width verify on the same gathered candidates: bit-identical."""
    rng = np.random.default_rng(m * 7919 + t * 131 + k * 17 + obj + w)
    full, compact = _compact_case(rng, m, t, k, obj, w)
    qr, qb, tl, ok, ox, oy, ob, oid = full
    _, q_cbm, q_sig, _, _, _, _, cbm, sig, _ = compact
    safe = np.clip(tl, 0, k - 1)
    cx = ox[safe].reshape(m, -1)
    cy = oy[safe].reshape(m, -1)
    cid = oid[safe].reshape(m, -1)
    cval = ((cid >= 0) & np.repeat(ok > 0, obj, axis=1)).astype(np.int8)
    ccbm = np.asarray(cbm)[safe].reshape(m, t * obj, -1)
    csig = np.asarray(sig)[safe].reshape(m, -1)
    out = np.asarray(ops.verify_candidates_compact(
        qr, q_cbm, q_sig, cx, cy, ccbm, csig, cval))
    exp = np.asarray(ref.skr_verify_compact_ref(
        *map(jnp.asarray, (qr, q_cbm, q_sig, cx, cy, ccbm, csig, cval))))
    np.testing.assert_array_equal(out, exp)
    wide = np.asarray(ref.skr_verify_ref(*map(jnp.asarray, (
        qr, qb, cx, cy, ob[safe].reshape(m, t * obj, -1), cval))))
    np.testing.assert_array_equal(out, wide)


@pytest.mark.parametrize("variant", ["vmem", "prefetch"])
@pytest.mark.parametrize("m,t,k,obj,w", _SWEEP)
def test_fused_verify_compact_sweep(variant, m, t, k, obj, w):
    """Both fused compact kernels (interpret) vs the compact oracle AND the
    full-width fused reference -- same ids in the same candidate slots,
    same per-slot Eq.1 counts."""
    rng = np.random.default_rng(m * 613 + t * 37 + k * 5 + obj + w)
    full, compact = _compact_case(rng, m, t, k, obj, w)
    ids, kwv = ops.fused_gather_verify_compact(*compact, variant=variant)
    eids, ekwv = ref.fused_verify_compact_ref(*map(jnp.asarray, compact))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(eids))
    np.testing.assert_array_equal(np.asarray(kwv), np.asarray(ekwv))
    wids, wkwv = ref.fused_verify_ref(*map(jnp.asarray, full))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(wids))
    np.testing.assert_array_equal(np.asarray(kwv), np.asarray(wkwv))


def test_fused_verify_compact_variants_equal():
    """VMEM and prefetch compact kernels are elementwise interchangeable --
    the engine's auto-selection can never change results."""
    rng = np.random.default_rng(43)
    _, compact = _compact_case(rng, 13, 5, 11, 16, 6)
    v_ids, v_kwv = ops.fused_gather_verify_compact(*compact, variant="vmem")
    p_ids, p_kwv = ops.fused_gather_verify_compact(*compact, variant="prefetch")
    np.testing.assert_array_equal(np.asarray(v_ids), np.asarray(p_ids))
    np.testing.assert_array_equal(np.asarray(v_kwv), np.asarray(p_kwv))


def test_compact_auto_prices_compact_bank(monkeypatch):
    """variant="auto" prices the COMPACT bank's VMEM footprint, not the
    full-width bank's: with the cutoff between the two, the VMEM compact
    kernel must be selected even though the full-width bank would have
    forced prefetch. (A slab narrower than 128 words still fills 128 lanes
    in VMEM, so the full width here is a lane-dense 256 words.)"""
    rng = np.random.default_rng(47)
    k, obj, w = 16, 16, 256
    _, compact = _compact_case(rng, 6, 3, k, obj, w)
    Wl = int(np.asarray(compact[7]).shape[2])
    small = ops.resident_bank_vmem_bytes(k, obj, Wl, 4)
    wide = ops.resident_bank_vmem_bytes(k, obj, w, 3)
    cut = (small + wide) // 2
    assert small < cut < wide
    monkeypatch.setattr(ops, "FUSED_VMEM_BANK_BYTES", cut)
    calls = []
    real = ops.fused_verify_compact
    monkeypatch.setattr(
        ops, "fused_verify_compact",
        lambda *a, **kw: calls.append("vmem") or real(*a, **kw))
    ids, kwv = ops.fused_gather_verify_compact(*compact, variant="auto")
    assert calls == ["vmem"], "auto priced the full-width bank"
    eids, ekwv = ref.fused_verify_compact_ref(*map(jnp.asarray, compact))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(eids))
    np.testing.assert_array_equal(np.asarray(kwv), np.asarray(ekwv))


def test_single_word_leaf_and_out_of_vocab_query():
    """Wl == 1 leaves (vocab <= 32 terms) verify exactly, and a query whose
    terms all fall outside every leaf dictionary is killed by the remap:
    zero signature, zero matches -- exactly what the full-width predicate
    says (those terms match no object in any leaf)."""
    rng = np.random.default_rng(53)
    m, t, k, obj, w = 7, 3, 6, 8, 4
    # per-leaf pools drawn only from the low 20 bits -> Wl == 1
    nlow = 20
    ob = np.zeros((k, obj, w), np.uint32)
    for c in range(k):
        pool = rng.choice(nlow, size=10, replace=False)
        for o in range(obj):
            picks = pool[: rng.integers(1, 5)]
            np.bitwise_or.at(
                ob[c, o], picks >> 5, np.uint32(1) << (picks & 31).astype(np.uint32))
    lt, cbm, sig = encode_leaf_vocab(ob)
    assert lt.shape[1] == 32, "vocab <= 32 terms must pack into one word"
    qr = np.tile(np.array([[0.0, 0.0, 1.0, 1.0]], np.float32), (m, 1))
    # query terms strictly above every pool: remap must kill them all
    qb = np.zeros((m, w), np.uint32)
    qb[:, w - 1] = rng.integers(1, 2 ** 31, m, dtype=np.uint32)
    tl = rng.integers(0, k, (m, t)).astype(np.int32)
    ok = np.ones((m, t), np.int8)
    q_cbm, q_sig = ops.remap_query_words(jnp.asarray(qb), lt, jnp.asarray(tl))
    assert not np.asarray(q_sig).any(), "out-of-vocab terms must zero the signature"
    ox = rng.uniform(0, 1, (k, obj)).astype(np.float32)
    oy = rng.uniform(0, 1, (k, obj)).astype(np.float32)
    oid = np.arange(k * obj, dtype=np.int32).reshape(k, obj)
    ids, kwv = ops.fused_gather_verify_compact(
        qr, q_cbm, q_sig, tl, ok, ox, oy, cbm, sig, oid)
    assert (np.asarray(ids) == -1).all() and not np.asarray(kwv).any()
    wids, wkwv = ref.fused_verify_ref(*map(jnp.asarray, (
        qr, qb, tl, ok, ox, oy, ob, oid)))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(wids))
    np.testing.assert_array_equal(np.asarray(kwv), np.asarray(wkwv))
    # and in-vocab queries on the same Wl == 1 bank still verify exactly
    qb2 = np.zeros((m, w), np.uint32)
    qb2[:, 0] = rng.integers(1, 1 << nlow, m, dtype=np.uint32)
    q_cbm2, q_sig2 = ops.remap_query_words(jnp.asarray(qb2), lt, jnp.asarray(tl))
    ids2, kwv2 = ops.fused_gather_verify_compact(
        qr, q_cbm2, q_sig2, tl, ok, ox, oy, cbm, sig, oid)
    wids2, wkwv2 = ref.fused_verify_ref(*map(jnp.asarray, (
        qr, qb2, tl, ok, ox, oy, ob, oid)))
    np.testing.assert_array_equal(np.asarray(ids2), np.asarray(wids2))
    np.testing.assert_array_equal(np.asarray(kwv2), np.asarray(wkwv2))


def test_encode_leaf_vocab_overflow_disables_bank():
    """Any single leaf over the cap returns the (None, None, None) sentinel
    -- the disable-all contract (mirrors NARROW_DICT_MAX)."""
    rng = np.random.default_rng(59)
    ob = _clustered_bank(rng, 4, 8, 2, pool_size=12)
    ob[2, 0, :] = 0xFFFFFFFF  # one leaf with 64 terms
    lt, cbm, sig = encode_leaf_vocab(ob, cap=16)
    assert lt is None and cbm is None and sig is None
    lt, cbm, sig = encode_leaf_vocab(ob, cap=64)  # at the cap: still encodes
    assert lt is not None


# ------------------------------------------------------------- engine parity
def _quick_snap():
    ds = make_dataset("fs", n=1000, seed=6)
    index, clusters = _build_index(ds, g=5, levels=2)
    return ds, IndexSnapshot.build(index, ds), clusters.k


def test_engine_compact_parity_skr_and_knn():
    """compact=None (the default, bank present) vs compact=False: identical
    ids and Eq.1 counters across fused variants, and identical kNN
    sequences -- the engine-level exactness gate of the compact bank."""
    ds, snap, max_leaves = _quick_snap()
    assert snap.has_compact_bank
    wl = make_workload(ds, m=16, dist="MIX", seed=31)
    base = serve_batch(snap, wl.rects, wl.kw_bitmap, max_leaves=max_leaves,
                       fused=False, compact=False)
    for fused in (False, True, None):
        out = serve_batch(snap, wl.rects, wl.kw_bitmap, max_leaves=max_leaves,
                          fused=fused, compact=None)
        for key in ("ids", "counts", "verified", "overflow"):
            np.testing.assert_array_equal(
                np.asarray(out[key]), np.asarray(base[key]),
                err_msg=f"{key} (fused={fused})")
    pts = np.stack([(wl.rects[:, 0] + wl.rects[:, 2]) / 2,
                    (wl.rects[:, 1] + wl.rects[:, 3]) / 2], 1).astype(np.float32)
    kb = serve_knn_batch(snap, pts, wl.kw_bitmap, 10, compact=False)
    kc = serve_knn_batch(snap, pts, wl.kw_bitmap, 10, compact=None)
    for key in ("ids", "dist2", "verified", "nodes_checked"):
        np.testing.assert_array_equal(
            np.asarray(kc[key]), np.asarray(kb[key]), err_msg=key)


def test_engine_without_compact_bank_falls_back():
    """A snapshot whose compact bank was disabled (overflow sentinel) serves
    identically on the full-width slab with compact left at the default."""
    ds, snap, max_leaves = _quick_snap()
    stripped = dataclasses.replace(
        snap, leaf_terms=None, leaf_obj_cbm=None, leaf_obj_sig=None)
    assert snap.has_compact_bank and not stripped.has_compact_bank
    wl = make_workload(ds, m=12, dist="MIX", seed=37)
    a = serve_batch(snap, wl.rects, wl.kw_bitmap, max_leaves=max_leaves)
    b = serve_batch(stripped, wl.rects, wl.kw_bitmap, max_leaves=max_leaves)
    for key in ("ids", "counts", "verified", "overflow"):
        np.testing.assert_array_equal(
            np.asarray(a[key]), np.asarray(b[key]), err_msg=key)


# ------------------------------------------------------------- delta compact
def _pinned_workload(ds, loc, kw_bits, m=12, seed=41):
    """A MIX workload with query 0 pinned over ``loc`` carrying ``kw_bits``."""
    wl = make_workload(ds, m=m, dist="MIX", seed=seed)
    R = np.asarray(wl.rects).copy()
    B = np.asarray(wl.kw_bitmap).copy()
    R[0] = (loc[0] - 0.1, loc[1] - 0.1, loc[0] + 0.1, loc[1] + 0.1)
    B[0] = kw_bits
    return dataclasses.replace(wl, rects=R, kw_bitmap=B)


def _delta_parity(ds, snap, max_leaves, log, wl):
    base = serve_batch(snap, wl.rects, wl.kw_bitmap, max_leaves=max_leaves,
                       delta=log.buffer, compact=False)
    for fused in (False, True, None):
        out = serve_batch(snap, wl.rects, wl.kw_bitmap, max_leaves=max_leaves,
                          delta=log.buffer, fused=fused, compact=None)
        for key in ("ids", "counts", "verified", "overflow"):
            np.testing.assert_array_equal(
                np.asarray(out[key]), np.asarray(base[key]),
                err_msg=f"{key} (fused={fused})")
    return base


def _insert_leaf(log, new_id):
    """The (leaf, slot) a buffered insert landed in."""
    where = np.argwhere(np.asarray(log.buffer.ins_id) == int(new_id))
    assert where.shape[0] == 1
    return int(where[0, 0])


def test_delta_insert_in_dict_keeps_compact():
    """Inserts whose terms are already in their leaf's dictionary keep the
    remapped insert slabs live (compact_ok True) and serve bit-identically
    to the full-width delta path."""
    ds, snap, max_leaves = _quick_snap()
    index, _ = _build_index(ds, g=5, levels=2)
    log = DeltaLog(index, ds, snap)
    # a probe insert discovers the routing leaf for this location
    rng = np.random.default_rng(0)
    src = int(rng.integers(ds.n))
    loc = ds.locs[src]
    probe = DeltaLog(index, ds, snap)
    pid = probe.insert(loc[None, :], ds.kw_ids[src][None])
    leaf = _insert_leaf(probe, pid[0])
    terms = np.asarray(snap.leaf_terms)[leaf]
    terms = terms[terms >= 0]
    assert terms.size >= 2, "routing leaf needs a usable dictionary"
    kw = terms[:2].astype(np.int64)
    new = log.insert(loc[None, :], kw[None, :])
    assert log.compact_ok and log.buffer.ins_cbm is not None
    assert _insert_leaf(log, new[0]) == leaf, "probe and real insert diverged"
    bits = np.zeros(snap.n_words, np.uint32)
    np.bitwise_or.at(bits, kw >> 5, np.uint32(1) << (kw & 31).astype(np.uint32))
    wl = _pinned_workload(ds, loc, bits)
    out = _delta_parity(ds, snap, max_leaves, log, wl)
    assert int(new[0]) in set(np.asarray(out["ids"][0]).tolist()), (
        "pinned query must see the compact-verified insert")


def test_delta_insert_out_of_dict_falls_back():
    """A buffered insert carrying a term NEW to its leaf flips the sticky
    compact_ok fallback (insert slabs dropped, delta slots verified on the
    full-width plane) -- and serving stays bit-identical."""
    ds, snap, max_leaves = _quick_snap()
    index, _ = _build_index(ds, g=5, levels=2)
    log = DeltaLog(index, ds, snap)
    rng = np.random.default_rng(1)
    src = int(rng.integers(ds.n))
    loc = ds.locs[src]
    probe = DeltaLog(index, ds, snap)
    pid = probe.insert(loc[None, :], ds.kw_ids[src][None])
    leaf = _insert_leaf(probe, pid[0])
    terms = np.asarray(snap.leaf_terms)[leaf]
    fresh = np.setdiff1d(np.arange(ds.vocab_size), terms[terms >= 0])
    assert fresh.size, "dataset vocab must exceed one leaf's dictionary"
    kw = np.array([int(fresh[0])], np.int64)
    new = log.insert(loc[None, :], kw[None, :])
    assert not log.compact_ok and log.buffer.ins_cbm is None
    bits = np.zeros(snap.n_words, np.uint32)
    np.bitwise_or.at(bits, kw >> 5, np.uint32(1) << (kw & 31).astype(np.uint32))
    wl = _pinned_workload(ds, loc, bits, seed=43)
    out = _delta_parity(ds, snap, max_leaves, log, wl)
    assert int(new[0]) in set(np.asarray(out["ids"][0]).tolist()), (
        "pinned query must see the full-width-verified insert")
