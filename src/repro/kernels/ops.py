"""Jitted public wrappers around the Pallas kernels.

The wrappers pad inputs to tile multiples, run each kernel, and slice
outputs back. They are the only entry points the rest of the framework
uses. Where a kernel runs is decided here and nowhere else, by the JAX
backend alone (``_interpret``):

* TPU -- every kernel compiles to Mosaic. The serving main path (frontier
  and kNN filters, narrow and full width; the fused verify kernels, VMEM
  and prefetch, full width and compact; the candidate verify kernels; the
  subscription matcher) compiles for v5e at serving widths
  (tests/test_tpu_compile.py) and runs there (``chip_smoke.py``). No
  wrapper falls back to interpret mode or to its ``ref.py`` twin on a TPU.
* CPU -- every kernel runs in Pallas interpret mode (its Python body under
  XLA:CPU), which is how the test suite checks it against ``ref.py``.

``skr_filter`` (reached only by the A/B ``mode="dense"`` descent) and
``cdf_mlp_bank`` (no serving caller) are not compiled for TPU.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .cdf_mlp import cdf_mlp_bank
from .frontier import frontier_filter, frontier_filter_narrow
from .fused_verify import (
    fused_verify,
    fused_verify_compact,
    fused_verify_prefetch,
    fused_verify_prefetch_compact,
    resident_bank_vmem_bytes,
)
from .knn_filter import knn_filter, knn_filter_narrow
from .skr_filter import skr_filter
from .skr_verify import skr_verify, skr_verify_compact
from .sub_match import sub_match
from . import ref


def _interpret() -> bool:
    """Interpret mode on the CPU backend, compiled Mosaic everywhere else."""
    return jax.default_backend() == "cpu"


# sentinel rectangle that intersects nothing under the closed-rect predicate
# (xlo > xhi): used for node/query padding here and in serve.plan
NEVER_RECT = (2.0, 2.0, -2.0, -2.0)

# VMEM budget for the resident (VMEM-variant) fused-verify leaf bank, in
# padded VMEM bytes (``resident_bank_vmem_bytes``): above it the engine
# routes fused verification to the scalar-prefetched kernel. A v5e core has
# 128 MiB of VMEM and a 16 MiB default scoped limit; the VMEM kernel sets
# its own limit to its bank footprint plus 16 MiB of headroom, and at a
# bank just under this cutoff it compiles for v5e at W = 256 and at the
# compact Wl (tests/test_tpu_compile.py). serve.engine._verify_leaves
# applies the rule; fused_gather_verify(variant=...) overrides it.
FUSED_VMEM_BANK_BYTES = 8 * 1024 * 1024


def leaf_bank_bytes(n_leaves: int, obj_per_leaf: int, n_words: int) -> int:
    """Bytes of the fused-verify leaf bank (obj_x/y/id f32+i32 rows plus the
    (K, OBJ, W) u32 bitmap slab) -- the quantity the engine compares against
    ``FUSED_VMEM_BANK_BYTES`` to pick the fused variant."""
    return int(n_leaves) * int(obj_per_leaf) * (3 * 4 + int(n_words) * 4)


def compact_leaf_bank_bytes(
    n_leaves: int, obj_per_leaf: int, n_compact_words: int
) -> int:
    """Bytes of the COMPACT fused-verify leaf bank (DESIGN.md §3.5): the
    obj_x/y/id rows, the one-word u32 signature plane, and the (K, OBJ, Wl)
    leaf-local bitmap slab. This -- not ``leaf_bank_bytes`` -- is what the
    engine prices against ``FUSED_VMEM_BANK_BYTES`` when the snapshot
    carries a compact bank, so far larger indexes stay on the VMEM
    variant."""
    return int(n_leaves) * int(obj_per_leaf) * (
        3 * 4 + 4 + int(n_compact_words) * 4
    )


def remap_query_words(q_bm, leaf_terms, leaves):
    """Remap query bitmaps into the selected leaves' local vocabularies.

    For each (query, slot) pair, gathers the slot's leaf dictionary
    (``leaf_terms[leaf]``: global term id per leaf-local bit, ``-1`` pad;
    serve/snapshot.py:``encode_leaf_vocab``), pulls each dictionary entry's
    bit out of the query's global bitmap, and re-packs them into ``Wl`` u32
    words over leaf-local bit positions. A query term absent from the
    leaf's dictionary contributes no local bit -- exactly the ISSUE's kill
    semantics: no object in that leaf carries the term, so dropping it
    cannot change any match (objects' term sets are subsets of the leaf
    dictionary). Dirty/negative leaf ids are clamp-gathered like the fused
    kernels; their slots are masked downstream by ``leaf_ok``.

    Returns ``(q_cbm (M, T, Wl) u32, q_sig (M, T) u32)`` with ``q_sig`` the
    OR-fold of the remapped words -- a per-(query, slot) kill flag
    (``q_sig == 0`` means nothing in the leaf can match) and the query half
    of the kernels' one-word signature prefilter. Traced: every caller runs
    it inside a compiled program -- the engine's verify stage
    (serve/engine.py:``_verify_stage``, after leaf selection), the kNN leaf
    steps and the sharded serving bodies.
    """
    q = jnp.asarray(q_bm, jnp.uint32)
    M, W = q.shape
    K, L = leaf_terms.shape
    Wl = L // 32
    safe = jnp.clip(jnp.asarray(leaves, jnp.int32), 0, K - 1)
    T = safe.shape[1]
    terms = leaf_terms[safe]  # (M, T, L) global term per local bit
    tpos = jnp.clip(terms, 0, 32 * W - 1)
    widx = (tpos >> 5).reshape(M, T * L)
    qw = jnp.take_along_axis(q, widx, axis=1).reshape(M, T, L)
    bits = (qw >> (tpos & 31).astype(jnp.uint32)) & jnp.uint32(1)
    bits = jnp.where(terms >= 0, bits, jnp.uint32(0))  # pad bits are inert
    shifts = jnp.arange(32, dtype=jnp.uint32)
    # distinct powers of two per lane: the sum IS the bitwise OR (exact)
    q_cbm = jnp.sum(
        bits.reshape(M, T, Wl, 32) << shifts, axis=-1, dtype=jnp.uint32
    )
    q_sig = q_cbm[..., 0]
    for w in range(1, Wl):  # static fold; Wl is a small power of two
        q_sig = q_sig | q_cbm[..., w]
    return q_cbm, q_sig


def pack_query_words(q_bm, min_bucket: int = 4):
    """Pack each query bitmap down to its nonzero words (host-side).

    Returns ``(wids, bits)``: word indices (M, Wp) int32 and the word values
    (M, Wp) uint32, with Wp the power-of-two bucket of the batch's max
    nonzero-word count (capped at W). Slots past a query's own count index
    one of its zero words, so their value is 0 and they can never
    contribute a bit -- packing is exact: ``OR_w (bm & q) == OR_p (bits &
    gathered)``. The engine gathers only the ``wids`` word planes per
    frontier slot, shrinking the descent's biggest operand from (M, F, W)
    to (M, F, Wp).

    Host-side on purpose: Wp must be a *static* shape, and the batch's
    bitmaps are concrete before any jitted descent step runs (the sharded
    path packs before ``shard_map`` so every shard agrees on Wp).
    """
    q = np.asarray(q_bm, dtype=np.uint32)
    M, W = q.shape
    nnz = int((q != 0).sum(axis=1).max()) if M else 0
    wp = max(int(nnz), 1)
    # power-of-two bucket (>= min_bucket) to bound distinct jit shapes, as
    # everywhere else in the width discipline; never wider than W itself
    b = max(min_bucket, 1)
    while b < wp:
        b *= 2
    wp = min(b, W)
    # stable argsort of the "is zero" flag keeps nonzero words first, in
    # original word order; zero-word slots carry value 0 and are inert
    order = np.argsort(q == 0, axis=1, kind="stable")
    wids = order[:, :wp].astype(np.int32)
    bits = np.take_along_axis(q, wids, axis=1).astype(np.uint32)
    return jnp.asarray(wids), jnp.asarray(bits)


def padded_tile_len(n: int, tile: int = 128) -> int:
    """Slots a kernel actually touches for a length-``n`` operand dimension:
    the wrappers below block by ``min(tile, n)`` and pad up to a multiple of
    it. Exposed so cost counters can report padded (true) device work."""
    t = min(tile, max(int(n), 1))
    return -(-int(n) // t) * t


def _pad_dim(a: jax.Array, axis: int, mult: int, fill=0) -> jax.Array:
    size = a.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(a, pads, constant_values=fill)


def filter_pairs(
    q_rects, q_bm, n_mbrs, n_bm, bm: int = 128, bk: int = 128
) -> jax.Array:
    """(M, K) int8 relevance via the Pallas filter kernel (padded + sliced)."""
    M, K = q_rects.shape[0], n_mbrs.shape[0]
    bm_ = min(bm, max(M, 1))
    bk_ = min(bk, max(K, 1))
    qr = _pad_dim(jnp.asarray(q_rects, jnp.float32), 0, bm_)
    qb = _pad_dim(jnp.asarray(q_bm, jnp.uint32), 0, bm_)
    # pad node MBRs with never-intersecting rects
    nm = jnp.asarray(n_mbrs, jnp.float32)
    pad_k = -(-K // bk_) * bk_ - K
    if pad_k:
        nm = jnp.concatenate([nm, jnp.tile(jnp.array([NEVER_RECT], jnp.float32), (pad_k, 1))], 0)
    nb = _pad_dim(jnp.asarray(n_bm, jnp.uint32), 0, bk_)
    out = skr_filter(qr, qb, nm, nb, bm=bm_, bk=bk_, interpret=_interpret())
    return out[:M, :K]


def match_subscriptions(
    obj_pts, obj_bm, sub_rects, sub_bm, sub_sig=None,
    bn: int = 8, bs: int = 128,
) -> jax.Array:
    """(N, S) int8 continuous-filter match matrix via the Pallas sub_match
    kernel (padded + sliced; DESIGN.md §8).

    ``obj_pts``/``obj_bm`` are the arriving objects (points + full-width
    bitmaps -- packed to their nonzero words here, the same host-side
    ``pack_query_words`` the descent uses); ``sub_rects``/``sub_bm`` are the
    compiled subscription block. ``sub_sig`` is the per-subscription OR-fold
    signature, recomputed when not supplied. Object padding carries a zero
    bitmap and subscription padding a zero bitmap + NEVER_RECT, so padded
    slots can never match.
    """
    obj_pts = np.asarray(obj_pts, np.float32).reshape(-1, 2)
    obj_bm = np.asarray(obj_bm, np.uint32)
    N, S = obj_pts.shape[0], np.asarray(sub_rects).shape[0]
    if N == 0 or S == 0:
        return jnp.zeros((N, S), jnp.int8)
    wids, bits = pack_query_words(obj_bm)
    o_sig = np.bitwise_or.reduce(obj_bm, axis=1).reshape(-1, 1)
    if sub_sig is None:
        sub_sig = np.bitwise_or.reduce(np.asarray(sub_bm, np.uint32), axis=1)
    s_sig = np.asarray(sub_sig, np.uint32).reshape(-1, 1)
    bn_ = min(bn, max(N, 1))
    bs_ = min(bs, max(S, 1))
    op = _pad_dim(jnp.asarray(obj_pts), 0, bn_)
    ow = _pad_dim(wids, 0, bn_)
    ob = _pad_dim(bits, 0, bn_)
    osg = _pad_dim(jnp.asarray(o_sig, jnp.uint32), 0, bn_)
    sr = jnp.asarray(sub_rects, jnp.float32)
    pad_s = -(-S // bs_) * bs_ - S
    if pad_s:
        sr = jnp.concatenate(
            [sr, jnp.tile(jnp.array([NEVER_RECT], jnp.float32), (pad_s, 1))], 0
        )
    sb = _pad_dim(jnp.asarray(sub_bm, jnp.uint32), 0, bs_)
    ssg = _pad_dim(jnp.asarray(s_sig, jnp.uint32), 0, bs_)
    out = sub_match(op, ow, ob, osg, sr, sb, ssg, bn=bn_, bs=bs_, interpret=_interpret())
    return out[:N, :S]


def filter_frontier(
    q_rects, q_bm, f_mbrs, f_bm, f_valid, bm: int = 8, bf: int = 128,
) -> jax.Array:
    """(M, F) int8 frontier-survivor matrix via the Pallas frontier kernel."""
    M, F = f_valid.shape
    bm_ = min(bm, max(M, 1))
    bf_ = min(bf, max(F, 1))
    qr = _pad_dim(jnp.asarray(q_rects, jnp.float32), 0, bm_)
    qb = _pad_dim(jnp.asarray(q_bm, jnp.uint32), 0, bm_)
    fm = _pad_dim(_pad_dim(jnp.asarray(f_mbrs, jnp.float32), 0, bm_), 1, bf_)
    fb = _pad_dim(_pad_dim(jnp.asarray(f_bm, jnp.uint32), 0, bm_), 1, bf_)
    fv = _pad_dim(_pad_dim(jnp.asarray(f_valid, jnp.int8), 0, bm_), 1, bf_)
    out = frontier_filter(qr, qb, fm, fb, fv, bm=bm_, bf=bf_, interpret=_interpret())
    return out[:M, :F]


def filter_frontier_narrow(
    q_rects, q_bits, f_codes, f_bm, f_valid, dict_x, dict_y,
    bm: int = 8, bf: int = 128,
) -> jax.Array:
    """(M, F) int8 frontier-survivor matrix on the bandwidth-lean planes:
    int16 MBR rank codes (dequantized through the per-level coordinate
    dictionaries by the gather feeding the kernel -- exact) and packed
    nonzero word planes from
    ``pack_query_words``. Bit-identical survivors to ``filter_frontier`` on
    the corresponding f32/full-width operands."""
    M, F = f_valid.shape
    bm_ = min(bm, max(M, 1))
    bf_ = min(bf, max(F, 1))
    qr = _pad_dim(jnp.asarray(q_rects, jnp.float32), 0, bm_)
    qb = _pad_dim(jnp.asarray(q_bits, jnp.uint32), 0, bm_)
    fc = _pad_dim(_pad_dim(jnp.asarray(f_codes, jnp.int16), 0, bm_), 1, bf_)
    fb = _pad_dim(_pad_dim(jnp.asarray(f_bm, jnp.uint32), 0, bm_), 1, bf_)
    fv = _pad_dim(_pad_dim(jnp.asarray(f_valid, jnp.int8), 0, bm_), 1, bf_)
    out = frontier_filter_narrow(
        qr, qb, fc, fb, fv,
        jnp.asarray(dict_x, jnp.float32), jnp.asarray(dict_y, jnp.float32),
        bm=bm_, bf=bf_, interpret=_interpret(),
    )
    return out[:M, :F]


def knn_frontier_dist(
    q_pts, q_bm, f_mbrs, f_bm, f_valid, bm: int = 8, bf: int = 128,
) -> jax.Array:
    """(M, F) f32 squared frontier MBR min-distances via the Pallas kNN kernel
    (+inf at invalid / keyword-miss slots, including the padding added here)."""
    M, F = f_valid.shape
    bm_ = min(bm, max(M, 1))
    bf_ = min(bf, max(F, 1))
    qp = _pad_dim(jnp.asarray(q_pts, jnp.float32), 0, bm_)
    qb = _pad_dim(jnp.asarray(q_bm, jnp.uint32), 0, bm_)
    fm = _pad_dim(_pad_dim(jnp.asarray(f_mbrs, jnp.float32), 0, bm_), 1, bf_)
    fb = _pad_dim(_pad_dim(jnp.asarray(f_bm, jnp.uint32), 0, bm_), 1, bf_)
    fv = _pad_dim(_pad_dim(jnp.asarray(f_valid, jnp.int8), 0, bm_), 1, bf_)
    out = knn_filter(qp, qb, fm, fb, fv, bm=bm_, bf=bf_, interpret=_interpret())
    return out[:M, :F]


def knn_frontier_dist_narrow(
    q_pts, q_bits, f_codes, f_bm, f_valid, dict_x, dict_y,
    bm: int = 8, bf: int = 128,
) -> jax.Array:
    """(M, F) f32 squared frontier MBR min-distances on the bandwidth-lean
    planes (int16 rank codes + packed word planes); bit-identical distances
    to ``knn_frontier_dist`` on the corresponding f32/full-width operands."""
    M, F = f_valid.shape
    bm_ = min(bm, max(M, 1))
    bf_ = min(bf, max(F, 1))
    qp = _pad_dim(jnp.asarray(q_pts, jnp.float32), 0, bm_)
    qb = _pad_dim(jnp.asarray(q_bits, jnp.uint32), 0, bm_)
    fc = _pad_dim(_pad_dim(jnp.asarray(f_codes, jnp.int16), 0, bm_), 1, bf_)
    fb = _pad_dim(_pad_dim(jnp.asarray(f_bm, jnp.uint32), 0, bm_), 1, bf_)
    fv = _pad_dim(_pad_dim(jnp.asarray(f_valid, jnp.int8), 0, bm_), 1, bf_)
    out = knn_filter_narrow(
        qp, qb, fc, fb, fv,
        jnp.asarray(dict_x, jnp.float32), jnp.asarray(dict_y, jnp.float32),
        bm=bm_, bf=bf_, interpret=_interpret(),
    )
    return out[:M, :F]


def verify_candidates(
    q_rects, q_bm, cand_x, cand_y, cand_bm, cand_valid, bm: int = 8, bc: int = 512,
) -> jax.Array:
    """(M, C) int8 verified-candidate matrix via the Pallas verify kernel."""
    M, C = cand_x.shape
    bm_ = min(bm, max(M, 1))
    bc_ = min(bc, max(C, 1))
    qr = _pad_dim(jnp.asarray(q_rects, jnp.float32), 0, bm_)
    qb = _pad_dim(jnp.asarray(q_bm, jnp.uint32), 0, bm_)
    cx = _pad_dim(_pad_dim(jnp.asarray(cand_x, jnp.float32), 0, bm_), 1, bc_)
    cy = _pad_dim(_pad_dim(jnp.asarray(cand_y, jnp.float32), 0, bm_), 1, bc_)
    cb = _pad_dim(_pad_dim(jnp.asarray(cand_bm, jnp.uint32), 0, bm_), 1, bc_)
    cv = _pad_dim(_pad_dim(jnp.asarray(cand_valid, jnp.int8), 0, bm_), 1, bc_)
    out = skr_verify(qr, qb, cx, cy, cb, cv, bm=bm_, bc=bc_, interpret=_interpret())
    return out[:M, :C]


def verify_candidates_compact(
    q_rects, q_cbm, q_sig, cand_x, cand_y, cand_cbm, cand_sig, cand_valid,
    bm: int = 8,
) -> jax.Array:
    """(M, T*OBJ) int8 verified-candidate matrix on the compact leaf
    vocabulary (DESIGN.md §3.5). Candidates must be leaf-slot-major (T
    slots of OBJ objects) because the remapped query words differ per slot;
    the kernel runs slot-major and tiles each slot's objects itself, so
    only the query rows are padded here."""
    M = cand_x.shape[0]
    bm_ = min(bm, max(M, 1))
    qr = _pad_dim(jnp.asarray(q_rects, jnp.float32), 0, bm_)
    qc = _pad_dim(jnp.asarray(q_cbm, jnp.uint32), 0, bm_)
    qs = _pad_dim(jnp.asarray(q_sig, jnp.uint32), 0, bm_)
    cx = _pad_dim(jnp.asarray(cand_x, jnp.float32), 0, bm_)
    cy = _pad_dim(jnp.asarray(cand_y, jnp.float32), 0, bm_)
    cb = _pad_dim(jnp.asarray(cand_cbm, jnp.uint32), 0, bm_)
    cs = _pad_dim(jnp.asarray(cand_sig, jnp.uint32), 0, bm_)
    cv = _pad_dim(jnp.asarray(cand_valid, jnp.int8), 0, bm_)
    out = skr_verify_compact(
        qr, qc, qs, cx, cy, cb, cs, cv, bm=bm_, interpret=_interpret()
    )
    return out[:M]


def pick_fused_variant(
    K: int, OBJ: int, n_words: int, compact: bool, variant: str = "auto"
) -> str:
    """The fused-verify kernel ``variant`` resolves to for a (K, OBJ) leaf
    bank of ``n_words`` bitmap words (``compact``: the leaf-local bank,
    which adds a signature plane): ``"auto"`` is ``"vmem"`` while the
    bank's padded VMEM footprint stays within ``FUSED_VMEM_BANK_BYTES``."""
    if variant not in ("auto", "vmem", "prefetch"):
        raise ValueError(f"unknown fused-verify variant: {variant!r}")
    if variant != "auto":
        return variant
    rows = 4 if compact else 3
    big = resident_bank_vmem_bytes(K, OBJ, n_words, rows) > FUSED_VMEM_BANK_BYTES
    return "prefetch" if big else "vmem"


def fused_gather_verify(
    q_rects, q_bm, top_leaf, leaf_ok, obj_x, obj_y, obj_bm, obj_id,
    bo: int = 1024, variant: str = "auto",
):
    """Fused leaf gather + verify via the Pallas fused kernels (DESIGN.md §3.5).

    Consumes the frontier descent's selected leaves (``top_leaf``/``leaf_ok``)
    and the snapshot's leaf object bank; the per-query candidate gather
    happens inside the kernel, so the ``(M, T*OBJ, W)`` gathered bitmap
    plane never materializes in HBM. Returns ``(ids, kwv)``:
    ids (M, T*OBJ) i32 matching object ids (``-1`` fill, leaf-slot-major --
    bit-identical to the unfused gather -> ``verify_candidates`` ordering)
    and kwv (M, T) i32 per-slot Eq.1 ``verified`` partial counts.

    ``variant`` picks the kernel: ``"vmem"`` maps the bank whole into VMEM,
    ``"prefetch"`` uses the scalar-prefetched leaf ids to DMA only the
    selected leaf tiles and keeps fusion for banks beyond VMEM, ``"auto"``
    compares the bank's padded VMEM footprint
    (``resident_bank_vmem_bytes``) against ``FUSED_VMEM_BANK_BYTES``. Both
    variants are elementwise identical (tests/test_kernels.py). ``bo`` is
    the object tile per grid step.
    """
    K, OBJ = obj_x.shape
    kernel = (
        fused_verify_prefetch
        if pick_fused_variant(K, OBJ, obj_bm.shape[2], False, variant) == "prefetch"
        else fused_verify
    )
    return kernel(
        jnp.asarray(q_rects, jnp.float32), jnp.asarray(q_bm, jnp.uint32),
        jnp.asarray(top_leaf, jnp.int32), jnp.asarray(leaf_ok, jnp.int8),
        jnp.asarray(obj_x, jnp.float32), jnp.asarray(obj_y, jnp.float32),
        jnp.asarray(obj_bm, jnp.uint32), jnp.asarray(obj_id, jnp.int32),
        bo=bo, interpret=_interpret(),
    )


def fused_gather_verify_compact(
    q_rects, q_cbm, q_sig, top_leaf, leaf_ok,
    obj_x, obj_y, obj_cbm, obj_sig, obj_id,
    bo: int = 1024, variant: str = "auto",
):
    """Compact-bank sibling of ``fused_gather_verify`` (DESIGN.md §3.5).

    Takes the per-slot remapped query words from ``remap_query_words``
    instead of the global bitmap, and the snapshot's compact leaf bank
    (``leaf_obj_cbm``/``leaf_obj_sig``). ``variant="auto"`` prices the
    COMPACT bank's VMEM footprint against ``FUSED_VMEM_BANK_BYTES`` -- the
    point of the compression is that the VMEM variant survives to larger
    indexes. Returns the same ``(ids, kwv)`` contract, bit-identical to the
    full-width kernels.
    """
    K, OBJ = obj_x.shape
    kernel = (
        fused_verify_prefetch_compact
        if pick_fused_variant(K, OBJ, obj_cbm.shape[2], True, variant) == "prefetch"
        else fused_verify_compact
    )
    return kernel(
        jnp.asarray(q_rects, jnp.float32), jnp.asarray(q_cbm, jnp.uint32),
        jnp.asarray(q_sig, jnp.uint32), jnp.asarray(top_leaf, jnp.int32),
        jnp.asarray(leaf_ok, jnp.int8),
        jnp.asarray(obj_x, jnp.float32), jnp.asarray(obj_y, jnp.float32),
        jnp.asarray(obj_cbm, jnp.uint32), jnp.asarray(obj_sig, jnp.uint32),
        jnp.asarray(obj_id, jnp.int32),
        bo=bo, interpret=_interpret(),
    )


def cdf_bank_forward(
    params: Dict[str, jax.Array], x: jax.Array, bn: int = 256, bb: int = 64,
) -> jax.Array:
    """(N, B) CDF values for the whole MLP bank at points x."""
    N = x.shape[0]
    B = params["w0"].shape[0]
    bn_ = min(bn, max(N, 1))
    bb_ = min(bb, max(B, 1))
    xp = _pad_dim(jnp.asarray(x, jnp.float32), 0, bn_)
    pp = {k: _pad_dim(v, 0, bb_) for k, v in params.items()}
    out = cdf_mlp_bank(pp, xp, bn=bn_, bb=bb_, interpret=_interpret())
    return out[:N, :B]


__all__ = [
    "FUSED_VMEM_BANK_BYTES",
    "compact_leaf_bank_bytes",
    "filter_pairs",
    "filter_frontier",
    "filter_frontier_narrow",
    "fused_gather_verify",
    "fused_gather_verify_compact",
    "knn_frontier_dist",
    "knn_frontier_dist_narrow",
    "leaf_bank_bytes",
    "match_subscriptions",
    "pack_query_words",
    "pick_fused_variant",
    "remap_query_words",
    "resident_bank_vmem_bytes",
    "verify_candidates",
    "verify_candidates_compact",
    "cdf_bank_forward",
    "ref",
]
