"""Pallas TPU kernels: fused leaf gather + candidate verification.

The unfused serving hot path bounces the leaf-verification operands through
HBM three times per batch: the frontier kernel writes the (M, F) survivor
matrix, the host-side trace gathers the selected leaves' object blocks into
a dense ``(M, take*OBJ)`` candidate plane -- the bitmap slab alone is
``(M, take*OBJ, W)`` u32, by far the biggest intermediate of a descent --
and ``skr_verify`` streams that plane back in. The fused kernels consume
the survivor-derived leaf selection directly and perform the gather INSIDE
the kernel, so the gathered candidate plane never exists in HBM.

Both variants produce outputs bit-identical to ``gather -> skr_verify``
(same candidate ordering: leaf-slot-major, ``-1`` at non-matches), pinned
by the ref-oracle sweeps in tests/test_kernels.py and the engine-level
fused/unfused parity suite in tests/test_query_parity.py:

* ``ids``  (M, T*OBJ) int32 -- matching object ids, ``-1`` elsewhere;
* ``kwv``  (M, T)     int32 -- per leaf slot, the count of keyword-matching
  valid candidates (the Eq.1 ``verified`` partial sums).

Layout notes (TPU) -- two bank regimes, one kernel body. Both variants
run an ``(M, T, OBJ / BO)`` grid: one (query, leaf slot) pair per step, the
leaf's objects in tiles of ``BO``. The selected leaf ids and slot flags
ride in as *scalar-prefetch* operands (``pltpu.PrefetchScalarGridSpec``);
there is no in-kernel vector gather, which the TPU compiler does not
support. Each step reads the leaf's ``(1, BO)`` coordinate/id rows and its
``(BO, W)`` bitmap rows, tests the words with ``keyword.row_word_hit`` (a
ones-block contraction on the MXU that also puts the objects on the
lanes), and writes one lane-dense ``(1, BO)`` id row plus a running
per-slot count.

* ``fused_verify`` (VMEM variant): the object bank is mapped whole into
  VMEM once (constant index maps, single-buffered) and each step reads the
  selected leaf's rows in place. Right answer when the bank fits VMEM
  (small-to-medium single-chip indexes); the kernel's VMEM limit is sized
  from the bank's padded footprint (``ops.resident_bank_vmem_bytes``).
* ``fused_verify_prefetch`` (scalar-prefetch variant): the leaf ids drive
  the bank BlockSpec index maps, so the pipeline DMAs exactly the selected
  ``(1, BO)`` / ``(BO, W)`` tiles -- only the chosen leaf rows ever enter
  VMEM. Steps of unselected slots re-address the previous step's block, so
  the pipeline issues no DMA for them. This keeps the fused path (and its
  one-HBM-pass byte profile) for leaf banks far beyond VMEM.

Auto-selection lives in ``ops.fused_gather_verify(variant="auto")``, the
default the engine's ``serve/engine.py::_verify_leaves`` passes through: it
compares the bank's byte size (``leaf_bank_bytes``, the ``obj_x/y/bm/id``
rows) against ``ops.FUSED_VMEM_BANK_BYTES`` and picks the VMEM variant
below the cutoff, the prefetch variant above it -- so the engine never
falls back to the unfused HBM round-trip on bank-size grounds (with a live
DeltaBuffer only its insert slots take the unfused verify).
``variant="vmem"``/``"prefetch"`` force either side for A/B rows and the
beyond-VMEM oracle sweeps.

Compact-bank twins (DESIGN.md §3.5): ``fused_verify_compact`` /
``fused_verify_prefetch_compact`` verify against the leaf-local vocabulary
slab (``(K, OBJ, Wl)`` with ``Wl << W``; serve/snapshot.py:
``encode_leaf_vocab``). The query side arrives already remapped per
selected leaf (``ops.remap_query_words``): ``q_cbm (M, T, Wl)`` holds each
query's words over slot ``t``'s leaf-local bit ids and ``q_sig (M, T)``
their OR-fold. The keyword test gains a one-word signature prefilter --
``(obj_sig & q_sig) != 0`` AND the word-plane any-reduction -- which is
implied by the word test (a real overlap always sets a shared signature
bit), so outputs stay bit-identical to the full-width kernels while
non-matching objects are decided on one word instead of ``Wl``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .keyword import row_word_hit

# VMEM the resident variant keeps free beside its bank: double-buffered
# query/output tiles and the (BO, W) temporaries of the word test
_RESIDENT_HEADROOM_BYTES = 16 * 1024 * 1024


def _pad128(n: int) -> int:
    return -(-int(n) // 128) * 128


def _pad8(n: int) -> int:
    return -(-int(n) // 8) * 8


def resident_bank_vmem_bytes(n_leaves: int, obj_per_leaf: int, n_words: int, n_rows: int) -> int:
    """VMEM the resident (VMEM-variant) kernel's bank occupies: ``n_rows``
    (K, OBJ) 32-bit planes plus the (K, OBJ, W) bitmap slab, each padded to
    the (8, 128) 32-bit tile -- a slab with W < 128 words still fills 128
    lanes per object."""
    K, OBJ, W = int(n_leaves), int(obj_per_leaf), int(n_words)
    rows = n_rows * _pad8(K) * _pad128(OBJ) * 4
    return rows + K * _pad8(OBJ) * _pad128(W) * 4


def _fused_kernel(*refs, resident: bool, bo: int, compact: bool):
    if compact:
        (tl_ref, ok_ref, fix_ref, qsig_ref, qr_ref, qw_ref,
         ox_ref, oy_ref, ow_ref, osig_ref, oid_ref, ids_ref, kwv_ref) = refs
    else:
        (tl_ref, ok_ref, fix_ref, qr_ref, qw_ref,
         ox_ref, oy_ref, ow_ref, oid_ref, ids_ref, kwv_ref) = refs
    del fix_ref  # index-map only
    i, t, o = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ok = ok_ref[i, t] > 0

    @pl.when(o == 0)
    def _init():
        kwv_ref[...] = jnp.zeros(kwv_ref.shape, jnp.int32)

    @pl.when(ok)
    def _verify():
        if resident:  # whole bank in VMEM: read the leaf's tile in place
            leaf = tl_ref[i, t]
            off = pl.multiple_of(o * bo, bo)

            def row(ref):
                return ref[pl.ds(leaf, 1), pl.ds(off, bo)]

            words = ow_ref[leaf, pl.ds(off, bo), :]  # (BO, W)
        else:  # the pipeline already DMA'd exactly this leaf's tile

            def row(ref):
                return ref[...]

            words = ow_ref[...]
        cx, cy, cid = row(ox_ref), row(oy_ref), row(oid_ref)  # (1, BO)
        kw = row_word_hit(words, qw_ref[...])  # (1, BO)
        if compact:  # one-word signature prefilter (implied by the word test)
            kw = kw & ((row(osig_ref) & qsig_ref[i, t]) != 0)
        qr = qr_ref[...]  # (1, 4)
        inr = (
            (cx >= qr[:, 0:1])
            & (cx <= qr[:, 2:3])
            & (cy >= qr[:, 1:2])
            & (cy <= qr[:, 3:4])
        )
        valid = cid >= 0
        ids_ref[...] = jnp.where(inr & kw & valid, cid, -1)
        kwv_ref[...] += jnp.sum((kw & valid).astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(jnp.logical_not(ok))
    def _skip():
        ids_ref[...] = jnp.full(ids_ref.shape, -1, jnp.int32)


def _fused_call(
    q_rects, q_words, q_sig, top_leaf, leaf_ok, bank, *, resident, bo, interpret
):
    """Common implementation of the four fused kernels.

    ``bank`` is ``(obj_x, obj_y, obj_words, obj_id)`` or, compact,
    ``(obj_x, obj_y, obj_words, obj_sig, obj_id)``; ``q_words`` is (M, W)
    (one bitmap per query) or, compact, (M, T, Wl) (remapped per slot) with
    ``q_sig`` (M, T). Returns ``(ids (M, T*OBJ) i32, kwv (M, T) i32)``."""
    compact = q_sig is not None
    M, T = top_leaf.shape
    K, OBJ = bank[0].shape
    W = bank[2].shape[2]
    bo = min(bo, OBJ)
    n_ob = pl.cdiv(OBJ, bo)
    OBJp = n_ob * bo
    obj_id = bank[-1]
    planes = list(bank[:-1])
    if compact:  # signatures meet the int32 scalar-prefetched query signature
        planes[3] = jax.lax.bitcast_convert_type(bank[3], jnp.int32)
    if OBJp != OBJ:
        pad = OBJp - OBJ
        planes = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in planes]
        obj_id = jnp.pad(obj_id, [(0, 0), (0, pad)], constant_values=-1)
    planes.append(obj_id)
    ox, oy, ow, *rest = planes  # rest: [sig,] id

    safe = jnp.clip(top_leaf.astype(jnp.int32), 0, K - 1)
    ok = (leaf_ok > 0).astype(jnp.int32)
    # an unselected slot's steps re-address the block the previous step
    # used (the last selected slot's final tile), so the pipeline skips
    # their DMAs; the kernel writes -1 ids for them without reading
    flat = jnp.arange(M * T, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(ok.reshape(-1) > 0, flat, -1))
    prev = jnp.where(last >= 0, safe.reshape(-1)[jnp.maximum(last, 0)], 0)
    tl_eff = jnp.where(ok.reshape(-1) > 0, safe.reshape(-1), prev).reshape(M, T)
    fix_o = jnp.where(last >= 0, n_ob - 1, 0).reshape(M, T).astype(jnp.int32)
    prefetch = [tl_eff, ok, fix_o]
    if compact:
        prefetch.append(jax.lax.bitcast_convert_type(q_sig, jnp.int32))
    n_pf = len(prefetch)

    def bank_o(o, i, t, ok_ref, fix_ref):
        return ok_ref[i, t] * o + (1 - ok_ref[i, t]) * fix_ref[i, t]

    if resident:
        one = pl.Buffered(1)
        row_spec = pl.BlockSpec((K, OBJp), lambda i, t, o, *_: (0, 0), pipeline_mode=one)
        word_spec = pl.BlockSpec(
            (K, OBJp, W), lambda i, t, o, *_: (0, 0, 0), pipeline_mode=one
        )
        n_rows = len(rest) + 2
        params = pltpu.CompilerParams(
            vmem_limit_bytes=resident_bank_vmem_bytes(K, OBJp, W, n_rows)
            + _RESIDENT_HEADROOM_BYTES
        )
    else:
        row_spec = pl.BlockSpec(
            (None, 1, bo),
            lambda i, t, o, tl, okr, fix, *_: (tl[i, t], 0, bank_o(o, i, t, okr, fix)),
        )
        word_spec = pl.BlockSpec(
            (None, bo, W),
            lambda i, t, o, tl, okr, fix, *_: (tl[i, t], bank_o(o, i, t, okr, fix), 0),
        )
        ox, oy, *rest = [a.reshape(K, 1, OBJp) for a in (ox, oy, *rest)]
        params = None
    if compact:
        qw = q_words.reshape(M * T, 1, -1)
        qw_spec = pl.BlockSpec((None, 1, qw.shape[2]), lambda i, t, o, *_: (i * T + t, 0, 0))
    else:
        qw = q_words.reshape(M, 1, -1)
        qw_spec = pl.BlockSpec((None, 1, qw.shape[2]), lambda i, t, o, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pf,
        grid=(M, T, n_ob),
        in_specs=[
            pl.BlockSpec((None, 1, 4), lambda i, t, o, *_: (i, 0, 0)),
            qw_spec,
            row_spec,
            row_spec,
            word_spec,
            *([row_spec] * len(rest)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, bo), lambda i, t, o, *_: (i * T + t, 0, o)),
            pl.BlockSpec((None, 1, 128), lambda i, t, o, *_: (i * T + t, 0, 0)),
        ],
    )
    kernel = functools.partial(_fused_kernel, resident=resident, bo=bo, compact=compact)
    ids, kwv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((M * T, 1, OBJp), jnp.int32),
            jax.ShapeDtypeStruct((M * T, 1, 128), jnp.int32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="fused_verify" + ("" if resident else "_prefetch") + ("_compact" if compact else ""),
    )(*prefetch, q_rects.reshape(M, 1, 4), qw, ox, oy, ow, *rest)
    ids = ids.reshape(M, T, OBJp)[:, :, :OBJ].reshape(M, T * OBJ)
    return ids, kwv[:, 0, 0].reshape(M, T)


@functools.partial(jax.jit, static_argnames=("bo", "interpret"))
def fused_verify(
    q_rects: jax.Array,  # (M, 4) f32
    q_bm: jax.Array,  # (M, W) u32
    top_leaf: jax.Array,  # (M, T) int32 selected leaf ids
    leaf_ok: jax.Array,  # (M, T) int8 (1 = slot holds a selected leaf)
    obj_x: jax.Array,  # (K, OBJ) f32 leaf object bank
    obj_y: jax.Array,  # (K, OBJ) f32
    obj_bm: jax.Array,  # (K, OBJ, W) u32
    obj_id: jax.Array,  # (K, OBJ) int32, -1 pad
    bo: int = 1024,
    interpret: bool = False,
):
    """(ids (M, T*OBJ) i32, kwv (M, T) i32): fused gather+verify over the
    VMEM-resident leaf bank, ``bo`` objects per grid step."""
    return _fused_call(
        q_rects, q_bm, None, top_leaf, leaf_ok, (obj_x, obj_y, obj_bm, obj_id),
        resident=True, bo=bo, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bo", "interpret"))
def fused_verify_prefetch(
    q_rects: jax.Array,  # (M, 4) f32
    q_bm: jax.Array,  # (M, W) u32
    top_leaf: jax.Array,  # (M, T) int32 selected leaf ids (dirty ids allowed)
    leaf_ok: jax.Array,  # (M, T) int8 (1 = slot holds a selected leaf)
    obj_x: jax.Array,  # (K, OBJ) f32 leaf object bank (HBM-resident)
    obj_y: jax.Array,  # (K, OBJ) f32
    obj_bm: jax.Array,  # (K, OBJ, W) u32
    obj_id: jax.Array,  # (K, OBJ) int32, -1 pad
    bo: int = 1024,
    interpret: bool = False,
):
    """Scalar-prefetched twin of ``fused_verify`` for banks beyond VMEM.

    The clamped leaf-id matrix drives the bank BlockSpecs, so each grid
    step DMAs exactly the ``(1, BO)`` / ``(BO, W)`` tile of the leaf that
    (query, slot) pair selected. Elementwise-identical outputs to
    ``fused_verify`` (same clamp + ``leaf_ok``/``cid`` validity semantics)."""
    return _fused_call(
        q_rects, q_bm, None, top_leaf, leaf_ok, (obj_x, obj_y, obj_bm, obj_id),
        resident=False, bo=bo, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bo", "interpret"))
def fused_verify_compact(
    q_rects: jax.Array,  # (M, 4) f32
    q_cbm: jax.Array,  # (M, T, Wl) u32 leaf-local remapped query words
    q_sig: jax.Array,  # (M, T) u32 per-(query, slot) signature
    top_leaf: jax.Array,  # (M, T) int32 selected leaf ids
    leaf_ok: jax.Array,  # (M, T) int8 (1 = slot holds a selected leaf)
    obj_x: jax.Array,  # (K, OBJ) f32 leaf object bank
    obj_y: jax.Array,  # (K, OBJ) f32
    obj_cbm: jax.Array,  # (K, OBJ, Wl) u32 compact bitmap slab
    obj_sig: jax.Array,  # (K, OBJ) u32 OR-fold signatures
    obj_id: jax.Array,  # (K, OBJ) int32, -1 pad
    bo: int = 1024,
    interpret: bool = False,
):
    """Compact-bank twin of ``fused_verify``: identical (ids, kwv) outputs,
    but the bitmap slab is ``Wl`` leaf-local words + a one-word signature
    instead of ``W`` global words."""
    return _fused_call(
        q_rects, q_cbm, q_sig, top_leaf, leaf_ok,
        (obj_x, obj_y, obj_cbm, obj_sig, obj_id),
        resident=True, bo=bo, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bo", "interpret"))
def fused_verify_prefetch_compact(
    q_rects: jax.Array,  # (M, 4) f32
    q_cbm: jax.Array,  # (M, T, Wl) u32 leaf-local remapped query words
    q_sig: jax.Array,  # (M, T) u32
    top_leaf: jax.Array,  # (M, T) int32 selected leaf ids (dirty ids allowed)
    leaf_ok: jax.Array,  # (M, T) int8
    obj_x: jax.Array,  # (K, OBJ) f32 leaf object bank (HBM-resident)
    obj_y: jax.Array,  # (K, OBJ) f32
    obj_cbm: jax.Array,  # (K, OBJ, Wl) u32
    obj_sig: jax.Array,  # (K, OBJ) u32
    obj_id: jax.Array,  # (K, OBJ) int32, -1 pad
    bo: int = 1024,
    interpret: bool = False,
):
    """Compact-bank twin of ``fused_verify_prefetch``: one DMA per selected
    (query, slot, object tile), with the per-slot remapped query words
    riding the same grid."""
    return _fused_call(
        q_rects, q_cbm, q_sig, top_leaf, leaf_ok,
        (obj_x, obj_y, obj_cbm, obj_sig, obj_id),
        resident=False, bo=bo, interpret=interpret,
    )
