"""Pallas TPU kernel: candidate verification (the Eq.1 ``w2`` stage).

After filtering, each query holds a capacity-padded candidate list (gathered
from the leaf inverted files). The kernel verifies in-rectangle membership +
keyword bitmap overlap + validity for a (query-tile x candidate-tile) block
entirely in VMEM. The bitmap plane is the big operand; the wrappers hand
it to the kernel word-major (``(BM, W, BC)``, candidates on the lanes) so
the word axis collapses in one sublane any-reduction (``keyword.word_hit``)
and only ``(BM, BC)`` registers accumulate. Candidates re-check in exact
f32 here -- this is the stage that guarantees the narrow-plane descent
(frontier.py) cannot change reported ids. Validity and matches cross the
kernel boundary as int32 (v5e has no int8 vector compare).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .keyword import word_hit


def _in_rect(qr, cx, cy):
    return (
        (cx >= qr[:, 0:1])
        & (cx <= qr[:, 2:3])
        & (cy >= qr[:, 1:2])
        & (cy <= qr[:, 3:4])
    )


def _verify_kernel(q_rects_ref, q_bm_ref, cx_ref, cy_ref, cbm_ref, cv_ref, out_ref):
    inr = _in_rect(q_rects_ref[...], cx_ref[...], cy_ref[...])  # (BM, BC)
    kw = word_hit(cbm_ref[...], q_bm_ref[...])  # (BM, W, BC) x (BM, W)
    out_ref[...] = (inr & kw & (cv_ref[...] > 0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bm", "bc", "interpret"))
def skr_verify(
    q_rects: jax.Array,  # (M, 4)
    q_bm: jax.Array,  # (M, W)
    cand_x: jax.Array,  # (M, C)
    cand_y: jax.Array,  # (M, C)
    cand_bm: jax.Array,  # (M, C, W)
    cand_valid: jax.Array,  # (M, C) int8
    bm: int = 8,
    bc: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, C = cand_x.shape
    W = q_bm.shape[1]
    bm = min(bm, M)
    bc = min(bc, C)
    grid = (pl.cdiv(M, bm), pl.cdiv(C, bc))
    out = pl.pallas_call(
        _verify_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, W), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bm, W, bc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, C), jnp.int32),
        interpret=interpret,
        name="skr_verify",
    )(q_rects, q_bm, cand_x, cand_y, jnp.swapaxes(cand_bm, 1, 2),
      cand_valid.astype(jnp.int32))
    return out.astype(jnp.int8)


def _verify_compact_kernel(
    q_rects_ref, q_cbm_ref, q_sig_ref, cx_ref, cy_ref,
    cbm_ref, csig_ref, cv_ref, out_ref,
):
    inr = _in_rect(q_rects_ref[...], cx_ref[...], cy_ref[...])  # (BM, BO)
    # one-word signature prefilter (implied by the word test -- kw unchanged)
    sig_hit = (csig_ref[...] & q_sig_ref[...]) != 0  # (BM, BO) x (BM, 1)
    kw = sig_hit & word_hit(cbm_ref[...], q_cbm_ref[...])  # (BM, Wl, BO)
    out_ref[...] = (inr & kw & (cv_ref[...] > 0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bm", "bo", "interpret"))
def skr_verify_compact(
    q_rects: jax.Array,  # (M, 4)
    q_cbm: jax.Array,  # (M, T, Wl) leaf-local remapped query words
    q_sig: jax.Array,  # (M, T) per-(query, slot) OR-fold signature
    cand_x: jax.Array,  # (M, T*OBJ) leaf-slot-major gathered candidates
    cand_y: jax.Array,  # (M, T*OBJ)
    cand_cbm: jax.Array,  # (M, T*OBJ, Wl) compact candidate bitmaps
    cand_sig: jax.Array,  # (M, T*OBJ) candidate signatures
    cand_valid: jax.Array,  # (M, T*OBJ) int8
    bm: int = 8,
    bo: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Compact-vocabulary twin of ``skr_verify`` (DESIGN.md §3.5).

    Candidates arrive leaf-slot-major (T slots of OBJ objects each, the
    fused kernels' ordering) because the query-side words differ PER SLOT:
    each selected leaf has its own vocabulary. The kernel therefore runs
    slot-major -- every operand is viewed ``(T, M, OBJ)`` (bitmaps
    ``(T, M, Wl, OBJ)``, word-major) so a block ``(BM, BO)`` of slot ``t``
    pairs with query words ``q_cbm[:, t]`` -- and the ``(T, M, OBJ)`` result
    is put back in leaf-slot-major order. ``OBJ`` is tiled by ``bo``
    (padded when ``bo`` does not divide it)."""
    M, T = q_sig.shape
    Wl = q_cbm.shape[2]
    OBJ = cand_x.shape[1] // T
    bm = min(bm, M)
    bo = min(bo, OBJ)
    OBJp = pl.cdiv(OBJ, bo) * bo

    def slot_major(a):  # (M, T*OBJ, ...) -> (T, M, ..., OBJp), zero pads
        a = jnp.moveaxis(a.reshape(M, T, OBJ, *a.shape[2:]), 2, -1)
        a = jnp.moveaxis(a, 1, 0)
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, OBJp - OBJ)])

    grid = (T, pl.cdiv(M, bm), OBJp // bo)
    row = pl.BlockSpec((None, bm, bo), lambda t, i, o: (t, i, o))
    out = pl.pallas_call(
        _verify_compact_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 4), lambda t, i, o: (i, 0)),
            pl.BlockSpec((None, bm, Wl), lambda t, i, o: (t, i, 0)),
            pl.BlockSpec((None, bm, 1), lambda t, i, o: (t, i, 0)),
            row,
            row,
            pl.BlockSpec((None, bm, Wl, bo), lambda t, i, o: (t, i, 0, o)),
            row,
            row,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((T, M, OBJp), jnp.int32),
        interpret=interpret,
        name="skr_verify_compact",
    )(
        q_rects, jnp.moveaxis(q_cbm, 1, 0), q_sig.T[:, :, None],
        slot_major(cand_x), slot_major(cand_y), slot_major(cand_cbm),
        slot_major(cand_sig), slot_major(cand_valid.astype(jnp.int32)),
    )
    return jnp.moveaxis(out[:, :, :OBJ], 0, 1).reshape(M, T * OBJ).astype(jnp.int8)
