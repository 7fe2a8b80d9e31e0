"""Pallas TPU kernels: Boolean-kNN frontier distance filtering (DESIGN.md §6).

The distance-bounded descent generalizes the range frontier filter
(``kernels/frontier.py``): instead of an intersect/bitmap boolean, each
(query, frontier-slot) pair needs the *squared min-distance* from the query
point to the slot's MBR, fused with the keyword-bitmap test, so the serving
engine can prune a slot against the query's current k-th best distance in
one VMEM-resident pass. Slots that fail the bitmap AND (or are ``-1``
padding) come back as ``+inf`` -- the natural "never survives a distance
bound" sentinel, mirroring the NEVER_RECT padding of the range path.

Like the range path, two variants share the predicate: ``knn_filter`` on
full-width f32/uint32 planes (A/B baseline and delta-augmented fallback)
and ``knn_filter_narrow`` on int16 rank-coded MBR planes + packed word
planes. The narrow variant dequantizes the codes to exact f32 in the XLA
gather that feeds the kernel (``frontier.dequantize_mbrs``), so both
variants run the same kernel and the emitted distances are bit-identical
-- the bound-tightening descent and top-k merges see the same numbers on
either path.

Layout notes (TPU): identical tiling to ``frontier_filter`` -- lane-dense
``(4, M, F)`` coordinate planes and word-major ``(M, W, F)`` bitmaps, the
frontier width on the lanes (BF = 128 by default), the keyword test a
sublane max over the word axis (``keyword.word_hit``); only the (BM, BF)
distance/keyword accumulators stay live.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .frontier import dequantize_mbrs
from .keyword import word_hit


def _mbr_sq_dist(px, py, xlo, ylo, xhi, yhi):
    # squared min-distance from point to (closed) MBR: clamp the outside gap
    dx = jnp.maximum(jnp.maximum(xlo - px, px - xhi), 0.0)
    dy = jnp.maximum(jnp.maximum(ylo - py, py - yhi), 0.0)
    return dx * dx + dy * dy


def _knn_kernel(q_pts_ref, q_bm_ref, f_mbrs_ref, f_bm_ref, f_valid_ref, out_ref):
    qp = q_pts_ref[...]  # (BM, 2)
    fm = f_mbrs_ref[...]  # (4, BM, BF) xlo/ylo/xhi/yhi planes
    d2 = _mbr_sq_dist(qp[:, 0:1], qp[:, 1:2], fm[0], fm[1], fm[2], fm[3])
    kw = word_hit(f_bm_ref[...], q_bm_ref[...])  # (BM, W, BF) x (BM, W)
    ok = kw & (f_valid_ref[...] > 0)
    out_ref[...] = jnp.where(ok, d2, jnp.inf).astype(jnp.float32)


def _knn_call(q_pts, q_bm, planes, words, f_valid, bm, bf, interpret):
    """Distances of ``planes`` (4, M, F) f32 / ``words`` (M, W, F) to the
    query points; (M, F) f32."""
    M, W, F = words.shape
    bm = min(bm, M)
    bf = min(bf, F)
    grid = (pl.cdiv(M, bm), pl.cdiv(F, bf))
    return pl.pallas_call(
        _knn_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, W), lambda i, j: (i, 0)),
            pl.BlockSpec((4, bm, bf), lambda i, j: (0, i, j)),
            pl.BlockSpec((bm, W, bf), lambda i, j: (i, 0, j)),
            pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, F), jnp.float32),
        interpret=interpret,
        name="knn_filter",
    )(q_pts, q_bm, planes, words, f_valid.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def knn_filter(
    q_pts: jax.Array,  # (M, 2)
    q_bm: jax.Array,  # (M, W)
    f_mbrs: jax.Array,  # (M, F, 4)
    f_bm: jax.Array,  # (M, F, W)
    f_valid: jax.Array,  # (M, F) int8
    bm: int = 8,
    bf: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(M, F) f32 squared MBR min-distances (+inf where the slot is invalid
    or shares no keyword bit). Inputs padded to tile multiples by ops.py."""
    return _knn_call(
        q_pts, q_bm, jnp.moveaxis(f_mbrs, -1, 0), jnp.swapaxes(f_bm, 1, 2),
        f_valid, bm, bf, interpret,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def knn_filter_narrow(
    q_pts: jax.Array,  # (M, 2) f32
    q_bits: jax.Array,  # (M, Wp) uint32 packed query words (ops.pack_query_words)
    f_codes: jax.Array,  # (M, F, 4) int16 MBR rank codes
    f_bm: jax.Array,  # (M, F, Wp) uint32 packed node word planes
    f_valid: jax.Array,  # (M, F) int8
    dict_x: jax.Array,  # (Dx,) f32
    dict_y: jax.Array,  # (Dy,) f32
    bm: int = 8,
    bf: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(M, F) f32 squared MBR min-distances, bit-identical to ``knn_filter``
    on the dequantized planes (+inf sentinel semantics unchanged)."""
    return _knn_call(
        q_pts, q_bits, dequantize_mbrs(f_codes, dict_x, dict_y),
        jnp.swapaxes(f_bm, 1, 2), f_valid, bm, bf, interpret,
    )
