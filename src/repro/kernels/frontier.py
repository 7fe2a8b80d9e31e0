"""Pallas TPU kernels: sparse-frontier node filtering (DESIGN.md §3).

``skr_filter`` scores the full (query x node) cross product -- O(M*K) work
per level no matter how selective the learned hierarchy is. The frontier
kernels instead receive, per query, a *gathered* tile of candidate nodes
(the query's frontier): MBRs ``(BM, BF, 4)``, bitmaps ``(BM, BF, W)`` and a
validity plane for the -1 padding slots, so per-level work is O(M*F) with F
the bucketed frontier width, not the level width.

Two variants share the rectangle-intersect + keyword-AND predicate:

* ``frontier_filter`` -- the full-width f32/uint32 baseline (kept for A/B
  and for the delta-augmented fallback, whose planes are not dictionary
  encoded).
* ``frontier_filter_narrow`` -- the bandwidth-lean descent. MBR planes
  arrive as **int16 rank codes** into per-level sorted coordinate
  dictionaries and are dequantized to the exact f32 coordinates by the XLA
  gather that feeds the kernel (lossless, so the survivor set is
  bit-identical to the f32 path -- strictly stronger than the
  conservative-superset requirement; the TPU compiler has no in-kernel
  vector gather). Bitmaps arrive as **packed word planes**:
  ops.pack_query_words keeps only each query's nonzero bitmap words (static
  bucketed width Wp <= W), and the engine gathers just those Wp words per
  frontier slot, so the biggest descent operand shrinks from ``(M, F, W)``
  u32 to ``(M, F, Wp)``.

Layout notes (TPU): both variants run one kernel on lane-dense planes --
the four MBR coordinates as ``(4, M, F)`` planes and the bitmaps word-major
``(M, W, F)`` -- so the frontier width is the minor (lane) dimension (BF =
128 lanes by default) and the keyword any-reduction is a sublane max over
the word axis (``keyword.word_hit``). The transposes ride the XLA gathers
that build the planes. Validity and survivors cross the kernel boundary as
int32 (v5e has no int8 vector compare); the wrappers keep the int8
interface.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .keyword import word_hit


def _frontier_kernel(q_rects_ref, q_bm_ref, f_mbrs_ref, f_bm_ref, f_valid_ref, out_ref):
    qr = q_rects_ref[...]  # (BM, 4)
    fm = f_mbrs_ref[...]  # (4, BM, BF) xlo/ylo/xhi/yhi planes
    inter = (
        (qr[:, 0:1] <= fm[2])
        & (fm[0] <= qr[:, 2:3])
        & (qr[:, 1:2] <= fm[3])
        & (fm[1] <= qr[:, 3:4])
    )  # (BM, BF)
    kw = word_hit(f_bm_ref[...], q_bm_ref[...])  # (BM, W, BF) x (BM, W)
    out_ref[...] = (inter & kw & (f_valid_ref[...] > 0)).astype(jnp.int32)


def _frontier_call(q_rects, q_bm, planes, words, f_valid, bm, bf, interpret):
    """Survivors of ``planes`` (4, M, F) f32 / ``words`` (M, W, F) against
    the queries; (M, F) int8."""
    M, W, F = words.shape
    bm = min(bm, M)
    bf = min(bf, F)
    grid = (pl.cdiv(M, bm), pl.cdiv(F, bf))
    out = pl.pallas_call(
        _frontier_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, W), lambda i, j: (i, 0)),
            pl.BlockSpec((4, bm, bf), lambda i, j: (0, i, j)),
            pl.BlockSpec((bm, W, bf), lambda i, j: (i, 0, j)),
            pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, F), jnp.int32),
        interpret=interpret,
        name="frontier_filter",
    )(q_rects, q_bm, planes, words, f_valid.astype(jnp.int32))
    return out.astype(jnp.int8)


def dequantize_mbrs(f_codes, dict_x, dict_y):
    """(4, M, F) exact f32 coordinate planes from (M, F, 4) int16 rank
    codes and the level's sorted coordinate dictionaries."""
    fc = f_codes.astype(jnp.int32)
    return jnp.stack(
        [dict_x[fc[..., 0]], dict_y[fc[..., 1]], dict_x[fc[..., 2]], dict_y[fc[..., 3]]]
    )


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def frontier_filter(
    q_rects: jax.Array,  # (M, 4)
    q_bm: jax.Array,  # (M, W)
    f_mbrs: jax.Array,  # (M, F, 4)
    f_bm: jax.Array,  # (M, F, W)
    f_valid: jax.Array,  # (M, F) int8
    bm: int = 8,
    bf: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(M, F) int8 survivor matrix. Inputs padded to tile multiples by ops.py."""
    return _frontier_call(
        q_rects, q_bm, jnp.moveaxis(f_mbrs, -1, 0), jnp.swapaxes(f_bm, 1, 2),
        f_valid, bm, bf, interpret,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def frontier_filter_narrow(
    q_rects: jax.Array,  # (M, 4) f32
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
    """(M, F) int8 survivor matrix, bit-identical to ``frontier_filter`` on
    the dequantized planes. Inputs padded to tile multiples by ops.py."""
    return _frontier_call(
        q_rects, q_bits, dequantize_mbrs(f_codes, dict_x, dict_y),
        jnp.swapaxes(f_bm, 1, 2), f_valid, bm, bf, interpret,
    )
