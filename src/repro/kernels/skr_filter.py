"""Pallas TPU kernel: SKR node filtering (the Eq.1 ``w1`` stage).

For a tile of queries and a tile of index nodes, decide in one VMEM-resident
pass whether each (query, node) pair is *relevant*: the query rectangle
intersects the node MBR AND the query keyword bitmap shares >=1 bit with the
node bitmap. This is the hot loop of level-synchronous traversal: on HBM it
touches ``M*4 + M*W + K*4 + K*W`` words and emits ``M*K`` bytes, so blocking
both operands into VMEM and reducing the bitmap-word axis in one packed
``any``-reduction keeps it at one HBM read per operand tile instead of one
per pair. (The node planes here are *shared* across the query tile --
node-major -- so, unlike the frontier kernels, there is no per-query packed
gather to exploit; the full W words stay resident.)

Layout notes (TPU): the minor dimension of the output tile is the node tile
(BK = 128 lanes); rect coordinates ride along as 4-wide minor arrays which
Mosaic pads -- acceptable because they are tiny next to the bitmap planes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _filter_kernel(q_rects_ref, q_bm_ref, n_mbrs_ref, n_bm_ref, out_ref):
    qr = q_rects_ref[...]  # (BM, 4)
    nr = n_mbrs_ref[...]  # (BK, 4)
    inter = (
        (qr[:, 0:1] <= nr[None, :, 2])
        & (nr[None, :, 0] <= qr[:, 2:3])
        & (qr[:, 1:2] <= nr[None, :, 3])
        & (nr[None, :, 1] <= qr[:, 3:4])
    )  # (BM, BK)
    qb = q_bm_ref[...]  # (BM, W) uint32
    nb = n_bm_ref[...]  # (BK, W) uint32
    # packed word-plane AND + single any-reduction per tile (popcount-style)
    kw = jnp.any((qb[:, None, :] & nb[None, :, :]) != 0, axis=-1)  # (BM, BK)
    out_ref[...] = (inter & kw).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def skr_filter(
    q_rects: jax.Array,
    q_bm: jax.Array,
    n_mbrs: jax.Array,
    n_bm: jax.Array,
    bm: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(M, K) int8 relevance matrix. Inputs padded to tile multiples by ops.py."""
    M, K = q_rects.shape[0], n_mbrs.shape[0]
    W = q_bm.shape[1]
    bm = min(bm, M)
    bk = min(bk, K)
    grid = (pl.cdiv(M, bm), pl.cdiv(K, bk))
    return pl.pallas_call(
        _filter_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, W), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, 4), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, W), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, K), jnp.int8),
        interpret=interpret,
        name="skr_filter",
    )(q_rects, q_bm, n_mbrs, n_bm)
