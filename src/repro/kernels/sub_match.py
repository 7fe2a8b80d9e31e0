"""Pallas TPU kernel: continuous-filter subscription matching (DESIGN.md §8).

The pub-sub subsystem (serve/subscribe.py) inverts the SKR problem: the
*subscriptions* are the indexed set -- a padded power-of-two block of
standing (rect, keyword bitmap) filters -- and every arriving object is a
point query matched against all of them in one cross-product sweep, the
FAST-style continuous-query scenario of ROADMAP item 2.

Predicate per (object, subscription) pair, Boolean semantics identical to
the SKR path: the object's point lies inside the subscription rectangle
(closed; a zero-area rect matches objects exactly at that point) AND the
keyword bitmaps share at least one bit (an empty keyword set matches
nothing, the same contract as an empty SKR query).

The kernel reuses the two bandwidth tricks of the descent kernels:

* **packed object word planes** (ops.pack_query_words): each arriving
  object carries only its nonzero bitmap words -- ``(BN, Wp)`` ids +
  values with Wp a static power-of-two bucket -- and the XLA gather that
  feeds the kernel pulls just those words out of the word-major ``(W, S)``
  subscription block, so the big operand is ``(N, Wp, S)`` instead of
  ``(N, W, S)`` (the TPU compiler has no in-kernel vector gather);
* **one-word OR-fold signatures**: a per-side 32-bit OR of all
  words; ``(o_sig & s_sig) != 0`` is a necessary condition for any shared
  bit, ANDed in as a register-cheap prefilter (empty slots on either side
  carry signature 0 and are therefore inert -- padding needs no separate
  validity plane).

Grid: ``(cdiv(N, bn), cdiv(S, bs))`` object x subscription tiles, the
subscriptions on the lanes (rects as ``(4, S)`` planes, word planes
word-major so the keyword any-reduction is a sublane max --
``keyword.word_hit``); output is the (N, S) int8 match matrix. The ref
twin is ``ref.sub_match_ref``; the brute-force ground truth (set
semantics, no bitmaps at all) is ``core.query.match_subscriptions_bruteforce``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .keyword import word_hit


def _sub_match_kernel(
    o_pts_ref, o_bits_ref, o_sig_ref, s_rects_ref, s_words_ref, s_sig_ref, out_ref
):
    op = o_pts_ref[...]  # (BN, 2) f32 object points
    sr = s_rects_ref[...]  # (4, BS) f32 subscription rect planes (NEVER_RECT pads)
    x = op[:, 0:1]  # (BN, 1)
    y = op[:, 1:2]
    inr = (
        (x >= sr[0:1]) & (x <= sr[2:3]) & (y >= sr[1:2]) & (y <= sr[3:4])
    )  # (BN, BS) point-in-rect
    # (BN, 1) x (1, BS) OR-fold signatures: shared-bit prefilter
    sig = (o_sig_ref[...] & s_sig_ref[...]) != 0
    kw = word_hit(s_words_ref[...], o_bits_ref[...])  # (BN, Wp, BS) x (BN, Wp)
    out_ref[...] = (inr & sig & kw).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bn", "bs", "interpret"))
def sub_match(
    o_pts: jax.Array,  # (N, 2) f32 arriving object points
    o_wids: jax.Array,  # (N, Wp) int32 packed word ids (ops.pack_query_words)
    o_bits: jax.Array,  # (N, Wp) uint32 packed word values
    o_sig: jax.Array,  # (N, 1) uint32 OR-fold object signatures
    s_rects: jax.Array,  # (S, 4) f32 subscription rects
    s_bm: jax.Array,  # (S, W) uint32 subscription bitmaps
    s_sig: jax.Array,  # (S, 1) uint32 OR-fold subscription signatures
    bn: int = 8,
    bs: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(N, S) int8 match matrix. Inputs padded to tile multiples by ops.py."""
    N = o_pts.shape[0]
    S = s_rects.shape[0]
    Wp = o_wids.shape[1]
    bn = min(bn, N)
    bs = min(bs, S)
    s_words = s_bm.T[o_wids.astype(jnp.int32)]  # (N, Wp, S) objects' words
    grid = (pl.cdiv(N, bn), pl.cdiv(S, bs))
    out = pl.pallas_call(
        _sub_match_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, Wp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((4, bs), lambda i, j: (0, j)),
            pl.BlockSpec((bn, Wp, bs), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, bs), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, S), jnp.int32),
        interpret=interpret,
        name="sub_match",
    )(o_pts, o_bits, o_sig, s_rects.T, s_words, s_sig.T)
    return out.astype(jnp.int8)
