"""In-kernel keyword-bitmap tests shared by the Pallas kernels.

Two layouts, both in the forms the TPU compiler (Mosaic) accepts on v5e --
no in-kernel vector gathers, no int8 compares, and a result whose slot axis
sits on the 128 lanes so it can be stored lane-dense:

* ``word_hit`` -- word-major planes ``(B, W, L)``: the word axis on
  sublanes, the L slots on lanes. The any-reduction over words is a sublane
  max, so the result ``(B, L)`` is already lane-major. The descent and
  candidate kernels take their gathered planes in this layout (the
  transpose happens in the XLA gather that builds them).
* ``row_word_hit`` -- object-major rows ``(N, W)`` as the snapshot's leaf
  bank stores them: the word axis on lanes. The fused verify kernels read
  bank rows in place, so the reduction over words is a contraction with a
  ones block on the MXU: it sums 0/1 terms (exact in f32 for any realistic
  W) and moves the object axis from sublanes to lanes in the same step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def word_hit(planes, q):
    """(B, L) bool: slot shares a set bit with its query row.

    ``planes`` (B, W, L) bitmap words, ``q`` (B, W) query words (same
    unsigned/signed 32-bit type)."""
    hit = ((planes & q[:, :, None]) != 0).astype(jnp.int32)
    return jnp.max(hit, axis=1) > 0


def row_word_hit(rows, q):
    """(1, N) bool: object row shares a set bit with the query words.

    ``rows`` (N, W) bitmap words, ``q`` (1, W) query words."""
    hit = jnp.where((rows & q) != 0, 1.0, 0.0).astype(jnp.float32)
    ones = jnp.ones((8, rows.shape[1]), jnp.float32)
    cnt = jax.lax.dot_general(
        ones, hit, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return cnt[0:1] > 0
