"""Pallas TPU kernel: fused CDF-MLP bank forward.

WISK keeps one tiny MLP (1 -> H -> H -> H -> 1, H=16) per high-frequency
keyword and evaluates *all* of them at many coordinates during split
learning. Evaluated naively, the ``(N, B, H)`` hidden activations of the
bank round-trip through HBM between the four layers; this kernel keeps a
(point-tile x model-tile) working set in VMEM and applies all four layers +
activations in one pass, writing only the final ``(N, B)`` CDF plane.

Block sizing: BN x BB x H floats x ~2 live layers; with BN=256, BB=64,
H=16 that's ~2 MB of VMEM -- comfortably under the ~16 MB budget while the
batched (BB,H,H) matmuls are MXU-shaped.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cdf_mlp_kernel(x_ref, w0_ref, b0_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, out_ref):
    x = x_ref[...]  # (BN, 1)
    w0 = w0_ref[...]  # (BB, 1, H)
    h = x[:, None, :] * w0[None, :, 0, :] + b0_ref[...][None]  # (BN, BB, H)
    h = jnp.maximum(h, 0.0)
    # batched matmuls over the model dim (dimension_numbers: contract H, batch BB)
    h = jax.lax.dot_general(
        h.swapaxes(0, 1), w1_ref[...], (((2,), (1,)), ((0,), (0,)))
    )  # (BB, BN, H)
    h = jnp.maximum(h + b1_ref[...][:, None, :], 0.0)
    h = jax.lax.dot_general(h, w2_ref[...], (((2,), (1,)), ((0,), (0,))))
    h = jnp.maximum(h + b2_ref[...][:, None, :], 0.0)
    o = jax.lax.dot_general(h, w3_ref[...], (((2,), (1,)), ((0,), (0,))))  # (BB, BN, 1)
    o = o[..., 0] + b3_ref[...][:, 0][:, None]
    out_ref[...] = jax.nn.sigmoid(o).swapaxes(0, 1)  # (BN, BB)


@functools.partial(jax.jit, static_argnames=("bn", "bb", "interpret"))
def cdf_mlp_bank(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (N,)
    bn: int = 256,
    bb: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Evaluate B CDF MLPs at N points -> (N, B)."""
    N = x.shape[0]
    B, _, H = params["w0"].shape
    bn = min(bn, N)
    bb = min(bb, B)
    grid = (pl.cdiv(N, bn), pl.cdiv(B, bb))
    return pl.pallas_call(
        _cdf_mlp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1, H), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bb, H), lambda i, j: (j, 0)),
            pl.BlockSpec((bb, H, H), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bb, H), lambda i, j: (j, 0)),
            pl.BlockSpec((bb, H, H), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bb, H), lambda i, j: (j, 0)),
            pl.BlockSpec((bb, H, 1), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, B), jnp.float32),
        interpret=interpret,
        name="cdf_mlp",
    )(
        x[:, None],
        params["w0"],
        params["b0"],
        params["w1"],
        params["b1"],
        params["w2"],
        params["b2"],
        params["w3"],
        params["b3"],
    )
