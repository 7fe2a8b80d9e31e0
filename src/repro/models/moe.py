"""Mixture-of-Experts FFN: capacity-based top-k routing with shared experts.

Two implementations with identical math:

* ``moe_ffn_dense``  -- reference path (single device / smoke tests): top-C
  token selection per expert, gather -> expert FFN -> weighted scatter-add.
* ``moe_ffn_sharded`` -- production path: an explicit ``shard_map`` over the
  mesh. Tokens stay sharded over the data axes and *replicated* over
  ``model``; experts shard over ``model`` (EP); FSDP-sharded expert weights
  are all-gathered per layer inside the region; outputs ``psum`` over
  ``model``. No all-to-all is needed because every model-rank sees its data
  group's tokens -- the EP collective cost is one activation psum, which the
  roofline analysis attributes explicitly.

Experts are padded to a multiple of EP_PAD (=16, the production model-axis
size) at init; the router masks padding experts to -inf.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..configs.base import ArchConfig
from ..sharding.rules import constrain, dp_axes
from .layers import Param, make, _dtype

EP_PAD = 16


def n_experts_padded(cfg: ArchConfig) -> int:
    return -(-cfg.n_experts // EP_PAD) * EP_PAD


def init_moe(key, cfg: ArchConfig) -> Dict:
    ks = jax.random.split(key, 5)
    d, f = cfg.d_model, cfg.d_expert or cfg.d_ff
    E = n_experts_padded(cfg)
    dt = _dtype(cfg)
    p = dict(
        w_router=make(ks[0], (d, E), ("wembed", None), 1.0, jnp.float32),
        w_gate=make(ks[1], (E, d, f), ("experts", "wembed", "expert_mlp"), 1.0, dt),
        w_up=make(ks[2], (E, d, f), ("experts", "wembed", "expert_mlp"), 1.0, dt),
        w_down=make(ks[3], (E, f, d), ("experts", "expert_mlp", "wembed"), 1.0, dt),
    )
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        kss = jax.random.split(ks[4], 3)
        p["shared"] = dict(
            w_gate=make(kss[0], (d, fs), ("wembed", "mlp"), 1.0, dt),
            w_up=make(kss[1], (d, fs), ("wembed", "mlp"), 1.0, dt),
            w_down=make(kss[2], (fs, d), ("mlp", "wembed"), 1.0, dt),
        )
    return p


def _shared_ffn(p: Dict, x: jax.Array, rules) -> jax.Array:
    g = constrain(x @ p["w_gate"], ("batch", "seq", "act_mlp"), rules)
    u = constrain(x @ p["w_up"], ("batch", "seq", "act_mlp"), rules)
    return constrain((jax.nn.silu(g) * u) @ p["w_down"], ("batch", "seq", "embed"), rules)


def _route(x2d: jax.Array, w_router: jax.Array, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """(T, d) -> (probs (T, E) f32 with padding masked, topk idx (T, K))."""
    E = w_router.shape[1]
    logits = (x2d.astype(jnp.float32) @ w_router).astype(jnp.float32)
    if E > cfg.n_experts:
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_idx = jax.lax.top_k(logits, cfg.moe_topk)
    return probs, top_idx


def _expert_compute(xg: jax.Array, wg, wu, wd) -> jax.Array:
    """xg: (E, C, d); weights (E, d, f)/(E, f, d) -> (E, C, d)."""
    g = jnp.einsum("ecd,edf->ecf", xg, wg)
    u = jnp.einsum("ecd,edf->ecf", xg, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)


def _capacity(n_tokens: int, cfg: ArchConfig, n_experts: int) -> int:
    c = int(n_tokens * cfg.moe_topk * cfg.capacity_factor / max(n_experts, 1))
    return max(8, -(-c // 8) * 8)


def _select_and_apply(
    x2d: jax.Array, probs: jax.Array, top_idx: jax.Array, wg, wu, wd, cfg: ArchConfig,
    e_lo: int, e_n: int, cap: int,
) -> jax.Array:
    """Top-C selection per expert in [e_lo, e_lo+e_n), FFN, weighted combine.

    Returns (T, d) partial output covering only these experts.
    """
    T, d = x2d.shape
    K = top_idx.shape[1]
    # score[e_local, t] = prob if expert in token's top-k else -1
    eids = e_lo + jnp.arange(e_n)  # (e_n,)
    chosen = (top_idx[None, :, :] == eids[:, None, None]).any(-1)  # (e_n, T)
    gate = jax.lax.dynamic_slice_in_dim(probs, e_lo, e_n, axis=1).T  # (e_n, T)
    score = jnp.where(chosen, gate, -1.0)
    top_val, tok_idx = jax.lax.top_k(score, min(cap, T))  # (e_n, C)
    valid = top_val > 0.0
    xg = x2d[tok_idx.reshape(-1)].reshape(e_n, -1, d)  # (e_n, C, d)
    yg = _expert_compute(xg, wg, wu, wd)
    w = jnp.where(valid, top_val, 0.0).astype(yg.dtype)[..., None]  # (e_n, C, 1)
    y = jnp.zeros((T, d), yg.dtype)
    y = y.at[tok_idx.reshape(-1)].add((yg * w).reshape(-1, d))
    return y


def moe_ffn_dense(params: Dict, x: jax.Array, cfg: ArchConfig, rules) -> jax.Array:
    """Reference MoE (no shard_map): full expert set on every device."""
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    probs, top_idx = _route(x2d, params["w_router"], cfg)
    E = params["w_gate"].shape[0]
    cap = _capacity(x2d.shape[0], cfg, cfg.n_experts)
    y = _select_and_apply(
        x2d, probs, top_idx, params["w_gate"], params["w_up"], params["w_down"], cfg, 0, E, cap
    )
    out = y.reshape(B, S, d).astype(x.dtype)
    if "shared" in params:
        out = out + _shared_ffn(params["shared"], x, rules)
    return constrain(out, ("batch", "seq", "embed"), rules)


def moe_ffn_sharded(params: Dict, x: jax.Array, cfg: ArchConfig, rules, mesh: Mesh) -> jax.Array:
    """Production MoE: shard_map EP over 'model', DP over data axes."""
    B, S, d = x.shape
    dp = dp_axes(mesh)
    E = params["w_gate"].shape[0]
    n_model = mesh.shape["model"]
    e_n = E // n_model
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    t_loc = max(1, (B * S) // n_dp)
    cap = _capacity(t_loc, cfg, cfg.n_experts)

    def local(xb, wr, wg, wu, wd):
        # xb: (B_loc, S, d) local tokens; weights: local experts, d FSDP-sharded
        wg = jax.lax.all_gather(wg, dp, axis=1, tiled=True) if dp else wg
        wu = jax.lax.all_gather(wu, dp, axis=1, tiled=True) if dp else wu
        wd = jax.lax.all_gather(wd, dp, axis=2, tiled=True) if dp else wd
        x2d = xb.reshape(-1, d)
        probs, top_idx = _route(x2d, wr, cfg)
        e_lo = jax.lax.axis_index("model") * e_n
        y = _select_and_apply(x2d, probs, top_idx, wg, wu, wd, cfg, e_lo, e_n, cap)
        y = jax.lax.psum(y, "model")
        return y.reshape(xb.shape)

    y = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(dp if dp else None, None, None),
            P(None, None),
            P("model", dp if dp else None, None),
            P("model", dp if dp else None, None),
            P("model", None, dp if dp else None),
        ),
        out_specs=P(dp if dp else None, None, None),
        check_vma=False,
    )(x, params["w_router"], params["w_gate"], params["w_up"], params["w_down"])
    out = y.astype(x.dtype)
    if "shared" in params:
        out = out + _shared_ffn(params["shared"], x, rules)
    return constrain(out, ("batch", "seq", "embed"), rules)


def moe_ffn(params: Dict, x: jax.Array, cfg: ArchConfig, rules, mesh: Optional[Mesh]) -> jax.Array:
    if mesh is not None and "model" in mesh.axis_names and mesh.shape["model"] > 1:
        E = params["w_gate"].shape[0]
        if E % mesh.shape["model"] == 0:
            return moe_ffn_sharded(params, x, cfg, rules, mesh)
    return moe_ffn_dense(params, x, cfg, rules)
