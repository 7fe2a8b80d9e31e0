"""Legacy leaf-sharded FLAT serving fallback (DESIGN.md §3.4, legacy regime).

This was the repo's original big-index story: abandon the hierarchy, shard
the leaf rows (with their object blocks) over the ``model`` mesh axis, have
every device filter its local leaves against replicated queries, and psum
the per-query counts. It is retired from the serving front door -- the
index-sharded regime (``launch/wisk_serve.py:serve_index_sharded``) serves
large indexes WITH the hierarchy at exact parity -- but stays as:

* the dry-run / roofline lowering surface (``launch/dryrun.py`` inspects
  its HLO on abstract shapes without allocating an index), and
* the A/B floor a hierarchical descent must beat (a flat scan touches every
  leaf; the descent touches ``nodes_checked`` of them).

``launch/wisk_serve.py`` re-exports these names, so historical imports
(tests, notebooks) keep working. On TPU the inner loops are the Pallas
kernels; the dry-run lowers the jnp reference math (identical semantics --
Mosaic kernels cannot target the CPU placeholder backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.wisk import WiskServeConfig
from ..kernels.ref import skr_filter_ref, skr_verify_ref
from jax import shard_map
from ..sharding.rules import default_rules, dp_axes, spec_for

OBJ_PER_LEAF = 512
TOP_LEAVES_LOCAL = 4


def wisk_serve_step(q_rects, q_bm, leaf_mbrs, leaf_bm, obj_x, obj_y, obj_bm, obj_valid,
                    two_stage: bool = False, stage2_cap: int = 512):
    """Local (per-device) filter + verify; counts/scanned/overflow psum'd
    over 'model'.

    q_*: local query shard; leaf_*/obj_*: local leaf shard.

    ``two_stage``: verify in-rectangle membership on the 8-byte (x, y) pairs
    first and gather the 512-byte keyword bitmaps only for the (capacity-
    bounded) spatial survivors -- the memory-roofline hillclimb of
    EXPERIMENTS.md section Perf (bitmap traffic drops ~C/stage2_cap).
    ``overflow`` counts the spatial survivors beyond ``stage2_cap`` whose
    matches the capacity bound dropped -- callers must surface it (counts
    are a lower bound whenever it is nonzero).
    """
    M = q_rects.shape[0]
    rel = skr_filter_ref(q_rects, q_bm, leaf_mbrs, leaf_bm)  # (Mloc, Kloc) int8
    sizes = jnp.sum(obj_valid > 0, axis=1)  # (Kloc,)
    score = rel.astype(jnp.int32) * (1 + sizes[None, :])
    _, top_leaf = jax.lax.top_k(score, TOP_LEAVES_LOCAL)  # (Mloc, L)
    # gather candidate coordinate blocks for each (query, local leaf)
    cx = obj_x[top_leaf].reshape(M, -1)
    cy = obj_y[top_leaf].reshape(M, -1)
    cval = obj_valid[top_leaf].reshape(M, -1)
    # leaves not relevant contribute nothing
    leaf_ok = jnp.take_along_axis(rel, top_leaf, axis=1)  # (Mloc, L)
    cval = cval * jnp.repeat(leaf_ok, OBJ_PER_LEAF, axis=1)

    if two_stage:
        inr = (
            (cx >= q_rects[:, 0:1]) & (cx <= q_rects[:, 2:3])
            & (cy >= q_rects[:, 1:2]) & (cy <= q_rects[:, 3:4])
            & (cval > 0)
        )
        cap = min(stage2_cap, inr.shape[1])
        val2, idx2 = jax.lax.top_k(inr.astype(jnp.int32), cap)  # (Mloc, cap)
        # map surviving candidate slots back to (leaf, slot) for a narrow gather
        leaf_of = jnp.repeat(top_leaf, OBJ_PER_LEAF, axis=1)  # (Mloc, C)
        slot_of = jnp.tile(jnp.arange(OBJ_PER_LEAF), (M, TOP_LEAVES_LOCAL))
        sel_leaf = jnp.take_along_axis(leaf_of, idx2, axis=1)
        sel_slot = jnp.take_along_axis(slot_of, idx2, axis=1)
        cbm2 = obj_bm[sel_leaf, sel_slot]  # (Mloc, cap, W): bitmaps of survivors only
        kw = jnp.any((cbm2 & q_bm[:, None, :]) != 0, axis=-1)
        match = (kw & (val2 > 0)).astype(jnp.int32)
        counts = jnp.sum(match, axis=1)
        overflow = jnp.maximum(jnp.sum(inr.astype(jnp.int32), axis=1) - cap, 0)
    else:
        cbm = obj_bm[top_leaf].reshape(M, -1, q_bm.shape[1])
        match = skr_verify_ref(q_rects, q_bm, cx, cy, cbm, cval)  # (Mloc, C) int8
        counts = jnp.sum(match.astype(jnp.int32), axis=1)
        overflow = jnp.zeros_like(counts)
    counts = jax.lax.psum(counts, "model")
    scanned = jax.lax.psum(jnp.sum(rel.astype(jnp.int32), axis=1), "model")
    overflow = jax.lax.psum(overflow, "model")
    return counts, scanned, overflow


def make_inputs(cfg: WiskServeConfig):
    """Abstract ``ShapeDtypeStruct`` inputs of the flat fallback step (for
    ``jit.lower`` dry-runs; host-only, nothing is allocated)."""
    W = cfg.vocab // 32
    sds = jax.ShapeDtypeStruct
    return dict(
        q_rects=sds((cfg.n_queries, 4), jnp.float32),
        q_bm=sds((cfg.n_queries, W), jnp.uint32),
        leaf_mbrs=sds((cfg.n_nodes, 4), jnp.float32),
        leaf_bm=sds((cfg.n_nodes, W), jnp.uint32),
        obj_x=sds((cfg.n_nodes, OBJ_PER_LEAF), jnp.float32),
        obj_y=sds((cfg.n_nodes, OBJ_PER_LEAF), jnp.float32),
        obj_bm=sds((cfg.n_nodes, OBJ_PER_LEAF, W), jnp.uint32),
        obj_valid=sds((cfg.n_nodes, OBJ_PER_LEAF), jnp.int8),
    )


def lower_wisk_serve(mesh: Mesh, cfg: WiskServeConfig = None, two_stage: bool = False):
    """Lower (never execute) the leaf-sharded fallback on ``mesh``: queries
    replicated over 'model', leaves + object blocks sharded, counts/scanned/
    overflow psum'd. Returns the jitted computation's ``Lowered`` handle --
    the dry-run surface for roofline/HLO inspection (host-only)."""
    cfg = cfg or WiskServeConfig()
    rules = default_rules(mesh)
    dp = dp_axes(mesh)
    qspec = spec_for(("query", None), rules)
    lspec = spec_for(("leaf", None), rules)
    ospec = spec_for(("leaf", "obj_slot", "word"), rules)
    in_specs = (qspec, qspec, lspec, lspec, lspec, lspec, ospec, lspec)
    out_specs = (P(dp), P(dp), P(dp))

    fn = shard_map(
        functools.partial(wisk_serve_step, two_stage=two_stage),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )
    inputs = make_inputs(cfg)
    shardings = dict(
        q_rects=NamedSharding(mesh, qspec),
        q_bm=NamedSharding(mesh, qspec),
        leaf_mbrs=NamedSharding(mesh, lspec),
        leaf_bm=NamedSharding(mesh, lspec),
        obj_x=NamedSharding(mesh, lspec),
        obj_y=NamedSharding(mesh, lspec),
        obj_bm=NamedSharding(mesh, ospec),
        obj_valid=NamedSharding(mesh, lspec),
    )
    order = list(inputs.keys())
    jitted = jax.jit(
        lambda *args: fn(*args),
        in_shardings=tuple(shardings[k] for k in order),
        out_shardings=tuple(NamedSharding(mesh, P(dp)) for _ in range(3)),
    )
    return jitted.lower(*[inputs[k] for k in order])
