"""Roofline rows: dry-run LLM-arch summary + serving descent bytes moved.

Two row families (EXPERIMENTS.md section Roofline):

* ``roofline/<arch>/<shape>`` -- the launch/dryrun compute/memory/collective
  decomposition (full runs only; needs ``experiments/dryrun`` artifacts).
* ``roofline/descent/*`` -- the analytic bytes-moved model of the serving
  descent (repro.roofline.descent_bytes) priced on the SAME deterministic
  quick config and converged frontier widths as bench_serving's quick A/Bs.
  The ``bytes=`` counters are exact ints diffed deterministically by
  tools/bench_compare.py; the legacy/narrow ratio row is the scoreboard
  evidence for the >=2x descent-bytes reduction of DESIGN.md §3.5, and the
  verify-compact row for the >=2x leaf-verify reduction of the leaf-local
  vocabulary bank (both asserted here so a regression fails the benchmark,
  not just the diff). ``leaf-vocab`` carries the per-leaf word-count
  distribution (wl_max / wl_p50 / wl_p95 / overflow_leaves) the compact
  pricing rests on.
"""
from pathlib import Path

import numpy as np

from . import common as C


def _descent_rows(rows):
    from repro.data.workloads import make_workload
    from repro.kernels import ops
    from repro.roofline import descent_bytes as DB
    from repro.serve.engine import retrieve_workload

    from .bench_serving import SWEEP_M, quick_snapshot

    ds, snap, max_leaves = quick_snapshot()
    test = make_workload(ds, m=SWEEP_M, dist="MIX", seed=7)
    out = retrieve_workload(snap, test, max_leaves=max_leaves)
    widths = [int(w) for w in out["frontier_widths"]]
    M = test.m
    W = snap.n_words
    OBJ = snap.obj_per_leaf
    K = snap.n_leaves
    T = int(np.asarray(out["ids"]).shape[1]) // OBJ
    wids, _ = ops.pack_query_words(np.asarray(test.kw_bitmap))
    Wp = int(wids.shape[1])
    dict_sizes = [
        (int(dx.size), int(dy.size))
        for dx, dy in zip(snap.level_dict_x, snap.level_dict_y)
    ]
    bank = ops.leaf_bank_bytes(K, OBJ, W)
    auto = ops.pick_fused_variant(K, OBJ, W, compact=False)

    legacy_f = DB.descent_bytes(M, widths, W)
    narrow_f = DB.descent_bytes(
        M, widths, W, narrow=True, packed_words=Wp, dict_sizes=dict_sizes
    )
    rows.append(C.row(
        "roofline/descent/filter-legacy", 0.0,
        f"bytes={legacy_f.total} ms={legacy_f.total_ms:.4f} widths=[{','.join(map(str, widths))}]"))
    rows.append(C.row(
        "roofline/descent/filter-narrow", 0.0,
        f"bytes={narrow_f.total} ms={narrow_f.total_ms:.4f} wp={Wp}"))
    for variant in ("unfused", "vmem", "prefetch"):
        vb = DB.verify_bytes(M, T, OBJ, W, K, variant)
        rows.append(C.row(
            f"roofline/descent/verify-{variant}", 0.0,
            f"bytes={vb} ms={DB.modeled_ms(vb):.4f}"))
    rows.append(C.row(
        "roofline/descent/bank", 0.0,
        f"bytes={bank} cutoff={ops.FUSED_VMEM_BANK_BYTES} auto={auto}"))

    # leaf-local vocabulary bank (DESIGN.md §3.5): per-leaf word-count
    # distribution + compact verify pricing on the auto-selected variant
    from repro.serve.snapshot import LEAF_DICT_MAX

    obm = np.asarray(snap.leaf_obj_bm)
    shifts = np.arange(32, dtype=np.uint32)
    vocab = (
        (np.bitwise_or.reduce(obm, axis=1)[:, :, None] >> shifts) & 1
    ).sum(axis=(1, 2)).astype(np.int64)
    wl_leaf = np.maximum(-(-vocab // 32), 1)
    overflow = int(np.sum(vocab > LEAF_DICT_MAX))
    assert snap.has_compact_bank, "quick config must keep the compact bank"
    Wl = snap.n_compact_words
    rows.append(C.row(
        "roofline/descent/leaf-vocab", 0.0,
        f"wl={Wl} wl_max={int(wl_leaf.max())} "
        f"wl_p50={int(np.percentile(wl_leaf, 50))} "
        f"wl_p95={int(np.percentile(wl_leaf, 95))} "
        f"overflow_leaves={overflow}"))
    cbank = ops.compact_leaf_bank_bytes(K, OBJ, Wl)
    cauto = ops.pick_fused_variant(K, OBJ, Wl, compact=True)
    cvb = DB.verify_bytes(M, T, OBJ, W, K, cauto, compact_words=Wl)
    rows.append(C.row(
        "roofline/descent/verify-compact", 0.0,
        f"bytes={cvb} ms={DB.modeled_ms(cvb):.4f} variant={cauto}"))
    rows.append(C.row(
        "roofline/descent/bank-compact", 0.0,
        f"bytes={cbank} cutoff={ops.FUSED_VMEM_BANK_BYTES} auto={cauto}"))
    vmem_vb = DB.verify_bytes(M, T, OBJ, W, K, "vmem")
    assert vmem_vb >= 2 * cvb, (
        f"modeled compact-verify reduction fell below 2x vs verify-vmem: "
        f"{vmem_vb / max(cvb, 1):.2f}x"
    )

    # end-to-end before/after: the seed path (f32 planes + unfused verify)
    # vs the shipping path (narrow planes + compact bank on the auto variant)
    before = DB.descent_bytes(
        M, widths, W, t=T, obj_per_leaf=OBJ, n_leaves=K,
        verify_variant="unfused")
    after = DB.descent_bytes(
        M, widths, W, narrow=True, packed_words=Wp, dict_sizes=dict_sizes,
        t=T, obj_per_leaf=OBJ, n_leaves=K, verify_variant=cauto,
        compact_words=Wl)
    cmp = DB.compare(before, after)
    rows.append(C.row(
        "roofline/descent/total-before", 0.0,
        f"bytes={before.total} ms={before.total_ms:.4f}"))
    rows.append(C.row(
        "roofline/descent/total-after", 0.0,
        f"bytes={after.total} ms={after.total_ms:.4f}"))
    rows.append(C.row(
        "roofline/descent/reduction", 0.0,
        f"ratio={cmp['ratio']:.2f}x filter_ratio="
        f"{legacy_f.total / max(narrow_f.total, 1):.2f}x"))
    assert cmp["ratio"] >= 2.0, (
        f"modeled descent-bytes reduction fell below 2x: {cmp['ratio']:.2f}x"
    )
    return rows


def run_quick():
    """CI lane: descent bytes only (the dryrun artifacts are full-run)."""
    return _descent_rows([])


def run():
    rows = []
    d = Path("experiments/dryrun")
    if not d.exists():
        rows.append(C.row("roofline/missing", 0.0, "run launch/dryrun first"))
    else:
        from repro.roofline.analysis import load_rows

        for r in load_rows(str(d)):
            rows.append(C.row(
                f"roofline/{r.arch}/{r.shape}", 0.0,
                f"compute_ms={r.compute_s*1e3:.2f};memory_ms={r.memory_s*1e3:.2f};"
                f"collective_ms={r.collective_s*1e3:.2f};bound={r.bottleneck};useful={r.useful_ratio:.2f}"))
    return _descent_rows(rows)
