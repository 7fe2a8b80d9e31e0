"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _rand_rects(rng, n):
    lo = rng.uniform(0, 0.8, (n, 2)).astype(np.float32)
    hi = lo + rng.uniform(0.01, 0.2, (n, 2)).astype(np.float32)
    return np.concatenate([lo, hi], axis=1)


@pytest.mark.parametrize("m,k,w", [(1, 1, 1), (7, 33, 3), (64, 128, 15), (130, 257, 16), (128, 128, 32)])
def test_skr_filter_sweep(m, k, w):
    rng = np.random.default_rng(m * 1000 + k + w)
    qr = _rand_rects(rng, m)
    nm = _rand_rects(rng, k)
    qb = (rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, w), dtype=np.uint32))
    nb = rng.integers(0, 2 ** 32, (k, w), dtype=np.uint32)
    out = np.asarray(ops.filter_pairs(qr, qb, nm, nb))
    exp = np.asarray(ref.skr_filter_ref(*map(jnp.asarray, (qr, qb, nm, nb))))
    np.testing.assert_array_equal(out, exp)


@pytest.mark.parametrize("m,c,w", [(1, 8, 1), (5, 100, 4), (16, 512, 15), (33, 1000, 8)])
def test_skr_verify_sweep(m, c, w):
    rng = np.random.default_rng(m + c + w)
    qr = _rand_rects(rng, m)
    qb = rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
    cx = rng.uniform(0, 1, (m, c)).astype(np.float32)
    cy = rng.uniform(0, 1, (m, c)).astype(np.float32)
    cb = (rng.integers(0, 2 ** 32, (m, c, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, c, w), dtype=np.uint32))
    cv = rng.integers(0, 2, (m, c)).astype(np.int8)
    out = np.asarray(ops.verify_candidates(qr, qb, cx, cy, cb, cv))
    exp = np.asarray(ref.skr_verify_ref(*map(jnp.asarray, (qr, qb, cx, cy, cb, cv))))
    np.testing.assert_array_equal(out, exp)


@pytest.mark.parametrize("n,b,h", [(1, 1, 16), (65, 23, 16), (301, 64, 16), (256, 130, 8)])
def test_cdf_mlp_sweep(n, b, h):
    rng = np.random.default_rng(n + b)
    params = {
        "w0": rng.normal(0, 1, (b, 1, h)), "b0": rng.normal(0, 1, (b, h)),
        "w1": rng.normal(0, 0.5, (b, h, h)), "b1": rng.normal(0, 0.5, (b, h)),
        "w2": rng.normal(0, 0.5, (b, h, h)), "b2": rng.normal(0, 0.5, (b, h)),
        "w3": rng.normal(0, 0.5, (b, h, 1)), "b3": rng.normal(0, 0.5, (b, 1)),
    }
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    out = np.asarray(ops.cdf_bank_forward(params, x))
    exp = np.asarray(ref.cdf_mlp_ref(params, x))
    np.testing.assert_allclose(out, exp, atol=2e-6)


@pytest.mark.parametrize(
    "m,f,w",
    [
        (1, 1, 1),  # degenerate single-slot frontier
        (5, 37, 3),  # nothing a multiple of the 128-lane tile
        (9, 130, 4),  # frontier just past one lane tile
        (33, 257, 8),  # queries and frontier both off-tile
        (8, 128, 16),  # exact tile for contrast
    ],
)
def test_frontier_filter_sweep(m, f, w):
    """Pallas frontier kernel (interpret) vs jnp oracle, incl. pad slots."""
    rng = np.random.default_rng(m * 7919 + f * 31 + w)
    qr = _rand_rects(rng, m)
    qb = (rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, w), dtype=np.uint32))
    fm = _rand_rects(rng, m * f).reshape(m, f, 4).astype(np.float32)
    fb = (rng.integers(0, 2 ** 32, (m, f, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, f, w), dtype=np.uint32))
    fv = rng.integers(0, 2, (m, f)).astype(np.int8)
    out = np.asarray(ops.filter_frontier(qr, qb, fm, fb, fv))
    exp = np.asarray(ref.frontier_filter_ref(*map(jnp.asarray, (qr, qb, fm, fb, fv))))
    np.testing.assert_array_equal(out, exp)


@pytest.mark.parametrize(
    "m,f,w",
    [
        (1, 1, 1),  # degenerate single-slot frontier
        (5, 37, 3),  # nothing a multiple of the 128-lane tile
        (9, 130, 4),  # frontier just past one lane tile
        (33, 257, 8),  # queries and frontier both off-tile
        (8, 128, 16),  # exact tile for contrast
    ],
)
def test_knn_filter_sweep(m, f, w):
    """Pallas kNN distance kernel (interpret) vs jnp oracle, incl. the +inf
    sentinel at invalid / keyword-miss slots and points inside MBRs (d=0)."""
    rng = np.random.default_rng(m * 613 + f * 17 + w)
    qp = rng.uniform(0, 1, (m, 2)).astype(np.float32)
    qb = (rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, w), dtype=np.uint32))
    fm = _rand_rects(rng, m * f).reshape(m, f, 4).astype(np.float32)
    fb = (rng.integers(0, 2 ** 32, (m, f, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, f, w), dtype=np.uint32))
    fv = rng.integers(0, 2, (m, f)).astype(np.int8)
    out = np.asarray(ops.knn_frontier_dist(qp, qb, fm, fb, fv))
    exp = np.asarray(ref.knn_filter_ref(*map(jnp.asarray, (qp, qb, fm, fb, fv))))
    # float kernel: +inf sentinel pattern must match exactly, finite
    # distances to float tolerance (FMA fusion may differ by 1 ULP)
    np.testing.assert_array_equal(np.isinf(out), np.isinf(exp))
    np.testing.assert_allclose(out[np.isfinite(out)], exp[np.isfinite(exp)], rtol=1e-6)
    assert np.isinf(out[(fv == 0)]).all()


def test_knn_filter_block_size_invariance():
    rng = np.random.default_rng(3)
    m, f, w = 21, 70, 5
    qp = rng.uniform(0, 1, (m, 2)).astype(np.float32)
    qb = rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
    fm = _rand_rects(rng, m * f).reshape(m, f, 4).astype(np.float32)
    fb = rng.integers(0, 2 ** 32, (m, f, w), dtype=np.uint32)
    fv = rng.integers(0, 2, (m, f)).astype(np.int8)
    a = np.asarray(ops.knn_frontier_dist(qp, qb, fm, fb, fv, bm=4, bf=16))
    b = np.asarray(ops.knn_frontier_dist(qp, qb, fm, fb, fv, bm=8, bf=128))
    np.testing.assert_array_equal(np.isinf(a), np.isinf(b))
    np.testing.assert_allclose(a[np.isfinite(a)], b[np.isfinite(b)], rtol=1e-6)


def test_frontier_filter_block_size_invariance():
    rng = np.random.default_rng(1)
    m, f, w = 21, 70, 5
    qr = _rand_rects(rng, m)
    qb = rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
    fm = _rand_rects(rng, m * f).reshape(m, f, 4).astype(np.float32)
    fb = rng.integers(0, 2 ** 32, (m, f, w), dtype=np.uint32)
    fv = rng.integers(0, 2, (m, f)).astype(np.int8)
    a = np.asarray(ops.filter_frontier(qr, qb, fm, fb, fv, bm=4, bf=16))
    b = np.asarray(ops.filter_frontier(qr, qb, fm, fb, fv, bm=8, bf=128))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("m,k,w", [(3, 5, 2), (127, 129, 7)])
def test_skr_filter_off_tile_padding(m, k, w):
    """skr_filter on shapes straddling the 128-lane tile boundary."""
    rng = np.random.default_rng(m + k * 13 + w)
    qr = _rand_rects(rng, m)
    nm = _rand_rects(rng, k)
    qb = rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
    nb = (rng.integers(0, 2 ** 32, (k, w), dtype=np.uint32)
          * rng.integers(0, 2, (k, w), dtype=np.uint32))
    out = np.asarray(ops.filter_pairs(qr, qb, nm, nb))
    exp = np.asarray(ref.skr_filter_ref(*map(jnp.asarray, (qr, qb, nm, nb))))
    np.testing.assert_array_equal(out, exp)


@pytest.mark.parametrize("m,c,w", [(2, 3, 1), (9, 513, 5)])
def test_skr_verify_off_tile_padding(m, c, w):
    """skr_verify on candidate widths just past the block size."""
    rng = np.random.default_rng(m * 3 + c + w)
    qr = _rand_rects(rng, m)
    qb = rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
    cx = rng.uniform(0, 1, (m, c)).astype(np.float32)
    cy = rng.uniform(0, 1, (m, c)).astype(np.float32)
    cb = rng.integers(0, 2 ** 32, (m, c, w), dtype=np.uint32)
    cv = rng.integers(0, 2, (m, c)).astype(np.int8)
    out = np.asarray(ops.verify_candidates(qr, qb, cx, cy, cb, cv))
    exp = np.asarray(ref.skr_verify_ref(*map(jnp.asarray, (qr, qb, cx, cy, cb, cv))))
    np.testing.assert_array_equal(out, exp)


def test_filter_block_size_invariance():
    rng = np.random.default_rng(0)
    m, k, w = 50, 90, 5
    qr = _rand_rects(rng, m)
    nm = _rand_rects(rng, k)
    qb = rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
    nb = rng.integers(0, 2 ** 32, (k, w), dtype=np.uint32)
    a = np.asarray(ops.filter_pairs(qr, qb, nm, nb, bm=16, bk=32))
    b = np.asarray(ops.filter_pairs(qr, qb, nm, nb, bm=128, bk=128))
    np.testing.assert_array_equal(a, b)


def _fused_operands(rng, m, t, k, obj, w):
    """Random fused-verify operands incl. out-of-range leaf ids and -1 pads."""
    qr = _rand_rects(rng, m)
    qb = (rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, w), dtype=np.uint32))
    tl = rng.integers(-1, k + 2, (m, t)).astype(np.int32)  # deliberately dirty
    ok = rng.integers(0, 2, (m, t)).astype(np.int8)
    ox = rng.uniform(0, 1, (k, obj)).astype(np.float32)
    oy = rng.uniform(0, 1, (k, obj)).astype(np.float32)
    ob = (rng.integers(0, 2 ** 32, (k, obj, w), dtype=np.uint32)
          * rng.integers(0, 2, (k, obj, w), dtype=np.uint32))
    oid = np.where(rng.integers(0, 4, (k, obj)) > 0,
                   rng.integers(0, 10 * k * obj, (k, obj)), -1).astype(np.int32)
    return qr, qb, tl, ok, ox, oy, ob, oid


@pytest.mark.parametrize(
    "m,t,k,obj,w",
    [
        (1, 1, 1, 1, 1),    # fully degenerate
        (5, 3, 9, 16, 3),   # nothing tile-aligned
        (9, 8, 36, 64, 15), # the fs-profile word width
        (33, 4, 17, 32, 8), # queries past the default bm tile
        (8, 16, 64, 8, 4),  # wide selection, narrow leaves
    ],
)
def test_fused_verify_sweep(m, t, k, obj, w):
    """Fused gather+verify kernel (interpret) vs jnp oracle: elementwise-
    identical ids (ordering included) and per-slot verified counts, under
    invalid slots, -1 object pads, and out-of-range leaf ids."""
    rng = np.random.default_rng(m * 7919 + t * 131 + k * 17 + obj + w)
    args = _fused_operands(rng, m, t, k, obj, w)
    ids, kwv = ops.fused_gather_verify(*args)
    eids, ekwv = ref.fused_verify_ref(*map(jnp.asarray, args))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(eids))
    np.testing.assert_array_equal(np.asarray(kwv), np.asarray(ekwv))


def test_fused_verify_block_size_invariance():
    """Object tiles that divide OBJ (8) and that force padding (16) give
    the same ids and counts, on both fused variants."""
    rng = np.random.default_rng(11)
    args = _fused_operands(rng, 21, 5, 12, 24, 5)
    a_ids, a_kwv = ops.fused_gather_verify(*args, bo=8, variant="vmem")
    b_ids, b_kwv = ops.fused_gather_verify(*args, bo=16, variant="prefetch")
    np.testing.assert_array_equal(np.asarray(a_ids), np.asarray(b_ids))
    np.testing.assert_array_equal(np.asarray(a_kwv), np.asarray(b_kwv))


def test_fused_verify_matches_unfused_gather_pipeline():
    """The fused kernel's contract with the engine: identical output to the
    host-side gather -> skr_verify pipeline it replaces (candidate order
    leaf-slot-major, -1 at non-matches)."""
    rng = np.random.default_rng(23)
    qr, qb, tl, ok, ox, oy, ob, oid = _fused_operands(rng, 10, 4, 8, 16, 4)
    m, t = tl.shape
    k, obj = ox.shape
    safe = np.clip(tl, 0, k - 1)
    cx = ox[safe].reshape(m, -1)
    cy = oy[safe].reshape(m, -1)
    cb = ob[safe].reshape(m, t * obj, -1)
    cid = oid[safe].reshape(m, -1)
    cval = ((cid >= 0) & np.repeat(ok > 0, obj, axis=1)).astype(np.int8)
    match = np.asarray(ops.verify_candidates(qr, qb, cx, cy, cb, cval))
    exp_ids = np.where(match > 0, cid, -1)
    ids, _ = ops.fused_gather_verify(qr, qb, tl, ok, ox, oy, ob, oid)
    np.testing.assert_array_equal(np.asarray(ids), exp_ids)

# ------------------------------------------------- narrow (bandwidth-lean) path

def _narrow_operands(rng, m, f, w):
    """Random narrow-descent operands: rank-coded MBR planes gathered at
    random frontier slots + packed query word planes (DESIGN.md §3.5)."""
    from repro.serve.snapshot import encode_mbr_planes

    n = max(2 * f, 4)
    lo = rng.uniform(0, 1, (n, 2)).astype(np.float32)
    mbrs = np.concatenate(
        [lo, lo + rng.uniform(0, 0.3, (n, 2)).astype(np.float32)], axis=1
    )
    codes, dicts_x, dicts_y = encode_mbr_planes([mbrs])
    n_bm = (rng.integers(0, 2 ** 32, (n, w), dtype=np.uint32)
            * rng.integers(0, 2, (n, w), dtype=np.uint32))
    qb = (rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
          * rng.integers(0, 2, (m, w), dtype=np.uint32))
    wids, bits = ops.pack_query_words(qb)
    wids = np.asarray(wids)
    idx = rng.integers(0, n, (m, f))
    f_codes = np.asarray(codes[0])[idx]
    f_bm = n_bm[idx[:, :, None], wids[:, None, :]]
    fv = rng.integers(0, 2, (m, f)).astype(np.int8)
    full = (qb, mbrs[idx], n_bm[idx])  # f32/full-width twins for cross-checks
    return bits, f_codes, f_bm, fv, dicts_x[0], dicts_y[0], full


@pytest.mark.parametrize(
    "m,f,w",
    [
        (1, 1, 1),   # degenerate single-slot frontier
        (5, 37, 3),  # nothing a multiple of the 128-lane tile
        (9, 130, 4),  # frontier just past one lane tile
        (33, 257, 8),  # queries and frontier both off-tile
        (8, 128, 15),  # the fs-profile word width
    ],
)
def test_frontier_filter_narrow_sweep(m, f, w):
    """Narrow frontier kernel (interpret) vs its jnp oracle AND the f32
    full-width reference: the rank-code/packed-word descent is lossless, so
    all three survivor masks must be bit-identical."""
    rng = np.random.default_rng(m * 7919 + f * 31 + w + 1)
    qr = _rand_rects(rng, m)
    bits, fc, fb, fv, dx, dy, (qb, fm_full, fb_full) = _narrow_operands(rng, m, f, w)
    out = np.asarray(ops.filter_frontier_narrow(qr, bits, fc, fb, fv, dx, dy))
    exp = np.asarray(ref.frontier_filter_narrow_ref(
        *map(jnp.asarray, (qr, bits, fc, fb, fv)), dx, dy))
    np.testing.assert_array_equal(out, exp)
    wide = np.asarray(ref.frontier_filter_ref(
        *map(jnp.asarray, (qr, qb, fm_full, fb_full, fv))))
    np.testing.assert_array_equal(out, wide)


@pytest.mark.parametrize(
    "m,f,w",
    [
        (1, 1, 1),
        (5, 37, 3),
        (9, 130, 4),
        (33, 257, 8),
        (8, 128, 15),
    ],
)
def test_knn_filter_narrow_sweep(m, f, w):
    """Narrow kNN distance kernel (interpret) vs oracle + f32 reference:
    identical +inf sentinel pattern, distances to float tolerance."""
    rng = np.random.default_rng(m * 613 + f * 17 + w + 1)
    qp = rng.uniform(0, 1, (m, 2)).astype(np.float32)
    bits, fc, fb, fv, dx, dy, (qb, fm_full, fb_full) = _narrow_operands(rng, m, f, w)
    out = np.asarray(ops.knn_frontier_dist_narrow(qp, bits, fc, fb, fv, dx, dy))
    exp = np.asarray(ref.knn_filter_narrow_ref(
        *map(jnp.asarray, (qp, bits, fc, fb, fv)), dx, dy))
    np.testing.assert_array_equal(np.isinf(out), np.isinf(exp))
    np.testing.assert_allclose(out[np.isfinite(out)], exp[np.isfinite(exp)], rtol=1e-6)
    wide = np.asarray(ref.knn_filter_ref(
        *map(jnp.asarray, (qp, qb, fm_full, fb_full, fv))))
    np.testing.assert_array_equal(np.isinf(out), np.isinf(wide))
    np.testing.assert_allclose(out[np.isfinite(out)], wide[np.isfinite(wide)], rtol=1e-6)


@pytest.mark.parametrize("m,w,seed", [(1, 1, 0), (7, 15, 1), (16, 15, 2), (5, 32, 3)])
def test_pack_query_words_properties(m, w, seed):
    """pack_query_words contracts: packed width a power-of-two bucket (or
    the full W when the bucket would exceed it), every nonzero word preserved
    at its original id, pad slots inert, and the AND-any keyword predicate
    invariant under packing."""
    rng = np.random.default_rng(seed)
    q = (rng.integers(0, 2 ** 32, (m, w), dtype=np.uint32)
         * rng.integers(0, 2, (m, w), dtype=np.uint32))
    wids, bits = ops.pack_query_words(q)
    wids, bits = np.asarray(wids), np.asarray(bits)
    wp = wids.shape[1]
    assert bits.shape == (m, wp) and wp <= w
    assert wp >= min(4, w)
    assert (wp & (wp - 1)) == 0 or wp == w  # power-of-two bucket, capped at W
    assert int((q != 0).sum(axis=1).max(initial=0)) <= wp  # nothing dropped
    for i in range(m):
        got = {(int(a), int(b)) for a, b in zip(wids[i], bits[i]) if b}
        want = {(int(j), int(q[i, j])) for j in range(w) if q[i, j]}
        assert got == want
    node = (rng.integers(0, 2 ** 32, (m, 6, w), dtype=np.uint32)
            * rng.integers(0, 2, (m, 6, w), dtype=np.uint32))
    full = np.any((node & q[:, None, :]) != 0, axis=-1)
    packed = np.any(
        (node[np.arange(m)[:, None, None], np.arange(6)[None, :, None],
              wids[:, None, :]] & bits[:, None, :]) != 0, axis=-1)
    np.testing.assert_array_equal(packed, full)


@pytest.mark.parametrize(
    "m,t,k,obj,w",
    [
        (1, 1, 1, 1, 1),    # fully degenerate
        (5, 3, 9, 16, 3),   # nothing tile-aligned
        (9, 8, 36, 64, 15), # the fs-profile word width
        (33, 4, 17, 32, 8), # queries past the default bm tile
    ],
)
def test_fused_verify_prefetch_sweep(m, t, k, obj, w):
    """Scalar-prefetched fused kernel (interpret) vs the same jnp oracle the
    VMEM variant is held to, under dirty leaf ids / -1 pads / invalid slots."""
    rng = np.random.default_rng(m * 7919 + t * 131 + k * 17 + obj + w + 1)
    args = _fused_operands(rng, m, t, k, obj, w)
    ids, kwv = ops.fused_gather_verify(*args, variant="prefetch")
    eids, ekwv = ref.fused_verify_ref(*map(jnp.asarray, args))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(eids))
    np.testing.assert_array_equal(np.asarray(kwv), np.asarray(ekwv))


def test_fused_verify_prefetch_equals_vmem():
    """The two fused variants are elementwise interchangeable -- the engine's
    auto-selection can never change results."""
    rng = np.random.default_rng(29)
    args = _fused_operands(rng, 13, 5, 11, 16, 6)
    v_ids, v_kwv = ops.fused_gather_verify(*args, variant="vmem")
    p_ids, p_kwv = ops.fused_gather_verify(*args, variant="prefetch")
    np.testing.assert_array_equal(np.asarray(v_ids), np.asarray(p_ids))
    np.testing.assert_array_equal(np.asarray(v_kwv), np.asarray(p_kwv))


def test_fused_verify_beyond_vmem_bank_stays_fused():
    """A leaf bank genuinely above FUSED_VMEM_BANK_BYTES: variant="auto"
    must resolve to the prefetch kernel (observed via monkeypatch counters)
    and still match the oracle bit-for-bit -- the no-fallback guarantee of
    DESIGN.md §3.5."""
    k, obj, w = 512, 256, 15
    assert ops.leaf_bank_bytes(k, obj, w) > ops.FUSED_VMEM_BANK_BYTES
    rng = np.random.default_rng(31)
    args = _fused_operands(rng, 4, 2, k, obj, w)
    calls = []
    import repro.kernels.ops as ops_mod

    real = ops_mod.fused_verify_prefetch
    try:
        ops_mod.fused_verify_prefetch = (
            lambda *a, **kw: calls.append("prefetch") or real(*a, **kw)
        )
        ids, kwv = ops.fused_gather_verify(*args, variant="auto")
    finally:
        ops_mod.fused_verify_prefetch = real
    assert calls == ["prefetch"], "auto picked the VMEM kernel above the cutoff"
    eids, ekwv = ref.fused_verify_ref(*map(jnp.asarray, args))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(eids))
    np.testing.assert_array_equal(np.asarray(kwv), np.asarray(ekwv))


def test_invalid_fused_variant_rejected():
    rng = np.random.default_rng(37)
    args = _fused_operands(rng, 2, 2, 4, 8, 2)
    with pytest.raises(ValueError, match="variant"):
        ops.fused_gather_verify(*args, variant="hbm")
