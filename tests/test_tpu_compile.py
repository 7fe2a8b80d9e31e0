"""The serving main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: in-kernel vector gathers, int8 compares, blocks off the (8, 128)
tile, more VMEM than a kernel may use. These tests compile each kernel the
``LiveIndex`` serving path reaches with its default engine switches, for a
described (not attached) v5e chip, at the widths ``chip_smoke.py`` serves:
the ``osm`` profile (8,192 terms = 256 bitmap words) built with the
benchmark's bounded settings -- 32 leaves padded to 16,384 objects, a
32-word leaf-local vocabulary, 4 packed query words, frontier widths up to
32 and coordinate dictionaries under 64 entries, 64-query batches; a live
delta's 32 insert slots per leaf; 256 arriving objects against 32
geofences. The resident (VMEM) fused kernels compile at a bank just under
``ops.FUSED_VMEM_BANK_BYTES``.

The topology is described inside a fixture (never while a module is
imported), and the persistent compilation cache is off around the
compiles: an entry written for a described chip cannot be read back here.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import frontier, fused_verify, knn_filter, ops, skr_verify, sub_match

M, T, K, OBJ = 64, 32, 32, 16384  # batch, leaf slots, leaves, objects per leaf
W, WL, WP, F, DICT = 256, 32, 4, 32, 64  # words, compact words, packed words, frontier, dict
B, N_ARRIVE, WP_ARRIVE, S = 32, 256, 8, 32  # delta slots, arrivals, their packed words, geofences


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; the program must hold a
    Mosaic kernel (not an interpret-mode lowering)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


f32, u32, i32, i16, i8 = jnp.float32, jnp.uint32, jnp.int32, jnp.int16, jnp.int8


def _filter_shapes(first, words, narrow):
    mbrs = ((M, F, 4), i16) if narrow else ((M, F, 4), f32)
    tail = [((DICT,), f32), ((DICT,), f32)] if narrow else []
    return [first, ((M, words), u32), mbrs, ((M, F, words), u32), ((M, F), i8), *tail]


def _fused_shapes(k, obj, words, compact):
    q = [((M, T, words), u32), ((M, T), u32)] if compact else [((M, words), u32)]
    sig = [((k, obj), u32)] if compact else []
    return [((M, 4), f32), *q, ((M, T), i32), ((M, T), i8),
            ((k, obj), f32), ((k, obj), f32), ((k, obj, words), u32), *sig, ((k, obj), i32)]


def _bank_under_cutoff(words, compact, obj=512):
    """The widest (K, obj) bank whose resident VMEM footprint stays within
    the cutoff -- and within 10% of it, so the compile tests the limit."""
    rows = 4 if compact else 3
    k = 1
    while ops.resident_bank_vmem_bytes(k + 1, obj, words, rows) <= ops.FUSED_VMEM_BANK_BYTES:
        k += 1
    assert ops.resident_bank_vmem_bytes(k, obj, words, rows) > 0.9 * ops.FUSED_VMEM_BANK_BYTES
    return k, obj


def _cases():
    kv, ov = _bank_under_cutoff(W, compact=False)
    kc, oc = _bank_under_cutoff(WL, compact=True)
    return {
        "frontier_filter_narrow": (
            lambda *a: frontier.frontier_filter_narrow(*a, interpret=False),
            _filter_shapes(((M, 4), f32), WP, narrow=True)),
        "frontier_filter": (
            lambda *a: frontier.frontier_filter(*a, interpret=False),
            _filter_shapes(((M, 4), f32), W, narrow=False)),
        "knn_filter_narrow": (
            lambda *a: knn_filter.knn_filter_narrow(*a, interpret=False),
            _filter_shapes(((M, 2), f32), WP, narrow=True)),
        "knn_filter": (
            lambda *a: knn_filter.knn_filter(*a, interpret=False),
            _filter_shapes(((M, 2), f32), W, narrow=False)),
        "fused_verify_prefetch_compact": (
            lambda *a: fused_verify.fused_verify_prefetch_compact(*a, interpret=False),
            _fused_shapes(K, OBJ, WL, compact=True)),
        "fused_verify_prefetch": (
            lambda *a: fused_verify.fused_verify_prefetch(*a, interpret=False),
            _fused_shapes(K, OBJ, W, compact=False)),
        "fused_verify_compact_at_vmem_cutoff": (
            lambda *a: fused_verify.fused_verify_compact(*a, interpret=False),
            _fused_shapes(kc, oc, WL, compact=True)),
        "fused_verify_at_vmem_cutoff": (
            lambda *a: fused_verify.fused_verify(*a, interpret=False),
            _fused_shapes(kv, ov, W, compact=False)),
        "skr_verify": (
            lambda *a: skr_verify.skr_verify(*a, interpret=False),
            [((M, 4), f32), ((M, W), u32), ((M, T * B), f32), ((M, T * B), f32),
             ((M, T * B, W), u32), ((M, T * B), i8)]),
        "skr_verify_compact": (
            lambda *a: skr_verify.skr_verify_compact(*a, interpret=False),
            [((M, 4), f32), ((M, T, WL), u32), ((M, T), u32), ((M, T * B), f32),
             ((M, T * B), f32), ((M, T * B, WL), u32), ((M, T * B), u32), ((M, T * B), i8)]),
        "sub_match": (
            lambda *a: sub_match.sub_match(*a, interpret=False),
            [((N_ARRIVE, 2), f32), ((N_ARRIVE, WP_ARRIVE), i32), ((N_ARRIVE, WP_ARRIVE), u32),
             ((N_ARRIVE, 1), u32), ((S, 4), f32), ((S, W), u32), ((S, 1), u32)]),
    }


@pytest.mark.parametrize("name", list(_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _cases()[name]
    _compile(fn, one_chip, *shapes)


def test_wrappers_compile_on_tpu_backend(monkeypatch):
    """On a TPU backend the ops wrappers never choose interpret mode."""
    assert ops._interpret() == (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False


@pytest.mark.parametrize("live_delta", [False, True])
def test_verify_stage_compiles_for_v5e(one_chip, monkeypatch, live_delta):
    """The engine's whole single-device verify stage (``_verify_stage``: leaf
    selection, query-word remap, fused compact kernel and, with a live delta
    after the compact fallback, the full-width delta-slot verify and merge)
    compiles as one program for v5e at the serving widths."""
    from repro.serve import engine
    from repro.serve.delta import DeltaBuffer

    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bank = engine._LeafBank(
        s((K, OBJ), f32), s((K, OBJ), f32), s((K, OBJ), i32), s((K, OBJ, W), u32),
        (s((K, 32 * WL), i32), s((K, OBJ, WL), u32), s((K, OBJ), u32)),
    )
    delta = None
    if live_delta:
        delta = DeltaBuffer(
            aug_mbrs=[s((K, 4), f32)], aug_bms=[s((K, W), u32)],
            ins_x=s((K, B), f32), ins_y=s((K, B), f32), ins_bm=s((K, B, W), u32),
            ins_id=s((K, B), i32), base_alive=s((K, OBJ), i8), slots_per_leaf=B,
        )
    compiled = engine._verify_stage.lower(
        bank, s((M, 4), f32), s((M, W), u32), s((M, F), i32), s((M, F), i8), delta,
        take=T, n_leaf=K, fused=True, variant="auto",
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == (2 if live_delta else 1)
