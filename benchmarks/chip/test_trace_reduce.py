"""Checks of the trace reduction and the per-layer readers built on it.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/test_trace_reduce.py -q

Run by hand: these files sit with the benchmark, not in the repo's test
suite. Three sources of events: a hand-made timeline whose answers are
known, a small trace recorded on a v5e chip (``fixtures/``: the events of
the first second of an ``osm-live-mix`` window, kept as JSON), and a trace
this process records on the CPU, for the ``.xplane.pb`` reader.
"""
import gzip
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))

import trace_reduce as tr  # noqa: E402

FIXTURE = CHIP_DIR / "fixtures" / "osm_live_mix_trace.json.gz"
MS = 1_000_000


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, CHIP_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(reduction):
    return SimpleNamespace(trace=reduction)


def _hand_made():
    # window 0..100 ms; two chips' worth of ops on one plane, overlapping
    ops = {"/device:TPU:0": [
        ("fusion.1", 10 * MS, 20 * MS),
        ("fusion.2", 15 * MS, 25 * MS),  # overlaps fusion.1: busy 10..25
        ("custom-call.3", 60 * MS, 70 * MS),
        ("fusion.4", 95 * MS, 110 * MS),  # runs past the window's end
    ]}
    spans = [
        ("window", 0, 100 * MS),
        ("serve_skr", 5 * MS, 40 * MS),
        ("idle", 40 * MS, 55 * MS),
        ("serve_knn", 55 * MS, 90 * MS),
    ]
    modules = {"/device:TPU:0": [
        ("jit_fused_verify_prefetch_compact(123)", 10 * MS, 20 * MS),
        ("jit__filter_frontier_level(77)", 15 * MS, 25 * MS),
        ("jit__knn_leaf_phase(9)", 60 * MS, 70 * MS),
        ("jit__knn_leaf_phase(10)", 95 * MS, 110 * MS),
    ]}
    return tr.reduce(tr.Events(ops, spans, modules))


def test_busy_is_the_union_of_op_intervals():
    r = _hand_made()
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.015 + 0.010 + 0.005)
    assert r.busy_in([5 * MS, 55 * MS], [40 * MS, 90 * MS]) == pytest.approx([0.015, 0.010])


def test_gaps_are_labelled_by_the_covering_span():
    r = _hand_made()
    # gaps: 0-10 (skr covers 5 of 10), 25-60 (skr 15, idle 15, knn 5 -> first max: idle
    # sorts before serve_skr, so idle), 70-95 (knn 20 of 25)
    assert sum(r.gap_seconds.values()) + r.busy_s == pytest.approx(r.window_s)
    assert r.gap_seconds["serve_skr"] == pytest.approx(0.010)
    assert r.gap_seconds["idle"] == pytest.approx(0.035)
    assert r.gap_seconds["serve_knn"] == pytest.approx(0.025)


def test_readers_on_hand_made_timeline():
    run = _run(_hand_made())
    assert _reader("skr_device_ms")(run) == pytest.approx(15.0)
    assert _reader("skr_host_ms")(run) == pytest.approx(35.0 - 15.0)
    assert _reader("knn_device_ms")(run) == pytest.approx(10.0)
    assert _reader("device_idle_pct")(run) == pytest.approx(70.0)
    assert _reader("fused_verify_ms")(run) == pytest.approx(10.0)
    bd = run.trace.breakdown()
    assert bd["device_ops"][0] == ["jit__knn_leaf_phase", pytest.approx(0.015)]
    assert [n for n, _ in bd["device_ops"]][1:] == [
        "jit_fused_verify_prefetch_compact", "jit__filter_frontier_level"]
    assert len(bd["idle_gaps"]) == 3


def test_readers_return_nothing_without_their_spans():
    r = tr.reduce(tr.Events({"/device:TPU:0": [("op", 1, 2)]}, [("window", 0, 10)]))
    for name in ("skr_device_ms", "skr_host_ms", "knn_device_ms", "fused_verify_ms"):
        assert _reader(name)(_run(r)) is None
    assert _reader("skr_device_ms")(_run(None)) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded chip trace")
def test_recorded_chip_trace():
    raw = json.loads(gzip.decompress(FIXTURE.read_bytes()))
    ev = tr.Events({k: [tuple(e) for e in v] for k, v in raw["ops"].items()},
                   [tuple(e) for e in raw["spans"]],
                   {k: [tuple(e) for e in v] for k, v in raw["modules"].items()})
    r = tr.reduce(ev)
    assert 0 < r.busy_s <= r.window_s
    assert sum(r.gap_seconds.values()) + r.busy_s == pytest.approx(r.window_s, rel=1e-9)
    run = _run(r)
    assert set(raw["expect"]) == {"skr_device_ms", "skr_host_ms", "knn_device_ms",
                                  "fused_verify_ms", "device_idle_pct"}
    for name, want in raw["expect"].items():
        assert _reader(name)(run) == pytest.approx(want, rel=1e-9), name


def test_xplane_reader_finds_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(1024)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # as run.py traces
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("serve_skr"):
                f(x).block_until_ready()
    paths = sorted(tmp_path.rglob("*.xplane.pb"))
    ev = tr.read_xplane(paths[-1])
    names = sorted({n for n, _, _ in ev.spans})
    assert names == ["serve_skr", "window"]
    r = tr.reduce(ev)
    assert r.busy == {} and r.busy_s == 0.0 and r.gap_seconds == {}  # no TPU plane
    assert r.window_s > 0
    assert _reader("device_idle_pct")(_run(r)) is None
