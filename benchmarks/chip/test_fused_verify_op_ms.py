"""Checks of ``metrics/fused_verify_op_ms.py``, the reader that finds the
fused gather+verify kernel by its operation's name.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/test_fused_verify_op_ms.py -q

Run by hand, like the other files beside the benchmark: a hand-made
timeline whose answer is known, and the trace recorded on a v5e chip in
``fixtures/``, where the kernel still ran as its own program: there the
operation's time lies inside the program's, which also holds a ~0.5 ms
copy before the kernel.
"""
import gzip
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))

import trace_reduce as tr  # noqa: E402

FIXTURE = CHIP_DIR / "fixtures" / "osm_live_mix_trace.json.gz"
MS = 1_000_000


def _module(name):
    spec = importlib.util.spec_from_file_location(name, CHIP_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name, want", [
    ("%fused_verify_prefetch_compact.1", True),
    ("fused_verify_compact.12", True),
    ("%fused_verify = s32[64,1024] custom-call(%a)", True),
    ("fused_verify_prefetch", True),
    ("%fusion.3", False),
    ("%skr_verify_compact.1", False),
    ("%fused_verify_stage.1", False),
    ("jit_fused_verify_compact(12)", False),
    ("", False),
])
def test_kernel_names(name, want):
    assert _module("fused_verify_op_ms").is_kernel(name) is want


def test_reads_the_kernel_inside_a_larger_program():
    # one compiled stage per SKR call holds the kernel; two calls, the
    # second also on a second chip whose kernel runs 2 ms longer
    ops = {
        "/device:TPU:0": [
            ("%select_fusion.2", 10 * MS, 11 * MS),
            ("%fused_verify_compact.1", 11 * MS, 15 * MS),
            ("%fused_verify_compact.1", 31 * MS, 37 * MS),
            ("%skr_verify_compact.1", 37 * MS, 38 * MS),
        ],
        "/device:TPU:1": [
            ("%fused_verify_compact.1", 11 * MS, 15 * MS),
            ("%fused_verify_compact.1", 31 * MS, 39 * MS),
        ],
    }
    spans = [("window", 0, 100 * MS), ("serve_skr", 5 * MS, 20 * MS),
             ("serve_skr", 30 * MS, 45 * MS), ("serve_knn", 50 * MS, 60 * MS)]
    modules = {p: [("jit__verify_stage(4)", 10 * MS, 40 * MS)] for p in ops}
    run = SimpleNamespace(trace=tr.reduce(tr.Events(ops, spans, modules)))
    # per call, averaged over the chips: 4 ms and 7 ms; median 5.5
    assert _module("fused_verify_op_ms").read(run) == pytest.approx(5.5)
    assert _module("fused_verify_ms").read(run) is None


def test_reads_nothing_without_the_kernel_or_its_spans():
    read = _module("fused_verify_op_ms").read
    r = tr.reduce(tr.Events({"/device:TPU:0": [("%fusion.1", 1, 2)]},
                            [("window", 0, 10), ("serve_skr", 0, 5)]))
    assert read(SimpleNamespace(trace=r)) is None
    r = tr.reduce(tr.Events({"/device:TPU:0": [("%fused_verify.1", 1, 2)]}, [("window", 0, 10)]))
    assert read(SimpleNamespace(trace=r)) is None
    assert read(SimpleNamespace(trace=None)) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded chip trace")
def test_recorded_chip_trace_agrees_with_the_program_reader():
    raw = json.loads(gzip.decompress(FIXTURE.read_bytes()))
    ev = tr.Events({k: [tuple(e) for e in v] for k, v in raw["ops"].items()},
                   [tuple(e) for e in raw["spans"]],
                   {k: [tuple(e) for e in v] for k, v in raw["modules"].items()})
    run = SimpleNamespace(trace=tr.reduce(ev))
    by_op = _module("fused_verify_op_ms").read(run)
    by_program = _module("fused_verify_ms").read(run)
    assert by_op is not None and 0 < by_op < by_program
    # the kernel's operations summed per serve_skr call, median over calls
    ops = raw["ops"]["/device:TPU:0"]
    per_call = sorted(
        sum(e - s for n, s, e in ops if n.startswith("%fused_verify") and t0 <= s and e <= t1)
        for name, t0, t1 in raw["spans"] if name == "serve_skr"
    )
    calls_with_kernel = [ns for ns in per_call if ns]
    assert calls_with_kernel
    assert by_op == pytest.approx(float(np.median(per_call)) * 1e-6, rel=1e-9)
