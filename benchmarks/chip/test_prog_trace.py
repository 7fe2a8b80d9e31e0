"""Checks of ``prog_trace`` (the program's spans on the trace's clock) and the
per-layer readers built on it.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/test_prog_trace.py -q

Run by hand, like the other files that sit with the benchmark. A hand-made
timeline: two ``serve_skr`` calls, one ``serve_knn`` call and one update,
with the loop's spans and the device's operations on the trace's clock and
the program's log on a clock ``SHIFT`` ns away. Every reader's answer is
known from the timeline's numbers.
"""
import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))

import prog_trace  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000
US = 1_000
SHIFT = 609 * 1000 * MS  # the program's clock minus the trace's
LAG = 10 * US  # from a loop span's start to the program span inside it
SKR_CALLS = (10 * MS, 60 * MS)
MIB = 2**20


def _skr_call(b, batch):
    """One SKR call at trace time ``b``: its program spans and counter
    events (trace clock), and the device's busy intervals inside it."""
    spans = [
        ("wisk.serve", b + LAG, b + 40 * MS - LAG, 0, batch),
        ("wisk.prep", b + MS // 10, b + 21 * MS // 10, 1, batch),  # 2 ms, no device
        ("wisk.descend", b + 21 * MS // 10, b + 101 * MS // 10, 1, batch),  # 8 ms, 2 busy
        ("wisk.sync", b + 101 * MS // 10, b + 201 * MS // 10, 1, batch),  # 10 ms, 6 busy
        ("wisk.verify", b + 201 * MS // 10, b + 251 * MS // 10, 1, batch),  # 5 ms, 1 busy
        ("wisk.fetch", b + 251 * MS // 10, b + 351 * MS // 10, 1, batch),  # 10 ms, 4 busy
        ("wisk.observe", b + 351 * MS // 10, b + 361 * MS // 10, 1, batch),
    ]
    counts = [("skr.rows", b + MS, 20), ("skr.pad_rows", b + MS, 12),
              ("skr.d2h_bytes", b + 35 * MS, 64 * MIB)]
    busy = [(b + 4 * MS, b + 6 * MS), (b + 12 * MS, b + 18 * MS),
            (b + 21 * MS, b + 22 * MS), (b + 26 * MS, b + 30 * MS)]
    return spans, counts, busy


def _timeline():
    spans, counts, busy = [], [], []
    for batch, b in enumerate(SKR_CALLS):
        s, c, d = _skr_call(b, batch)
        spans += s
        counts += c
        busy += d
    spans += [
        ("wisk.serve_knn", 110 * MS + LAG, 150 * MS - LAG, 0, 2),
        ("wisk.insert", 160 * MS + LAG, 170 * MS - LAG, 0, 3),
        ("wisk.delta_insert", 160 * MS + MS // 10, 163 * MS + MS // 10, 1, 3),  # 3 ms
        ("wisk.geofence_match", 163 * MS + MS // 10, 165 * MS + MS // 10, 1, 3),  # 2 ms
        ("wisk.drain", 168 * MS, 169 * MS, 0, 4),
    ]
    counts += [("knn.chunks", 115 * MS, 8), ("knn.live_chunks", 115 * MS, 2)]
    busy += [(120 * MS, 140 * MS)]
    loop = [("window", 0, 200 * MS), ("serve_knn", 110 * MS, 150 * MS), ("update", 160 * MS, 170 * MS)]
    loop += [("serve_skr", b, b + 40 * MS) for b in SKR_CALLS]
    ops = {"/device:TPU:0": [(f"fusion.{i}", s, e) for i, (s, e) in enumerate(busy)]}
    reduction = tr.reduce(tr.Events(ops, loop, {}))
    return reduction, spans, counts


def _program_log(spans, counts):
    """The program's view: every time on its own clock, ``SHIFT`` away."""
    return ([(n, t0 + SHIFT, t1 + SHIFT, d, b) for n, t0, t1, d, b in spans]
            + [(n, t + SHIFT, k) for n, t, k in counts])


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, CHIP_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_a_known_offset_is_recovered():
    reduction, spans, counts = _timeline()
    pt = prog_trace.align(reduction.spans, _program_log(spans, counts))
    assert pt.offset_ns == -SHIFT - LAG and pt.spread_ns == 0
    assert pt.spans["wisk.prep"][0, 0] == SKR_CALLS[0] + MS // 10 - LAG
    assert pt.counts["knn.chunks"][0].tolist() == [115 * MS - LAG, 8]


def test_mismatched_pair_counts_give_none():
    reduction, spans, counts = _timeline()
    spans = [s for s in spans if not (s[0] == "wisk.serve" and s[4] == 1)]
    assert prog_trace.align(reduction.spans, _program_log(spans, counts)) is None


def test_a_wide_offset_spread_gives_none():
    reduction, spans, counts = _timeline()
    late = {0, 2}  # two of the four paired calls sit 1 ms later on the program's clock
    spans = [(n, t0 + MS, t1 + MS, d, b) if b in late else (n, t0, t1, d, b)
             for n, t0, t1, d, b in spans]
    assert prog_trace.align(reduction.spans, _program_log(spans, counts)) is None


READINGS = {
    "skr_prep_ms": 2.0,
    "skr_dispatch_ms": (8 - 2) + (5 - 1),
    "skr_sync_ms": 10 - 6,
    "skr_id_copy_ms": 10 - 4,
    "skr_pad_pct": 100 * 24 / 64,
    "skr_d2h_mb": 64.0,
    "knn_host_ms": (40 - 2 * LAG / MS) - 20,
    "knn_live_chunk_pct": 25.0,
    "delta_insert_ms": 3.0,
    "geofence_match_ms": 2.0,
}


@pytest.fixture
def run(monkeypatch):
    reduction, spans, counts = _timeline()
    monkeypatch.setattr(prog_trace, "_program_log", lambda: _program_log(spans, counts))
    return SimpleNamespace(trace=reduction)


@pytest.mark.parametrize("name", sorted(READINGS))
def test_readers_on_the_timeline(run, name):
    assert _reader(name)(run) == pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(READINGS))
def test_readers_without_the_program_record_read_nothing(monkeypatch, name):
    reduction, _, _ = _timeline()
    monkeypatch.setattr(prog_trace, "_program_log", lambda: None)
    assert _reader(name)(SimpleNamespace(trace=reduction)) is None
    assert _reader(name)(SimpleNamespace(trace=None)) is None
