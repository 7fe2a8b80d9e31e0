"""The stall watch samples a stalled pass's stack and never crashes the run.

    python -m pytest benchmarks/chip/test_watch.py -q

Run by hand (seconds, CPU).
"""
import gc
import json
import os
import subprocess
import sys
import time
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))

from watch import Watch  # noqa: E402

PASSES = 20

# Passes of eager JAX dispatch, each longer than the watch's stall_s: the
# loop thread pushes and pops frames all the while that its stack is read.
STALLING_LOOP = f"""
import json, sys, time
sys.path.insert(0, {str(CHIP_DIR)!r})
import jax
import numpy as np
from watch import Watch

def planted_eager_dispatch(x, seconds):
    t_end = time.perf_counter() + seconds
    total = 0
    while time.perf_counter() < t_end:
        total += int(np.asarray(jax.lax.slice(x, (0,), (64,))).sum())
    return total

x = jax.numpy.arange(4096)
planted_eager_dispatch(x, 0.0)
with Watch(stall_s=0.05) as w:
    for _ in range({PASSES}):
        w.arm()
        planted_eager_dispatch(x, 0.2)
        w.disarm()
print(w.stack_report(100_000))
print(json.dumps(dict(samples=w.samples, passes=len({{s.pass_at_s for s in w.stacks}}),
                      named=sum(s.count for s in w.stacks if "planted_eager_dispatch" in s.stack))))
"""


def test_stalled_passes_of_eager_dispatch_are_sampled_and_the_run_lives():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", STALLING_LOOP], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["passes"] >= PASSES // 2, p.stdout[-2000:]
    assert got["named"] >= PASSES // 2, p.stdout[-2000:]
    assert p.stdout.startswith("stall_stacks: "), p.stdout[:200]
    assert "in planted_eager_dispatch" in p.stdout


def test_a_pass_shorter_than_stall_s_leaves_no_samples():
    with Watch(stall_s=0.5) as w:
        for _ in range(5):
            w.arm()
            time.sleep(0.05)
            w.disarm()
            time.sleep(0.05)
    assert not w._thread.is_alive()
    assert (w.samples, w.stacks, w.stack_report(1000)) == (0, [], "")


def test_a_sample_held_off_by_the_interpreter_lock_reads_late():
    with Watch(stall_s=0.05) as w:
        w.arm()
        t0 = time.perf_counter()
        sum(range(30_000_000))  # a native call that keeps the lock
        held = time.perf_counter() - t0
        time.sleep(0.3)
        w.disarm()
    assert w.stacks, "no sample of a 0.3 s wait after the stall began"
    assert w.stacks[0].late_s >= 0.5 * (held - 0.05), (held, w.stacks[0])
    assert "test_a_sample_held_off_by_the_interpreter_lock_reads_late" in w.stacks[0].stack


def test_stalls_and_gc_summary_keep_their_keys():
    with Watch(stall_s=0.5) as w:
        gc.collect()
    calls = [("serve_skr", 0.0, 0.1, 4), ("serve_skr", 0.7, 1.3, 4), ("update", 1.35, 1.4, 1)]
    stalls = w.stalls(calls)
    assert [s["span"] for s in stalls] == ["between_calls", "serve_skr"]
    assert all(set(s) == {"span", "at_s", "ms", "gc_ms"} for s in stalls)
    summary = w.gc_summary()
    assert set(summary) == {"collections", "total_ms", "longest_ms", "longest_generation",
                            "longest_at_s"}
    assert summary["collections"] >= 1 and summary["longest_generation"] == 2
