"""Rehearse the benchmark without a chip. Run by hand, never by the tests.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py loop      # every cell, tiny, on the CPU
    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py compile   # serving kernels for a v5e

``loop`` drives each cell's whole run (set-up, window, comparison) on the
CPU with Pallas in interpret mode, skipping the look for a chip, at a tiny
size: each configuration's generator shapes with few objects, a low rate
and a short window. It asserts that the run is correct and prints no
metric (a CPU run measures no device).

``compile`` compiles the Pallas kernels each cell's ``serve`` /
``serve_knn`` path reaches for a described (not attached) v5e chip, at the
cell's real bitmap widths: 32 leaves padded to 16,384 objects, 64-query
batches, frontier width 32, the cell's full and packed word counts, and
for a cell with updates the delta slots and geofences.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import tempfile
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(CHIP_DIR))


def tiny_root(dest: Path, n: int = 2000, rate: float = 12.0, fences: int = 64,
              traffic_over=None) -> Path:
    """A copy of the benchmark's files with every configuration cut to ``n``
    objects and every traffic mix to ``rate`` operations per second (and
    ``traffic_over``'s keys set in every mix that has updates)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chip = dest / CHIP_DIR.relative_to(ROOT)
    (chip / "configs").mkdir(parents=True, exist_ok=True)
    (chip / "traffic").mkdir(parents=True, exist_ok=True)
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["data"]["n"] = n
        cfg["train_workload"]["m"] = 32
        cfg["build"]["partition"]["max_clusters"] = 8
        cfg["build"]["partition"]["n_steps"] = 10
        cfg["build"]["packing"]["epochs"] = 1
        cfg["build"]["cdf_train_steps"] = 10
        (dest / c["file"]).write_text(json.dumps(cfg))
    for t in (CHIP_DIR / "traffic").glob("*.json"):
        traffic = json.loads(t.read_text())
        traffic["rate_per_s"] = rate
        traffic["warmup_batches"] = 1
        if traffic["geofences"]["count"]:
            traffic["geofences"]["count"] = fences
        if traffic_over and traffic["mix"]["insert"] > 0:
            traffic.update(traffic_over)
        (chip / "traffic" / t.name).write_text(json.dumps(traffic))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


def run_tiny(root: Path, workload: str, seed: int, seconds: float = 4.0, extra=(), patch=None):
    """One CPU run of ``workload`` under ``root``; returns (result, output)."""
    import run as runmod

    out = io.StringIO()
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds), *extra]
    with contextlib.redirect_stdout(out):
        rc = runmod.main(argv, require_tpu=False, root=root, store_dir=root / "store",
                         patch=patch)
    text = out.getvalue()
    if rc != 0:
        raise RuntimeError(f"run exited {rc}:\n{text}")
    return json.loads(text.strip().splitlines()[-1]), text


def rehearse_loop(seed: int) -> None:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny_root(Path(tmp))
        for w in bench["workloads"]:
            for trace in ("0", "1"):
                result, text = run_tiny(root, w["name"], seed, extra=("--trace", trace))
                print(text, end="")
                assert result["correct"], result["checks"]
                assert result["metrics"] == {}, "a CPU run printed metrics"
                assert "busy_s" not in result["device"], "a CPU run printed device busy time"
                print(f"rehearsal {w['name']} trace={trace}: correct, no device metric printed")


def compile_for_v5e() -> None:
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(1, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import frontier, fused_verify, knn_filter, skr_verify, sub_match

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    f32, u32, i32, i16, i8 = jnp.float32, jnp.uint32, jnp.int32, jnp.int16, jnp.int8
    M, T, K, OBJ, F, DICT = 64, 32, 32, 16384, 32, 64
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: json.loads((ROOT / c["file"]).read_text()) for c in bench["configs"]}

    def compile_one(label, fn, shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text(), label
        print(f"compiled for v5e: {label}")

    for w in bench["workloads"]:
        cfg = configs[w["config"]]
        traffic = json.loads((CHIP_DIR / "traffic" / f"{w['traffic']}.json").read_text())
        W = (int(cfg["data"]["vocab"]) + 31) // 32
        WL = min(W, 32)
        live = traffic["mix"]["insert"] > 0
        name = w["name"]
        for wp in sorted({min(4, W), min(8, W)}):
            compile_one(f"{name} frontier_filter_narrow Wp={wp}",
                        lambda *a: frontier.frontier_filter_narrow(*a, interpret=False),
                        [((M, 4), f32), ((M, wp), u32), ((M, F, 4), i16), ((M, F, wp), u32),
                         ((M, F), i8), ((DICT,), f32), ((DICT,), f32)])
        compile_one(f"{name} frontier_filter W={W}",
                    lambda *a: frontier.frontier_filter(*a, interpret=False),
                    [((M, 4), f32), ((M, W), u32), ((M, F, 4), f32), ((M, F, W), u32), ((M, F), i8)])
        compile_one(f"{name} fused_verify_prefetch_compact Wl={WL}",
                    lambda *a: fused_verify.fused_verify_prefetch_compact(*a, interpret=False),
                    [((M, 4), f32), ((M, T, WL), u32), ((M, T), u32), ((M, T), i32), ((M, T), i8),
                     ((K, OBJ), f32), ((K, OBJ), f32), ((K, OBJ, WL), u32), ((K, OBJ), u32),
                     ((K, OBJ), i32)])
        compile_one(f"{name} fused_verify_prefetch W={W}",
                    lambda *a: fused_verify.fused_verify_prefetch(*a, interpret=False),
                    [((M, 4), f32), ((M, W), u32), ((M, T), i32), ((M, T), i8),
                     ((K, OBJ), f32), ((K, OBJ), f32), ((K, OBJ, W), u32), ((K, OBJ), i32)])
        if traffic["mix"]["knn"] > 0:
            compile_one(f"{name} knn_filter W={W}",
                        lambda *a: knn_filter.knn_filter(*a, interpret=False),
                        [((M, 2), f32), ((M, W), u32), ((M, F, 4), f32), ((M, F, W), u32), ((M, F), i8)])
        if live:
            B = int(cfg["serving"]["slots_per_leaf"])
            S = int(traffic["geofences"]["count"])
            compile_one(f"{name} skr_verify delta slots={B}",
                        lambda *a: skr_verify.skr_verify(*a, interpret=False),
                        [((M, 4), f32), ((M, W), u32), ((M, T * B), f32), ((M, T * B), f32),
                         ((M, T * B, W), u32), ((M, T * B), i8)])
            compile_one(f"{name} sub_match geofences={S}",
                        lambda *a: sub_match.sub_match(*a, interpret=False),
                        [((1, 2), f32), ((1, 8), i32), ((1, 8), u32), ((1, 1), u32),
                         ((S, 4), f32), ((S, W), u32), ((S, 1), u32)])


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("loop", "compile"))
    ap.add_argument("--seed", type=int, default=2**31 + 12345)
    a = ap.parse_args()
    rehearse_loop(a.seed) if a.what == "loop" else compile_for_v5e()
