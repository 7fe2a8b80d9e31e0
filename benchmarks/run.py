"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``--only serving,knn`` filters
(comma-separated substrings; an unmatched filter is an error that lists the
valid module names). ``--json`` additionally persists the scoreboard modules'
records as ``BENCH_<module>.json`` documents (git-sha-stamped; see
EXPERIMENTS.md section Scoreboard) into ``--out-dir``; ``--quick`` runs each
module's CI-sized quick path where one exists. Committed baselines at the
repo root are refreshed by re-running with ``--json --quick --out-dir .``
and diffed against fresh runs by tools/bench_compare.py.
"""
import argparse
import importlib
import inspect
import sys
import time
import traceback
from pathlib import Path

MODULES = [
    "bench_distribution",   # Fig 8
    "bench_region_size",    # Fig 9
    "bench_num_keywords",   # Fig 10
    "bench_scalability",    # Fig 11
    "bench_robustness",     # Fig 12
    "bench_index_size",     # Table 3
    "bench_construction",   # Table 4
    "bench_accel",          # Fig 13
    "bench_dynamic",        # Figs 14/15 + DESIGN.md section 7 maintenance A/B
    "bench_packing",        # Figs 16/17/18
    "bench_cdf",            # Fig 19
    "bench_itemsets",       # Fig 20
    "bench_action_mask",    # Fig 21
    "bench_knn",            # Fig 23 (appendix)
    "bench_serving",        # TPU-path serving (DESIGN.md section 3)
    "bench_roofline",       # EXPERIMENTS.md roofline summary
]

# the persistent-scoreboard modules: committed BENCH_*.json baselines live at
# the repo root and CI re-runs + diffs them (EXPERIMENTS.md section Scoreboard)
SCOREBOARD = {
    "bench_serving": "BENCH_serving.json",
    "bench_knn": "BENCH_knn.json",
    "bench_construction": "BENCH_construction.json",
    "bench_dynamic": "BENCH_dynamic.json",
    "bench_roofline": "BENCH_roofline.json",
}


def select_modules(only):
    """The MODULES entries matching the comma-separated substring filter
    (None -> all). Raises ValueError when a filter matches nothing."""
    if not only:
        return list(MODULES)
    pats = [p.strip() for p in only.split(",") if p.strip()]
    selected = [m for m in MODULES if any(p in m for p in pats)]
    if not selected:
        raise ValueError(
            f"--only {only!r} matches no benchmark module; valid names: "
            + ", ".join(MODULES)
        )
    return selected


def run_module(mod, quick: bool):
    """The module's record list: ``run_quick()`` when quick and available,
    else ``run(quick=True)`` when the signature takes it, else ``run()``."""
    if quick and hasattr(mod, "run_quick"):
        return mod.run_quick()
    if quick and "quick" in inspect.signature(mod.run).parameters:
        return mod.run(quick=True)
    return mod.run()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filters on module names")
    ap.add_argument("--quick", action="store_true",
                    help="run each module's CI-sized quick path if it has one")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<module>.json for the scoreboard modules")
    ap.add_argument("--out-dir", default=".",
                    help="directory for --json output (default: cwd, i.e. the "
                         "committed-baseline location when run from the repo root)")
    args = ap.parse_args()
    try:
        selected = select_modules(args.only)
    except ValueError as e:
        sys.exit(str(e))
    out_dir = Path(args.out_dir)
    from repro.launch.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    print("name,us_per_call,derived")
    print(f"# compile cache: {cache_dir}")
    failures = 0
    for mod_name in selected:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            records = run_module(mod, args.quick)
            for row in records:
                print(row, flush=True)
            elapsed = time.time() - t0
            if args.json and mod_name in SCOREBOARD:
                from . import common as C

                out_dir.mkdir(parents=True, exist_ok=True)
                path = out_dir / SCOREBOARD[mod_name]
                C.write_scoreboard(
                    path,
                    C.scoreboard_payload(mod_name, list(records),
                                         quick=args.quick, elapsed_s=elapsed),
                )
                print(f"# wrote {path}", flush=True)
            print(f"# {mod_name} done in {elapsed:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {mod_name} FAILED", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
