"""On-chip benchmark of WISK serving: one cell, one run, one process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``: ``workloads``) is one deployment
(``configs/<config>.json``) under one traffic mix (``traffic/<mix>.json``).
The run:

1. set-up (``setup_s``): the collection from the configuration's
   ``data_seed``; the WISK index from the store (built and stored on the
   checkout's first run, see ``store.py``); ``LiveIndex``; the run's
   geofences; the delta backlog; warm-up of every shape the window uses,
   until a round compiles nothing;
2. the measured window: ``--seconds`` of open-loop traffic from ``--seed``
   (``loop.py``), traced by the profiler with ``--trace 1``;
3. the comparison of every answer with the plain reference
   (``reference.py``), which decides ``correct``;
4. the metrics of the cell (``metrics/<name>.py``): its end-to-end metrics
   with ``--trace 0``, its per-layer metrics with ``--trace 1``.

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key. The run exits non-zero, printing no
result, when JAX's first device is not a TPU, when there are fewer devices
than the cell asks for, or when the device kind has no entry in
``peaks.json``. ``--control bf16`` puts the control (``control.py``) in the
program's place; ``--rate`` overrides the traffic file's rate (for the
sweep that finds a cell's knee).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(CHIP_DIR))

RUNS_DIR = CHIP_DIR / ".runs"  # traces (git-ignored)
STACKS_SHOWN = 12000  # characters of the stalls' sampled stacks printed to the log
CACHE_DIR = CHIP_DIR / ".cache" / "jax"  # persistent compile cache (git-ignored)
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
    "/jax/core/compile/backend_compile_duration": "compiled",
}


def say(phase: str, **fields) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


class CompileClock:
    """Programs lowered and compiled, and the seconds spent, from JAX's own
    monitoring events (a persistent-cache hit lowers but does not compile)."""

    def __init__(self) -> None:
        import jax

        self.count = {"lowered": 0, "compiled": 0}
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        kind = _COMPILE_EVENTS.get(event)
        if kind:
            self.count[kind] += 1
            self.seconds += duration

    def lowered(self) -> int:
        return self.count["lowered"]


class RunData:
    """What a metric reader sees of a run."""

    def __init__(self, plan, rec, setup_s, trace):
        import numpy as np

        from loop import GRACE_S

        self.plan, self.rec, self.setup_s, self.trace = plan, rec, setup_s, trace
        self.window_s = rec.window_s
        lat = rec.latency.copy()
        gone = np.isnan(lat)
        lat[gone] = rec.window_s + GRACE_S - plan.due[gone]  # unanswered: at least this late
        self._lat = lat

    def latency_ms(self, kinds):
        import numpy as np

        sel = np.isin(self.plan.kind, kinds)
        return self._lat[sel] * 1e3

    def spans(self, name):
        """(t0, t1) seconds of each of the loop's calls named ``name``."""
        return [(t0, t1) for n, t0, t1, _ in self.rec.calls if n == name]


def _metric_entries(bench, cell: str, trace: bool):
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def _read_metric(name: str, run: RunData):
    spec = importlib.util.spec_from_file_location(f"metric_{name}", CHIP_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _peaks(kind: str):
    table = json.loads((CHIP_DIR / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} has no entry in peaks.json")
    return table["devices"][kind]


def main(argv=None, require_tpu: bool = True, root: Path = ROOT, store_dir: Path = None,
         patch=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("none", "bf16"), default="none")
    ap.add_argument("--rate", type=float, default=None, help="override the traffic file's rate")
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args(argv)

    import cell as cellmod

    spec = cellmod.load_spec(args.workload, root)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run: the program under test is missing ({ROOT / 'src' / 'repro'})", file=sys.stderr)
        return 2
    if args.rate is not None:
        spec.traffic["rate_per_s"] = args.rate
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(1, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu" or len(devices) < spec.chips:
            print(f"run: needs {spec.chips} TPU chip(s); JAX found {len(devices)} "
                  f"{dev.platform} device(s)", file=sys.stderr)
            return 2
        try:
            peaks = _peaks(dev.device_kind)
        except KeyError as e:
            print(f"run: {e}", file=sys.stderr)
            return 2
        say("peaks", **peaks)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    say("device", platform=dev.platform, kind=repr(dev.device_kind), count=len(devices),
        jax=jax.__version__, workload=spec.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, control=args.control, rate_per_s=spec.traffic["rate_per_s"])
    clock = CompileClock()

    import numpy as np

    from gen.traffic import KINDS, make_plan

    objs = cellmod.make_collection(spec.config)
    say("collection", objects=objs.n, vocab=objs.vocab, t_s=f"{time.perf_counter() - T_START:.3f}")
    if args.control != "none":
        from control import ControlServer

        server = ControlServer(objs, args.control)
    else:
        server = cellmod.ProgramServer(spec, objs, say, store_dir or cellmod.store.STORE_DIR)
        if patch is not None:
            patch(server)
    say("server_ready", t_s=f"{time.perf_counter() - T_START:.3f}",
        compile_s=f"{clock.seconds:.3f}")
    setup = cellmod.prepare(server, spec, objs, args.seed, args.seconds, say)
    plan = make_plan(objs, spec.traffic, args.seconds, args.seed,
                     next_id=objs.n + setup.inserted, live_inserted=setup.live_inserted,
                     deleted=setup.deleted)
    skr_bms = server.bitmaps(plan.skr_kw)
    knn_bms = server.bitmaps(plan.knn_kw)
    kinds = [k for k in ("skr", "knn") if spec.traffic["mix"][k] > 0]
    rounds = cellmod.warm_up(server, objs, spec, kinds, clock.lowered, say)
    state = _describe(server)
    say("served_state", backlog_updates=len(setup.updates), geofences=len(setup.fence_ids),
        warmup_rounds=rounds, **state)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    say("setup", setup_s=f"{setup_s:.3f}", compile_s=f"{clock.seconds:.3f}",
        programs_lowered=clock.count["lowered"], programs_compiled=clock.count["compiled"],
        built_index=getattr(server, "built", False),
        build_s=f"{getattr(server, 'build_s', 0.0):.3f}" if getattr(server, "built", False) else "stored")
    counts = {k: int((plan.kind == i).sum()) for i, k in enumerate(KINDS)}
    say("window_plan", ops=plan.n, **counts)

    from loop import drive
    from watch import Watch

    k = int(spec.traffic["knn_k"])
    mb = int(spec.traffic["max_batch"])
    before = dict(clock.count)
    host0 = _host_counters()
    trace_dir = RUNS_DIR / f"{spec.name}-{args.seed}-trace"
    watch = Watch()
    reduction = None
    if args.trace:
        import trace_reduce

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # the benchmark's own spans, not the runtime's
        opts.enable_hlo_proto = False
        with jax.profiler.trace(str(trace_dir), profiler_options=opts):
            with jax.profiler.TraceAnnotation("window"), watch:
                rec = drive(server, plan, args.seconds, len(setup.updates), mb, k, skr_bms,
                            knn_bms, annotate=jax.profiler.TraceAnnotation, watch=watch)
        reduction = trace_reduce.reduce_dir(trace_dir)
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        with watch:
            rec = drive(server, plan, args.seconds, len(setup.updates), mb, k, skr_bms, knn_bms,
                        watch=watch)
    in_window = {k2: clock.count[k2] - before[k2] for k2 in clock.count}
    host1 = _host_counters()
    peak_rss_mb = round(host1.pop("max_rss_mb"), 1)
    say("window_host", **{k2: round(host1[k2] - host0[k2], 3) for k2 in host1 if k2 in host0},
        max_rss_mb=peak_rss_mb)
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    gc.unfreeze()
    wl = np.asarray(rec.wake_late) * 1e3
    slowest = max(rec.calls, key=lambda c: c[2] - c[1], default=("none", 0.0, 0.0, 0))
    ends = np.asarray([c[2] for c in rec.calls[:-1]])
    gaps = np.asarray([c[1] for c in rec.calls[1:]]) - ends  # loop time between calls
    widest = int(np.argmax(gaps)) if gaps.size else -1
    say("window",programs_lowered=in_window["lowered"], programs_compiled=in_window["compiled"],
        calls=len(rec.calls), answered=int(np.isfinite(rec.latency).sum()), of=plan.n,
        loop_wake_late_ms_p50=f"{np.percentile(wl, 50):.3f}" if wl.size else "none",
        loop_wake_late_ms_max=f"{wl.max():.3f}" if wl.size else "none",
        dispatch_lag_ms_p95=f"{_dispatch_lag_p95(plan, rec):.3f}",
        slowest_call=slowest[0], slowest_call_ms=f"{(slowest[2] - slowest[1]) * 1e3:.3f}",
        slowest_call_at_s=f"{slowest[1]:.3f}",
        widest_gap_ms=f"{gaps[widest] * 1e3:.3f}" if gaps.size else "none",
        widest_gap_at_s=f"{ends[widest]:.3f}" if gaps.size else "none",
        between_calls_s=f"{gaps.sum():.3f}", peak_bytes_in_use=peak)
    say("window_gc", **watch.gc_summary())
    for stall in watch.stalls(rec.calls):
        say("stall", **stall)
    stacks = watch.stack_report(STACKS_SHOWN)
    if stacks:
        print(stacks, flush=True)
    say("served_state_after", **_describe(server))

    import check

    t_check = time.perf_counter()
    verdict = check.verify(objs, setup, plan, rec, k)
    say("check", seconds=f"{time.perf_counter() - t_check:.3f}", correct=verdict.correct)

    run = RunData(plan, rec, setup_s, reduction)
    metrics = {}
    if dev.platform == "tpu":
        for m in _metric_entries(spec.bench, spec.name, bool(args.trace)):
            value = _read_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        say("metrics", withheld=f"a {dev.platform} run measures no device")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": spec.chips,
              "memory_peak_bytes": peak}
    result = {"correct": verdict.correct, "attempted": plan.n,
              "failed": int(np.isnan(rec.latency).sum()), "metrics": metrics, "device": device}
    if reduction is not None and dev.platform == "tpu":
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["checks"] = verdict.table()
    say("done", t_s=f"{time.perf_counter() - T_START:.3f}")
    for name, row in verdict.table().items():
        print(f"check {name}: {row['value']} (limit {row['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _describe(server) -> dict:
    """The served state for the log; a diagnostic that reads the program's
    internals, so a run outlives their renaming."""
    try:
        return server.describe()
    except (AttributeError, TypeError, ValueError) as e:
        return {"described": f"unavailable ({type(e).__name__}: {e})"}


def _host_counters() -> dict:
    """What the host did to this process (``getrusage``): page faults,
    context switches, CPU seconds and peak resident memory."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"minflt": ru.ru_minflt, "majflt": ru.ru_majflt, "nvcsw": ru.ru_nvcsw,
            "nivcsw": ru.ru_nivcsw, "cpu_s": ru.ru_utime + ru.ru_stime,
            "max_rss_mb": ru.ru_maxrss / 1024}


def _dispatch_lag_p95(plan, rec) -> float:
    """95th percentile of how long a request waited for its call to start."""
    import numpy as np

    lag = (rec.started - plan.due)[np.isfinite(rec.started)] * 1e3
    return float(np.percentile(lag, 95)) if lag.size else float("nan")


if __name__ == "__main__":
    faulthandler.enable(file=sys.stderr, all_threads=True)  # a fatal signal leaves the stacks
    sys.exit(main())
