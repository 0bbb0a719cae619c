"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is found by name: the cell in BENCHMARK.json
names its configuration (``benchmark/configs/<config>.json``); its
parameters are ``benchmark/workloads/<cell>.json``, whose ``generator``
names the traffic code (``benchmark/traffic/<generator>.py``); each
metric is read by ``benchmark/metrics/<metric>.py``. A new cell, config
or metric is new files and entries, never an edit.

The run: set-up (the sidecar, ``prewarm`` of the cell's rungs and
buckets, the seeded traffic, the writers' joins), a window of
``--seconds``, an untimed drain, the peak device memory, then the check
against the plain reference. The last line of stdout is one JSON
object; the numbers compared, each beside its limit, are the last lines
of stderr and the last key of that object.

With no TPU, or fewer chips than the cell asks for, it exits 3 and
prints no result. ``--rehearsal`` (the harness's own tests only) runs
the configuration's tiny sizes on whatever JAX finds, the CPU here.
``--control <name>`` puts the configuration's control (the reference
with one guarantee broken) in the program's place for the check.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", default=None)
    return ap.parse_args(argv)


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"unknown workload {name!r}")


def metrics_for(bench: dict, cell: str, trace: int) -> list:
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def fail(msg: str, code: int) -> None:
    print(msg, file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = cell_of(bench, args.workload)
    wl = load_json(BENCH / "workloads" / f"{args.workload}.json")
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    if wl["config"] != cell["config"] or wl["traffic"] != cell["traffic"]:
        fail(f"{args.workload}: workload file and BENCHMARK.json differ", 2)
    sizes = dict(cfg["sizes"])
    if args.rehearsal:
        sizes.update(cfg["rehearsal"])

    # the persistent compile cache at a fixed path inside the checkout
    cache = ROOT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearsal and (platform != "tpu"
                               or len(devices) < cell["chips"]):
        fail(f"no TPU with {cell['chips']} chip(s): JAX found "
             f"{len(devices)} {platform} device(s)", 3)
    peaks = load_json(BENCH / "peaks.json")
    kind = devices[0].device_kind
    if platform == "tpu" and kind not in peaks["devices"]:
        fail(f"device kind {kind!r} is not in benchmark/peaks.json", 2)

    from .harness import CompileMeter, SliceTracer, Spans, peak_bytes

    meter = CompileMeter()
    spans = Spans()
    logdir = ROOT / ".bench_trace"
    if args.trace:
        shutil.rmtree(logdir, ignore_errors=True)
    tracer = SliceTracer(bool(args.trace), str(logdir), spans,
                         **{**wl["trace_slice"],
                            **sizes.get("trace_slice", {})})
    gen = importlib.import_module(f"benchmark.traffic.{wl['generator']}")
    traffic = gen.Traffic(cfg, wl, sizes, args.seed, args.seconds,
                          spans, tracer)
    traffic.setup()
    setup_s = time.perf_counter() - t_start
    at_setup = meter.snapshot()
    rec = traffic.window()
    in_window = meter.since(at_setup)
    rec.update(setup_s=setup_s, compiles_in_window=in_window)
    memory_peak = peak_bytes(devices[0])
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    if args.trace and platform == "tpu":
        from .trace_reduce import reduce_slice

        rec["trace"] = reduce_slice(logdir)
        rec["traced_rounds"] = tracer.rounds
        rec["peak"] = peaks["devices"].get(kind)
        device.update(busy_s=rec["trace"]["busy_s"],
                      window_s=rec["trace"]["window_s"])
    print(json.dumps({"run": args.workload, "seed": args.seed,
                      "setup_s": setup_s, "compiles_at_setup": at_setup,
                      "compiles_in_window": in_window,
                      **{k: v for k, v in rec.items()
                         if k not in ("latency_ms", "trace",
                                      "traced_rounds", "peak")},
                      "trace": {k: v for k, v in rec.get("trace", {}).items()
                                if k != "device_ops"}},
                     default=float), file=sys.stderr, flush=True)

    checks, control = traffic.check(control=args.control)
    limits = cfg["limits"]
    if control is not None:
        print(json.dumps({"sound_checks": checks}), file=sys.stderr)
        checks = control
    correct = all(checks[k] <= limits[k] for k in limits)
    metrics = {}
    for m in metrics_for(bench, args.workload, args.trace):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"benchmark.metrics.{m['name']}")
        value = reader.read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": rec["ops"],
              "failed": checks.get("nacked_ops", 0) + checks["unapplied_ops"],
              "metrics": metrics, "device": device}
    if "trace" in rec:
        result["breakdown"] = {
            "device_ops": rec["trace"]["device_ops"][:10],
            "idle_gaps": rec["trace"]["idle_gaps"][:10]}
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    for k in limits:
        print(f"check {k} {checks[k]} limit {limits[k]}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    __package__ = "benchmark"
    import benchmark  # noqa: F401  (the package the relative imports use)

    sys.exit(main())
