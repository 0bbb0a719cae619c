"""Benchmark harness: batched merge throughput vs compiled baseline.

Prints exactly ONE JSON line on stdout no matter what happens:

  {"metric": ..., "value": N, "unit": "ops/s", "vs_baseline": R,
   "detail": {stages...}}

Architecture:

- The parent process is stdlib-only (never imports jax) and runs each
  benchmark stage in a SUBPROCESS with a hard timeout, so one stage
  that hangs or crashes cannot take the others with it.
- Without ``--smoke`` every stage runs on the TPU and nowhere else: a
  stage that finds no TPU, fails or times out fails the run (non-zero
  exit, no numbers for it). ``--smoke`` is the explicitly requested
  CPU rehearsal at reduced sizes. Every stage record names the
  ``platform``, ``device_kind`` and ``device_count`` it ran on.
- Baselines: `vs_baseline` compares the batched kernel to the C++ -O2
  scalar replayer (native/merge_replay.cpp) running the identical
  sequenced-path semantics on the same host — the stand-in for the
  reference's Node.js merge-tree (no Node runtime exists in this
  zero-egress image; a V8-JITted B-tree is bounded above by compiled
  C++ on the same workload, making the factor conservative). The raw
  Python-oracle comparison is also recorded per stage.

Stages = BASELINE.md configs:
  config1  SharedString single-doc replay             (BASELINE #1)
  config2  N docs x concurrent clients, batched apply  (BASELINE #2)
  config3  SharedMatrix N-matrix spreadsheet           (BASELINE #3)
  config4  SharedTree rebase over N trees              (BASELINE #4)
  config5  service pipeline: sequencer -> sidecar      (BASELINE #5-lite)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

STAGES = ("probe", "fuzz", "config1", "config2", "config3", "config4",
          "config5", "config6", "config7", "config8", "config9",
          "config10", "config11", "config12", "config13", "config14",
          "config15", "config16", "config17")

# Machine-readable corpus identity, stamped into EVERY stage record
# (r5 silently changed the stream mix — flow-mix quarter joined — and
# broke config2/config5 comparability with r3/r4 behind a docstring
# note; comparisons must be able to check this field instead).
# Bump `version` whenever a generator change alters the op mix.
STREAM_CORPUS = {"generator": "fuzzmix+flowmix", "version": 2,
                 "changed": "r5: flow-mix quarter joined the corpus"}
STAGE_CORPUS = {
    "probe": {"generator": "fuzzmix-tiny", "version": 1},
    "fuzz": {"generator": "fuzzmix-adversarial", "version": 1},
    "config1": STREAM_CORPUS,
    "config2": STREAM_CORPUS,
    "config3": {"generator": "matrix-synthetic", "version": 1},
    "config4": {"generator": "tree-fuzz", "version": 2,
                "changed": "r7: moves joined the corpus (peer AND "
                           "trunk changesets; tree serving plane)"},
    "config5": STREAM_CORPUS,
    "config6": {"generator": "ladder-typing", "version": 1},
    "config7": STREAM_CORPUS,
    "config8": {"generator": "overload-mix", "version": 1},
    "config9": {"generator": "open-loop-poisson", "version": 1},
    "config10": {"generator": "mesh-hotspot", "version": 1},
    "config11": {"generator": "chaos-standard", "version": 1},
    "config12": {"generator": "chaos-failover", "version": 1},
    "config13": {"generator": "chaos-netsplit", "version": 1},
    "config14": {"generator": "route-tri-corpus", "version": 2,
                 "changed": "r6: remove-heavy quarter joined "
                            "(event-splitting evidence)"},
    "config15": {"generator": "columnar-pack-mix", "version": 1},
    "config16": {"generator": "heat-attribution", "version": 1},
    "config17": {"generator": "tree-serve", "version": 1},
}


# ======================================================================
# stage implementations (run inside the subprocess)

def _stage_env_setup(backend: str, stage: str = "") -> None:
    """Must run before the first jax import in the stage process. The
    persistent compilation cache (``utils.compile_cache``) lets later
    stages skip compiles an earlier one already paid for."""
    if backend == "cpu":
        if stage == "config10":
            # the mesh-scaling stage emulates a multi-device mesh on
            # CPU (same recipe as the tier-1 mesh_cpu_subprocess
            # fixture); must land before the first jax import
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=4"
                ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from fluidframework_tpu.utils.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != backend:
        raise RuntimeError(
            f"stage {stage} asked for {backend!r} but JAX found "
            f"{platform!r}; bench does not fall back to another backend"
        )
    enable_compile_cache()


def _build_streams(n_streams: int, steps: int, clients: int, seed0: int):
    """Bench corpus (``testing.record_corpus_stream``): 3/4 generic
    fuzz-mix streams + 1/4 webflow-mix editor streams (tag-pair
    markers, pair-consistent removes, css token-list annotate churn).
    NOTE: the flow mix joined in r5, so corpus-sensitive numbers
    (config2/config5) are not directly comparable to r3/r4 records."""
    from fluidframework_tpu.ops import encode_stream
    from fluidframework_tpu.testing import record_corpus_stream

    raw, encoded = [], []
    for i in range(n_streams):
        _, stream = record_corpus_stream(i, seed0, clients, steps)
        raw.append(stream)
        encoded.append(encode_stream(stream))
    return raw, encoded


def _sync(out):
    """Force completion with a device->host transfer, which provably
    includes the compute (an earlier shared-chip setup returned from
    block_until_ready at dispatch). Every timing in this harness must
    pass through here."""
    import numpy as np

    leaf = out.count if hasattr(out, "count") else out
    return np.asarray(leaf)


def _time_kernel(table_fn, batch, reps: int, cooldown: float):
    """Best-of-reps window time (transfer-forced, see _sync). Returns
    the warmup (compile) seconds alongside so every stage record
    separates compile from run."""
    from fluidframework_tpu.ops import apply_window

    t0 = time.perf_counter()
    out = apply_window(table_fn(), batch)  # warmup/compile
    _sync(out)
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        fresh = table_fn()
        _sync(fresh)
        time.sleep(cooldown)
        t0 = time.perf_counter()
        out = apply_window(fresh, batch)
        _sync(out)
        times.append(time.perf_counter() - t0)
    return out, min(times), times, compile_s


# Per-chip HBM bandwidth peak by ``device_kind`` (GB/s). Source: Google
# Cloud TPU documentation, "TPU v5e" system architecture (16 GB HBM2 at
# 819 GB/s per chip). A device missing here is an error, not a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def _hbm_stats(jitted, args, window_time_s):
    """Compiler-modeled HBM traffic for ONE window dispatch, scaled
    by the measured window time vs the running device's HBM peak
    (``HBM_PEAK_GBPS``). Direction of the bound: XLA's cost_analysis
    'bytes accessed' OVERCOUNTS real HBM traffic wherever fusion/VMEM
    reuse serves bytes on-chip, so achieved_gbps and the utilization
    figure are UPPER bounds on what the HBM actually sustained —
    model-traffic numbers for auditing which regime a kernel is in,
    not profiler counters. None on the CPU (the ``--smoke``
    rehearsal), which has no HBM peak to compare with."""
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    peak = HBM_PEAK_GBPS.get(device.device_kind)
    if peak is None:
        raise KeyError(
            f"no HBM peak for device_kind {device.device_kind!r}; add "
            "it to bench.HBM_PEAK_GBPS with its source"
        )
    ca = jitted.lower(*args).compile().cost_analysis()
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    if not bytes_accessed or not window_time_s:
        return None
    gbps = bytes_accessed / window_time_s / 1e9
    return {
        "model_bytes_per_window": int(bytes_accessed),
        "model_gbps_upper_bound": round(gbps, 3),
        "device_kind": device.device_kind,
        "peak_gbps": peak,
        "hbm_utilization_upper_bound": round(gbps / peak, 5),
    }


def _cpp_baseline(encoded, min_seconds: float = 1.0):
    """ops/s of the C++ scalar replayer over the distinct streams;
    None when the toolchain is missing."""
    from fluidframework_tpu.native.replay_baseline import (
        encode_ops_array,
        replay,
    )

    arrays = [encode_ops_array(e) for e in encoded]
    probe = replay(arrays[0], reps=1)
    if probe is None:
        return None, None
    # calibrate reps to fill the budget
    per = max(probe[2], 1e-6)
    reps = max(1, int(min_seconds / (per * len(arrays))))
    total_ops = 0
    total_t = 0.0
    checksums = []
    for arr in arrays:
        checksum, _live, dt = replay(arr, reps=reps)
        checksums.append(checksum)
        total_ops += arr.shape[0] * reps
        total_t += dt
    return total_ops / total_t, checksums


def _py_baseline(raw_streams, seconds: float):
    from fluidframework_tpu.models.mergetree import MergeTreeClient
    from fluidframework_tpu.protocol.messages import MessageType

    ops = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        for stream in raw_streams:
            obs = MergeTreeClient("bench-observer")
            obs.start_collaboration("bench-observer")
            for msg in stream:
                if msg.type == MessageType.OPERATION:
                    obs.apply_msg(msg)
                    ops += 1
            if time.perf_counter() - t0 > seconds:
                break
    return ops / (time.perf_counter() - t0)


def _pct(sorted_arr, q: float):
    """Percentile by rank on an ascending sample (the ONE definition
    every stage's statistics flow through)."""
    n = len(sorted_arr)
    if n == 0:
        return None
    return sorted_arr[min(n - 1, int(n * q))]


def _dist(times) -> dict:
    """Median + spread + percentiles of a timing sample — every stage
    record carries these so progress claims rest on more than 1-3
    unqualified samples."""
    arr = sorted(times)
    med = _pct(arr, 0.5)
    return {
        "window_median_s": round(med, 4),
        "window_spread_pct": round(
            100 * (arr[-1] - arr[0]) / med, 1) if med else None,
        "n_reps": len(arr),
        # dispatch-window latency percentiles: an op entering a window
        # is applied within one window time, so these bound op-apply
        # latency on the batched path (single-doc latency is config1's
        # host-route op_apply_p50/99_ms)
        "p50_ms": round(med * 1000, 2),
        "p99_ms": round(_pct(arr, 0.99) * 1000, 2),
    }


def _real_ops(batch) -> int:
    import numpy as np

    from fluidframework_tpu.ops.segment_table import KIND_NOOP

    return int((np.asarray(batch.kind) != KIND_NOOP).sum())


def _time_chunked(table_fn, batch, reps: int, cooldown: float,
                  chunk_k: int):
    """Chunked-executor timing twin of _time_kernel: the chunk program
    compiles at pack time (host pass, reported separately) and the
    window applies in ceil-ish W/take macro-steps."""
    from fluidframework_tpu.ops.merge_chunk import (
        apply_window_chunked,
        build_chunked,
    )

    t0 = time.perf_counter()
    chunked = build_chunked(batch, K=chunk_k)
    pack_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = apply_window_chunked(table_fn(), chunked, K=chunk_k)
    _sync(out)
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        fresh = table_fn()
        _sync(fresh)
        time.sleep(cooldown)
        t0 = time.perf_counter()
        out = apply_window_chunked(fresh, chunked, K=chunk_k)
        _sync(out)
        times.append(time.perf_counter() - t0)
    import numpy as np

    steps = int(np.asarray(chunked["chunk_start"]).sum(axis=1).max())
    return out, min(times), times, compile_s, pack_s, steps, chunked


def _kernel_stage(name: str, docs: int, base: int, steps: int,
                  clients: int, capacity: int, seed0: int, reps: int,
                  cooldown: float, chunk_k: int = 8) -> dict:
    """Shared body of the pure-kernel configs: build workload, time the
    batched dispatch on BOTH executors (sequential scan + chunked
    macro-steps), checksum-verify against the C++ replayer, record
    both baselines. The headline number is the faster executor; both
    are reported."""
    from fluidframework_tpu.native.replay_baseline import table_checksum
    from fluidframework_tpu.ops import build_batch, fetch, make_table

    raw, encoded = _build_streams(base, steps, clients, seed0=seed0)
    tiled = [encoded[d % base] for d in range(docs)]
    batch = build_batch(tiled)
    table, best, times, compile_s = _time_kernel(
        lambda: make_table(docs, capacity), batch, reps, cooldown
    )
    np_table = fetch(table)
    assert not np_table["overflow"].any(), f"{name} capacity overflow"
    real = _real_ops(batch)

    chunk_rec = None
    try:
        # secondary executor measurement: fewer reps + short cooldown
        # so the stage (two executors + both baselines + parity) stays
        # inside the TPU subprocess budget
        (ctab, cbest, ctimes, ccompile, cpack, csteps,
         chunked_prog) = _time_chunked(
            lambda: make_table(docs, capacity), batch,
            max(2, reps // 2), min(cooldown, 2.0), chunk_k,
        )
        cnp = fetch(ctab)
        # live-state parity vs the sequential executor (bit-identical
        # contract, tests/test_merge_chunk.py)
        import numpy as np

        for d in range(min(8, docs)):
            n = int(np_table["count"][d])
            assert n == int(cnp["count"][d]), f"{name} chunk count d{d}"
            for f in ("length", "seq", "client", "removed_seq",
                      "op_id", "op_off"):
                assert np.array_equal(
                    np_table[f][d, :n], cnp[f][d, :n]
                ), f"{name} chunk parity {f} d{d}"
        window = int(batch.kind.shape[1])
        from fluidframework_tpu.ops import merge_chunk

        # same jit object + shapes the timing loop just compiled, so
        # the AOT lower/compile below resolves from the compilation
        # cache instead of paying a second on-chip compile
        cjit, cargs = merge_chunk.compiled_window(
            make_table(docs, capacity), chunked_prog, K=chunk_k)
        chunk_hbm = _hbm_stats(cjit, cargs, cbest)
        chunk_rec = {
            "ops_per_sec": round(real / cbest, 1),
            "best_window_time_s": round(cbest, 4),
            "window_times_s": [round(t, 4) for t in ctimes],
            "compile_s": round(ccompile, 2),
            "chunk_pack_s": round(cpack, 2),
            "macro_steps": csteps,
            "steps_per_window_ratio": round(csteps / window, 3),
            "K": chunk_k,
            "hbm": chunk_hbm,
            "parity": "live-state-verified x8 vs sequential",
        }
    except Exception as e:  # noqa: BLE001 - recorded, not fatal
        chunk_rec = {"error": f"{type(e).__name__}: {e}"[:300]}
        cbest = None

    cpp_ops_s, checksums = _cpp_baseline(encoded)
    if checksums is not None:
        for d in range(min(4, docs)):
            assert checksums[d % base] == table_checksum(np_table, d), (
                f"{name} kernel/C++ divergence doc {d}"
            )
    py_ops_s = _py_baseline(raw, 2.0)
    from fluidframework_tpu.ops.merge_kernel import compiled_window

    # compiled_window() is the exact jit the timing loop dispatched
    # (apply_window routes to it), so its AOT lower/compile hits the
    # compilation cache; skip the stat when the opt-in Pallas kernel
    # was the timed executor — attributing XLA-program bytes over a
    # Pallas window time would be a wrong utilization number
    hbm = None if os.environ.get("FFTPU_PALLAS") == "1" else \
        _hbm_stats(
            compiled_window(),
            (make_table(docs, capacity), batch), best,
        )
    from fluidframework_tpu.service.tpu_sidecar import default_executor

    headline = best if cbest is None else min(best, cbest)
    return {
        "docs": docs,
        "window": int(batch.kind.shape[1]),
        "kernel_ops_per_sec": round(real / headline, 1),
        "hbm": hbm,
        "executor": (
            "chunked" if cbest is not None and cbest < best
            else "sequential-scan"
        ),
        # what the SERVING path (sidecar) would dispatch on this
        # backend — the kernel stage measures both executors either way
        "serving_default_executor": default_executor(),
        "sequential_ops_per_sec": round(real / best, 1),
        "chunked": chunk_rec,
        "cpp_baseline_ops_per_sec": (
            round(cpp_ops_s, 1) if cpp_ops_s else None
        ),
        "py_baseline_ops_per_sec": round(py_ops_s, 1),
        "real_ops": real,
        "best_window_time_s": round(headline, 4),
        "compile_s": round(compile_s, 2),
        "window_times_s": [round(t, 4) for t in times],
        # the distribution fields describe the WINNING executor (the
        # one the headline uses), not always the sequential scan
        **_dist(ctimes if cbest is not None and cbest < best
                else times),
        "parity": "checksum-verified" if checksums else "cpp-unavailable",
    }


def stage_probe(scale: str, reps: int, cooldown: float) -> dict:
    """Localizes TPU liveness/compile cost before any heavy stage runs
    (a run that dies in backend init/compile otherwise records
    nothing). Records backend-init seconds, a tiny-kernel
    compile+run on the dispatcher path, and whether the Pallas fast
    path lowered."""
    import numpy as np

    t0 = time.perf_counter()
    import jax

    backend = jax.default_backend()
    ndev = len(jax.devices())
    init_s = time.perf_counter() - t0

    from fluidframework_tpu.ops import (
        apply_window,
        build_batch,
        encode_stream,
        fetch,
        make_table,
    )
    from fluidframework_tpu.testing import FuzzConfig, record_op_stream

    _, stream = record_op_stream(FuzzConfig(
        n_clients=2, n_steps=20, seed=1, insert_weight=0.6,
        remove_weight=0.2, annotate_weight=0.1, process_weight=0.1,
    ))
    batch = build_batch([encode_stream(stream)])
    t0 = time.perf_counter()
    table = apply_window(make_table(1, 128), batch)
    _sync(table)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    table = apply_window(make_table(1, 128), batch)
    _sync(table)
    run_s = time.perf_counter() - t0
    count = int(np.asarray(fetch(table)["count"])[0])

    pallas = {"attempted": False}
    if backend == "tpu":
        from fluidframework_tpu.ops.pallas_merge import (
            apply_window_pallas,
        )

        pallas["attempted"] = True
        try:
            t0 = time.perf_counter()
            ptab = apply_window_pallas(make_table(1, 128), batch)
            _sync(ptab)
            pallas["compile_s"] = round(time.perf_counter() - t0, 2)
            ref = fetch(table)
            got = fetch(ptab)
            pallas["matches_xla"] = all(
                bool(np.array_equal(ref[f], got[f])) for f in ref
            )
        except Exception as e:  # noqa: BLE001 - recorded, not raised
            pallas["error"] = f"{type(e).__name__}: {e}"[:400]
    return {
        "devices": ndev,
        "backend_init_s": round(init_s, 2),
        "tiny_compile_s": round(compile_s, 2),
        "tiny_run_s": round(run_s, 4),
        "live_slots": count,
        "pallas": pallas,
    }


def stage_fuzz(scale: str, reps: int, cooldown: float) -> dict:
    """On-backend adversarial fuzz smoke: the
    1000+ CPU fuzz tests never execute the TPU backend; this stage
    runs seeded differential fuzz ON the stage's backend — batched
    kernel AND chunked executor vs the scalar oracle, full per-position
    (char, props) signatures, not just checksums — so on-chip
    correctness evidence rides every bench run."""
    import numpy as np

    from fluidframework_tpu.models.mergetree import MergeTreeClient
    from fluidframework_tpu.ops import (
        build_batch,
        encode_stream,
        extract_signature,
        fetch,
        make_table,
    )
    from fluidframework_tpu.ops.host_bridge import interned_signature
    from fluidframework_tpu.ops.merge_chunk import (
        apply_window_chunked,
        build_chunked,
    )
    from fluidframework_tpu.ops.merge_kernel import apply_window
    from fluidframework_tpu.protocol.messages import MessageType
    from fluidframework_tpu.testing import FuzzConfig, record_op_stream

    n_seeds, steps, clients = {
        "full": (10, 160, 6), "cpu": (10, 120, 4), "smoke": (4, 60, 3),
    }[scale]
    streams, encs = [], []
    for seed in range(n_seeds):
        _, s = record_op_stream(FuzzConfig(
            n_clients=clients, n_steps=steps, seed=90000 + seed,
            insert_weight=0.5, remove_weight=0.3,
            annotate_weight=0.1, process_weight=0.1,
        ))
        streams.append(s)
        encs.append(encode_stream(s))
    batch = build_batch(encs)
    capacity = 1024
    seq_tab = fetch(apply_window(make_table(n_seeds, capacity), batch))
    chunked = build_chunked(batch, K=8)
    chunk_tab = fetch(apply_window_chunked(
        make_table(n_seeds, capacity), chunked, K=8))

    mismatches = []
    for d, (stream, enc) in enumerate(zip(streams, encs)):
        obs = MergeTreeClient("oracle")
        obs.start_collaboration("oracle")
        for msg in stream:
            if msg.type == MessageType.OPERATION:
                obs.apply_msg(msg)
        want = interned_signature(obs, enc)
        if extract_signature(seq_tab, enc, d) != want:
            mismatches.append(("sequential", d))
        if extract_signature(chunk_tab, enc, d) != want:
            mismatches.append(("chunked", d))
        n = int(seq_tab["count"][d])
        for f in ("length", "seq", "client", "removed_seq"):
            if not np.array_equal(seq_tab[f][d, :n],
                                  chunk_tab[f][d, :n]):
                mismatches.append(("executor-divergence", d, f))
    if os.environ.get("FFTPU_FUZZ_SABOTAGE"):
        # test hook: prove a correctness failure poisons the run's
        # top-level status
        mismatches.append(("sabotage", -1))
    assert not mismatches, f"fuzz mismatches: {mismatches}"
    return {
        "seeds": n_seeds,
        "steps": steps,
        "clients": clients,
        "executors": ["sequential-scan", "chunked"],
        "result": "all-signatures-match",
        "parity": f"signature-verified x{n_seeds} x2 executors",
    }


def stage_config1(scale: str, reps: int, cooldown: float) -> dict:
    """BASELINE #1: single-doc replay — measured on the SERVING ROUTE
    a single document actually takes: small/lone
    documents run on the host tier (the same scalar engines the
    sidecar's eviction path uses; batching across documents is where
    the device wins, and a 1-doc dispatch pays full launch latency for
    nothing). Reports:

    - host serving ops/s (C++ twin — the native single-doc engine) and
      per-op apply-latency percentiles (measured op-by-op on the
      Python replica, labeled as such);
    - the 1-doc device dispatch as a reference number, so the routing
      decision stays visible."""
    import numpy as np

    from fluidframework_tpu.models.mergetree import MergeTreeClient
    from fluidframework_tpu.protocol.messages import MessageType

    steps, capacity = {
        "full": (600, 2048), "cpu": (300, 1024), "smoke": (80, 512),
    }[scale]
    raw, encoded = _build_streams(1, steps, clients=2, seed0=4242)
    stream = raw[0]

    # host serving: per-op apply latency on the scalar replica
    lat_ns = []
    obs = MergeTreeClient("serve")
    obs.start_collaboration("serve")
    for msg in stream:
        if msg.type != MessageType.OPERATION:
            continue
        t0 = time.perf_counter_ns()
        obs.apply_msg(msg)
        lat_ns.append(time.perf_counter_ns() - t0)
    lat_ms = np.array(sorted(lat_ns)) / 1e6
    py_serve_ops_s = 1e9 * len(lat_ns) / max(sum(lat_ns), 1)

    cpp_ops_s, checksums = _cpp_baseline(encoded, min_seconds=1.0)
    serving_ops_s = cpp_ops_s or py_serve_ops_s

    # device reference (1-doc dispatch; worst case by design)
    device = _kernel_stage(
        "config1-device-ref", docs=1, base=1, steps=steps, clients=2,
        capacity=capacity, seed0=4242, reps=max(2, reps // 2),
        cooldown=cooldown,
    )
    return {
        "serving_route": "host-scalar (C++ twin; device engages at "
                         "batch scale — see config2)",
        "kernel_ops_per_sec": round(serving_ops_s, 1),
        "cpp_baseline_ops_per_sec": (
            round(cpp_ops_s, 1) if cpp_ops_s else None
        ),
        "py_baseline_ops_per_sec": round(py_serve_ops_s, 1),
        "op_apply_p50_ms": round(float(_pct(lat_ms, 0.5)), 5),
        "op_apply_p99_ms": round(float(_pct(lat_ms, 0.99)), 5),
        "latency_source": "py-replica per-op timing",
        "real_ops": len(lat_ns),
        "parity": device["parity"],
        "device_reference": {
            k: device[k] for k in (
                "kernel_ops_per_sec", "executor", "best_window_time_s",
                "window", "chunked",
            ) if k in device
        },
    }


def stage_config2(scale: str, reps: int, cooldown: float) -> dict:
    """BASELINE #2: N docs x concurrent clients typing, one batched
    dispatch across all docs — the headline throughput config."""
    # full-scale docs 4096: on an earlier shared-chip setup the
    # per-step cost was nearly flat in docs until HBM saturated (16384
    # docs regressed); not yet re-measured on the v5e
    docs, base, steps, clients, capacity = {
        "full": (4096, 16, 220, 4, 1024),
        "cpu": (64, 8, 120, 3, 512),
        "smoke": (16, 4, 60, 3, 512),
    }[scale]
    return _kernel_stage("config2", docs=docs, base=base, steps=steps,
                         clients=clients, capacity=capacity,
                         seed0=31337, reps=reps, cooldown=cooldown)


def stage_config3(scale: str, reps: int, cooldown: float) -> dict:
    """BASELINE #3: N-matrix spreadsheet workload — 10k-row scale on
    the full config. Axis ops (row/col insert+remove runs) run through
    the merge kernel as a single 2N-doc dispatch; cell sets apply as
    one vectorized host scatter. The op stream here is sequentially
    consistent (refseq = seq-1) — concurrency semantics are covered by
    the kernel fuzz suites; this stage measures scale."""
    import jax

    from fluidframework_tpu.models.mergetree.ops import (
        InsertOp,
        RemoveOp,
    )
    from fluidframework_tpu.ops import fetch
    from fluidframework_tpu.ops.matrix_bridge import (
        MatrixStream,
        extract_matrix,
    )
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    matrices, row_runs, run_len, cols, cells, removes, capacity = {
        "full": (64, 205, 50, 16, 4000, 60, 1024),
        "cpu": (8, 40, 25, 8, 800, 20, 256),
        "smoke": (2, 10, 10, 4, 100, 5, 128),
    }[scale]
    import random

    rng = random.Random(1337)

    def build_stream(m):
        ms = MatrixStream()
        seq = 0
        alloc = 0

        def send(contents):
            nonlocal seq
            seq += 1
            ms.add_message(SequencedMessage(
                client_id="w", sequence_number=seq,
                minimum_sequence_number=max(0, seq - 1),
                client_sequence_number=seq,
                reference_sequence_number=seq - 1,
                type=MessageType.OPERATION, contents=contents,
            ))

        n_rows = 0
        for r in range(row_runs):
            send({"target": "rows", "op": InsertOp(
                pos1=rng.randint(0, n_rows),
                text="\x00" * run_len,
                handle=[f"w/{m}/{alloc}", 0],
            )})
            alloc += 1
            n_rows += run_len
        for c in range(cols):
            send({"target": "cols", "op": InsertOp(
                pos1=rng.randint(0, c), text="\x00",
                handle=[f"w/{m}/c{c}", 0],
            )})
        for _ in range(removes):
            start = rng.randint(0, n_rows - 2)
            send({"target": "rows", "op": RemoveOp(
                pos1=start, pos2=start + 1)})
            n_rows -= 1
        for _ in range(cells):
            send({
                "target": "cell",
                "row": f"w/{m}/{rng.randint(0, row_runs - 1)}:"
                       f"{rng.randint(0, run_len - 1)}",
                "col": f"w/{m}/c{rng.randint(0, cols - 1)}:0",
                "value": rng.randint(0, 9999),
            })
        return ms

    streams = [build_stream(m) for m in range(matrices)]
    total_ops = sum(ms.op_count for ms in streams)

    # pack ONCE outside the timed region (config2 methodology); the
    # pack cost is reported separately. Cells apply ON DEVICE: one
    # sort + last-wins + scatter per window (matrix.ts:79 LWW),
    # not a sequential scan.
    import numpy as np

    from fluidframework_tpu.ops.matrix_cells import CellPack
    from fluidframework_tpu.ops.matrix_bridge import (
        dispatch_matrix_batch,
        pack_matrix_batch,
    )

    t0 = time.perf_counter()
    batch = pack_matrix_batch(streams)
    cellpack = CellPack(n_rows=row_runs * run_len, n_cols=cols)
    cellpack.pack(streams)
    pack_s = time.perf_counter() - t0

    def dispatch():
        table = dispatch_matrix_batch(batch, matrices, capacity)
        cells_grid = cellpack.apply()
        return table, cells_grid

    def sync_both(table, cells_grid):
        _sync(table)
        # small derived leaf: a full np.asarray of the [M, R, C] grid
        # would charge a ~40MB D2H transfer to the kernel time
        _sync(cells_grid[:, 0, 0])

    t0 = time.perf_counter()
    table, cells_grid = dispatch()  # warmup/compile
    sync_both(table, cells_grid)
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        time.sleep(cooldown)
        t0 = time.perf_counter()
        table, cells_grid = dispatch()
        sync_both(table, cells_grid)
        times.append(time.perf_counter() - t0)
    best = min(times)
    np_table = fetch(table)
    np_grid = np.asarray(cells_grid)
    assert not np_table["overflow"].any(), "config3 capacity overflow"

    # host materialization of one matrix (untimed sanity)
    t0 = time.perf_counter()
    grid = extract_matrix(np_table, streams[0], 0)
    extract_s = time.perf_counter() - t0

    # scalar python baseline (host replay of both axes + dict cells)
    from fluidframework_tpu.ops.host_replay import replay_encoded

    t0 = time.perf_counter()
    # parity breadth: sample at least 4 matrices (all of them below
    # 4), not one
    sample = streams[: max(min(4, matrices), matrices // 8)]
    scalar_ops = 0
    host_replays = []
    for ms in sample:
        host_replays.append(
            (replay_encoded(ms.rows.ops), replay_encoded(ms.cols.ops))
        )
        cells_map = {}
        for rh, ch, v in zip(ms.cell_rows, ms.cell_cols, ms.cell_vals):
            cells_map[(rh, ch)] = v
        scalar_ops += ms.op_count
    py_s = time.perf_counter() - t0
    py_ops_s = scalar_ops / py_s

    # parity: device axis handle order == host-replay handle order for
    # EVERY sampled matrix (both axes)
    from fluidframework_tpu.ops.matrix_bridge import _visible_handles

    for d0, (ms0, (host_rows, host_cols)) in enumerate(
            zip(sample, host_replays)):
        assert _visible_handles(np_table, 2 * d0, ms0.row_allocs) == \
            _visible_handles(
                host_rows.as_table(), 0, ms0.row_allocs), (
                f"config3 device/host row-axis divergence m={d0}")
        assert _visible_handles(
            np_table, 2 * d0 + 1, ms0.col_allocs) == \
            _visible_handles(
                host_cols.as_table(), 0, ms0.col_allocs), (
                f"config3 device/host col-axis divergence m={d0}")
    # parity: device LWW grid == host dict for the sampled matrices
    for m, ms in enumerate(sample):
        host_cells = {}
        for rh, ch, v in zip(ms.cell_rows, ms.cell_cols, ms.cell_vals):
            host_cells[(rh, ch)] = v
        for (rh, ch), want in host_cells.items():
            got = cellpack.lookup(np_grid, m, rh, ch)
            assert got == want, (
                f"config3 cell LWW divergence m={m} {rh},{ch}"
            )

    cpp_ops_s, _ = _cpp_baseline(
        [ms.rows for ms in streams[:8]]
        + [ms.cols for ms in streams[:8]]
    )

    return {
        "matrices": matrices,
        "rows": row_runs * run_len,
        "kernel_ops_per_sec": round(total_ops / best, 1),
        "cpp_baseline_ops_per_sec": (
            round(cpp_ops_s, 1) if cpp_ops_s else None
        ),
        "py_baseline_ops_per_sec": round(py_ops_s, 1),
        "real_ops": total_ops,
        "cell_ops": int(sum(len(ms.cell_vals) for ms in streams)),
        "best_window_time_s": round(best, 4),
        "compile_s": round(compile_s, 2),
        "pack_s": round(pack_s, 3),
        "extract_one_matrix_s": round(extract_s, 4),
        "window_times_s": [round(t, 4) for t in times],
        **_dist(times),
        "parity": (
            f"axis-handles + cell-LWW x{len(sample)}; "
            f"grid {len(grid)}x{len(grid[0]) if grid else 0}"
        ),
    }


def stage_config4(scale: str, reps: int, cooldown: float) -> dict:
    """BASELINE #4: SharedTree concurrent rebase over N trees — each
    tree rebases one peer changeset over a K-deep trunk suffix in a
    single batched dispatch (the EditManager sequenced path's hot
    loop)."""
    import copy
    import random

    import jax
    import numpy as np

    from fluidframework_tpu.models.tree import changeset as cs
    from fluidframework_tpu.ops.tree_atoms import (
        TreeAtoms,
        apply_atoms,
        encode_changeset,
        stack_changesets,
    )
    from fluidframework_tpu.ops.tree_kernel import rebase_over_trunk
    from fluidframework_tpu.testing.tree_fuzz import (
        random_changeset,
        random_trunk,
    )

    docs, k_trunk, base_n, edits = {
        "full": (4096, 8, 24, 5),
        "cpu": (512, 8, 24, 5),
        "smoke": (64, 4, 12, 3),
    }[scale]
    rng = random.Random(2024)

    base = [{"type": "n", "value": i} for i in range(base_n)]
    cases = []
    for _ in range(docs):
        c_marks = random_changeset(rng, base_n, edits, move_p=0.35)
        overs, cur = random_trunk(rng, base, k_trunk, edits,
                                  move_p=0.35)
        cases.append((c_marks, overs, cur))

    c_stack = stack_changesets(
        [encode_changeset(c)[0] for c, _, _ in cases])
    trunk = TreeAtoms(*[
        np.stack([
            np.stack([encode_changeset(o)[0][f] for o in overs])
            for _, overs, _ in cases
        ])
        for f in ("kind", "pos", "n", "muted", "pos2")
    ])

    t0 = time.perf_counter()
    out = rebase_over_trunk(c_stack, trunk)  # warmup/compile
    np.asarray(out.kind)
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        time.sleep(cooldown)
        t0 = time.perf_counter()
        out = rebase_over_trunk(c_stack, trunk)
        np.asarray(out.kind)
        times.append(time.perf_counter() - t0)
    best = min(times)
    rebases = docs * k_trunk
    kernel_ops_s = rebases / best

    # parity: applied-state equality on sample docs (Forest-applied —
    # a rebased move is a paired del+rev, which bare walk_apply has no
    # repair store for)
    from fluidframework_tpu.models.tree.forest import Forest
    for d in range(min(4, docs)):
        c_marks, overs, cur = cases[d]
        change = {"root": c_marks}
        for o in overs:
            change = cs.rebase(change, {"root": o})
        fexp = Forest({"root": copy.deepcopy(cur)})
        fexp.apply(change, ("expect", d))
        expect = fexp.content().get("root", [])
        out_np = {f: np.asarray(getattr(out, f))[d]
                  for f in out._fields}
        content = encode_changeset(c_marks)[1]
        assert apply_atoms(cur, out_np, content) == expect, (
            f"config4 kernel/scalar divergence doc {d}"
        )

    # scalar python baseline on a sample
    sample = cases[:min(64, docs)]
    t0 = time.perf_counter()
    for c_marks, overs, _ in sample:
        change = {"root": c_marks}
        for o in overs:
            change = cs.rebase(change, {"root": o})
    scalar_t = time.perf_counter() - t0
    py_ops_s = len(sample) * k_trunk / scalar_t

    return {
        "docs": docs,
        "trunk_depth": k_trunk,
        "kernel_ops_per_sec": round(kernel_ops_s, 1),
        "cpp_baseline_ops_per_sec": None,
        "py_baseline_ops_per_sec": round(py_ops_s, 1),
        "real_ops": rebases,
        "best_window_time_s": round(best, 4),
        "compile_s": round(compile_s, 2),
        "window_times_s": [round(t, 4) for t in times],
        **_dist(times),
        "parity": "applied-state-verified x4",
        "unit": "rebases/s",
        # rebase_over_trunk has exactly one executor shape (lax.scan
        # over the trunk suffix); stamped for config14/config17-style
        # record comparability, not because there is a choice here
        "executor_route": "scan",
    }


def stage_config5(scale: str, reps: int, cooldown: float) -> dict:
    """BASELINE #5: full service pipeline replay at corpus scale — the
    ARRAY LANE. The corpus lives columnar (the ingress parses envelopes
    into per-channel numeric queues at the edge — demux OFF the hot
    loop); per round the pipeline does:

      1 native FFI call  — MultiDocSequencer.ticket_boxcar re-tickets
                           every document's message slice (deli,
                           lambdas/src/deli/lambda.ts boxcar shape);
      2 np.repeat + 2 scatters — stamp (seq, msn) onto the precomputed
                           op-row window (the only per-round host work);
      1 device dispatch  — apply_window over [docs, window].

    Host packing is double-buffered against the device for free: the
    dispatch returns at enqueue and the host immediately packs the
    next round; only the final round syncs. A per-round-synced pass
    afterwards records the round-latency percentiles. Scalar-Python
    pipeline baseline (per-op sequencer + scalar merge observers) on a
    subset, as before.

    SERVING ROUTE IS BACKEND-AWARE: on a TPU
    backend the merge apply is the XLA kernel (the batched device
    lane); on a host without an accelerator the product route is the
    native host tier — the same C++ engines the sidecar's eviction
    path serves from (MergeHostSession, merge_replay.cpp Session) —
    NOT an XLA CPU emulation of the device kernel. The r4 CPU number
    (0.52x scalar python) measured the latter; the host tier is the
    honest CPU pipeline."""
    import numpy as np

    from fluidframework_tpu.models.mergetree import MergeTreeClient
    from fluidframework_tpu.native.sequencer_core import (
        MultiDocSequencer,
    )
    from fluidframework_tpu.ops import (
        OpBatch,
        extract_text,
        fetch,
        make_table,
    )
    from fluidframework_tpu.ops.host_bridge import OP_FIELDS
    from fluidframework_tpu.ops.merge_kernel import apply_window
    from fluidframework_tpu.ops.segment_table import KIND_NOOP
    from fluidframework_tpu.protocol.messages import MessageType

    docs, base, steps, clients, capacity, apply_every = {
        "full": (16384, 16, 220, 4, 1024, 64),
        "cpu": (1024, 8, 120, 3, 512, 48),
        "smoke": (64, 4, 40, 2, 256, 20),
    }[scale]

    from fluidframework_tpu.native import (
        load_native_sequencer,
        native_build_error,
    )

    if load_native_sequencer() is None:
        # EVERY route tickets through the native boxcar sequencer; a
        # host with no C++ toolchain gets an explicit marker record
        # instead of a crash deep in MultiDocSequencer.__init__
        return {
            "docs": docs,
            "skipped": (
                "native sequencer unavailable: "
                f"{native_build_error() or 'toolchain missing'}"
            ),
        }

    raw, encoded = _build_streams(base, steps, clients, seed0=777)

    # ---- corpus prep (columnar; one-time, untimed) ------------------
    # per distinct stream: message-level ticket inputs + op-row content
    # grouped by message, then tiled across docs
    prep = []
    for enc, stream in zip(encoded, raw):
        msgs = [m for m in stream if m.type == MessageType.OPERATION]
        rows = [op for op in enc.ops if op["kind"] != KIND_NOOP]
        by_seq: dict[int, int] = {}
        for op in rows:
            by_seq[op["seq"]] = by_seq.get(op["seq"], 0) + 1
        counts = np.array([by_seq.get(m.sequence_number, 0)
                           for m in msgs], np.int64)
        assert counts.sum() == len(rows)
        cids = np.array([
            int(m.client_id.rsplit("-", 1)[1]) for m in msgs
        ], np.int64)
        csns = np.array([m.client_sequence_number for m in msgs],
                        np.int64)
        refs = np.array([m.reference_sequence_number for m in msgs],
                        np.int64)
        content = {
            f: np.array([op[f] for op in rows], np.int32)
            for f in OP_FIELDS
        }
        prep.append(dict(counts=counts, cids=cids, csns=csns,
                         refs=refs, content=content, enc=enc,
                         n_msgs=len(msgs), n_rows=len(rows)))

    max_msgs = max(p["n_msgs"] for p in prep)
    rounds = (max_msgs + apply_every - 1) // apply_every

    # per-round precomputed boxcar inputs + content windows + row maps.
    # Every round pads to ONE window width: apply_window compiles per
    # (docs, window) shape and a 20-40s on-chip compile per distinct
    # round width would eat the stage budget; one width = one compile.
    uniform_win = 0
    for r in range(rounds):
        m0, m1 = r * apply_every, (r + 1) * apply_every
        for p in prep:
            uniform_win = max(
                uniform_win, int(p["counts"][m0:m1].sum())
            )
    round_data = []
    for r in range(rounds):
        m0, m1 = r * apply_every, (r + 1) * apply_every
        doc_start = [0]
        cids_l, csns_l, refs_l, counts_l = [], [], [], []
        win = uniform_win
        for d in range(docs):
            p = prep[d % base]
            sl = slice(m0, min(m1, p["n_msgs"]))
            cids_l.append(p["cids"][sl])
            csns_l.append(p["csns"][sl])
            refs_l.append(p["refs"][sl])
            counts_l.append(p["counts"][sl])
            doc_start.append(doc_start[-1] + len(p["cids"][sl]))
        if doc_start[-1] == 0:
            break
        cids = np.concatenate(cids_l)
        counts = np.concatenate(counts_l)
        # flat destination indices for the row scatter
        row_in_doc = []
        doc_of_row = []
        content_win = {
            f: np.zeros((docs, max(win, 1)), np.int32)
            for f in OP_FIELDS
        }
        content_win["kind"][:] = KIND_NOOP
        for d in range(docs):
            p = prep[d % base]
            sl_counts = counts_l[d]
            n = int(sl_counts.sum())
            if n == 0:
                continue
            r0 = int(p["counts"][:m0].sum())
            for f in OP_FIELDS:
                content_win[f][d, :n] = p["content"][f][r0:r0 + n]
            row_in_doc.append(np.arange(n, dtype=np.int64))
            doc_of_row.append(np.full(n, d, np.int64))
        flat_dst = (
            np.concatenate(doc_of_row) * max(win, 1)
            + np.concatenate(row_in_doc)
        )
        round_data.append(dict(
            doc_start=np.array(doc_start, np.int64),
            cids=cids, csns=np.concatenate(csns_l),
            refs=np.concatenate(refs_l), counts=counts,
            content=content_win, flat_dst=flat_dst, win=max(win, 1),
        ))
    rounds = len(round_data)

    import jax as _jax

    from fluidframework_tpu.native import (
        load_merge_replay,
        merge_replay_error,
    )

    on_tpu = _jax.default_backend() == "tpu"
    # CPU product route = the native host tier. Without a working C++
    # toolchain load_merge_replay() is None — fall back to the XLA
    # pipeline on CPU and LABEL the record "emulation" instead of
    # dying inside MergeHostSession.__init__ (a missing g++ used to
    # kill the whole stage)
    use_host_tier = not on_tpu and load_merge_replay() is not None
    host_tier_error = None if (on_tpu or use_host_tier) else (
        merge_replay_error() or "host tier unavailable"
    )
    if use_host_tier:
        from fluidframework_tpu.native.replay_baseline import (
            MergeHostSession,
        )

        F_SEQ = OP_FIELDS.index("seq")
        F_MSN = OP_FIELDS.index("min_seq")
        for rd in round_data:
            # flat row-major [n_rows, 12] in per-doc sequenced order —
            # the host tier's natural layout (no padding lanes)
            win = rd["win"]
            doc_of_row = (rd["flat_dst"] // win).astype(np.int32)
            row_in_doc = (rd["flat_dst"] % win).astype(np.int64)
            flat = np.zeros(
                (len(doc_of_row), len(OP_FIELDS)), np.int32
            )
            for j, f in enumerate(OP_FIELDS):
                flat[:, j] = rd["content"][f][doc_of_row, row_in_doc]
            rd["flat_rows"] = np.ascontiguousarray(flat)
            rd["doc_of_row"] = doc_of_row

    def make_seqs():
        m = MultiDocSequencer(docs)
        for d in range(docs):
            for c in range(clients):
                m.join(d, c)
        return m

    def run_pipeline_host(sync_each_round: bool):
        """CPU serving route: native sequencer -> native merge tier.
        No device in the loop; `sync_each_round` only toggles the
        latency sampling (the tier is synchronous by nature)."""
        seqs = make_seqs()
        sess = MergeHostSession(docs)
        lat = []
        total = 0
        t0 = time.perf_counter()
        for rd in round_data:
            tr = time.perf_counter()
            seq, msn, status = seqs.ticket_boxcar(
                rd["doc_start"], rd["cids"], rd["csns"], rd["refs"]
            )
            assert not status.any(), "config5 unexpected nack"
            rows = np.array(rd["flat_rows"])  # copy: reused across reps
            rows[:, F_SEQ] = np.repeat(seq, rd["counts"])
            rows[:, F_MSN] = np.repeat(msn, rd["counts"])
            sess.apply(rows, rd["doc_of_row"])
            total += rows.shape[0]
            if sync_each_round:
                lat.append(time.perf_counter() - tr)
        return sess, total, time.perf_counter() - t0, lat

    # device-lane executor: the sidecar's backend-aware serving route
    # (chunked on launch-taxed backends, scan elsewhere;
    # FFTPU_SIDECAR_EXECUTOR overrides) — the pipeline stage must
    # measure the route serving actually takes, not just the kernel
    from fluidframework_tpu.service.tpu_sidecar import (
        CHUNK_K,
        default_executor,
    )

    route_executor = default_executor()
    if route_executor == "chunked":
        from fluidframework_tpu.ops.merge_chunk import (
            apply_window_chunked,
            build_chunked,
        )

    def _route_apply(table, arrays):
        if route_executor == "chunked":
            # chunk compile rides the host half of each round (the
            # sidecar's pack-time cost, reported via round latency)
            return apply_window_chunked(
                table, build_chunked(OpBatch(**arrays), K=CHUNK_K),
                K=CHUNK_K,
            )
        return apply_window(table, OpBatch(**arrays))

    def run_pipeline(sync_each_round: bool):
        seqs = make_seqs()
        table = make_table(docs, capacity)
        lat = []
        total = 0
        t0 = time.perf_counter()
        for rd in round_data:
            tr = time.perf_counter()
            seq, msn, status = seqs.ticket_boxcar(
                rd["doc_start"], rd["cids"], rd["csns"], rd["refs"]
            )
            assert not status.any(), "config5 unexpected nack"
            row_seq = np.repeat(seq, rd["counts"]).astype(np.int32)
            row_msn = np.repeat(msn, rd["counts"]).astype(np.int32)
            arrays = dict(rd["content"])
            sq = np.array(arrays["seq"])  # copy: reused across reps
            mq = np.array(arrays["min_seq"])
            sq.reshape(-1)[rd["flat_dst"]] = row_seq
            mq.reshape(-1)[rd["flat_dst"]] = row_msn
            arrays["seq"] = sq
            arrays["min_seq"] = mq
            table = _route_apply(table, arrays)
            total += len(row_seq)
            if sync_each_round:
                _sync(table)
                lat.append(time.perf_counter() - tr)
        _sync(table)
        return table, total, time.perf_counter() - t0, lat

    run = run_pipeline_host if use_host_tier else run_pipeline
    if not use_host_tier:
        run(False)  # warmup: compiles the window shapes
    times = []
    for _ in range(max(reps, 2)):
        time.sleep(cooldown)
        state, total_real, elapsed, _ = run(False)
        times.append(elapsed)
    best = min(times)
    state, _, _, lat = run(True)  # latency pass (per-round sync)
    table = None if use_host_tier else state

    # scalar-python pipeline baseline (per-op objects), sample docs
    from fluidframework_tpu.protocol.messages import ClientDetail
    from fluidframework_tpu.service.sequencer import DocumentSequencer

    t1 = time.perf_counter()
    scalar_ops = 0
    for d in range(min(docs, base)):
        seq_d = DocumentSequencer(f"scalar-{d}")
        obs = MergeTreeClient("obs")
        obs.start_collaboration("obs")
        for c in range(clients):
            seq_d.client_join(ClientDetail(f"client-{c}"))
        for msg in raw[d % base]:
            if msg.type != MessageType.OPERATION:
                continue
            from fluidframework_tpu.protocol.messages import (
                DocumentMessage,
            )

            res = seq_d.ticket(msg.client_id, DocumentMessage(
                client_sequence_number=msg.client_sequence_number,
                reference_sequence_number=(
                    msg.reference_sequence_number
                ),
                type=msg.type, contents=msg.contents,
            ))
            obs.apply_msg(res.message)
            scalar_ops += 1
    py_ops_s = scalar_ops / max(time.perf_counter() - t1, 1e-9)

    # parity: pipeline tip text vs scalar oracle replay (both routes)
    n_check = min(4, docs)
    if use_host_tier:
        np_table = None
    else:
        np_table = fetch(table)
        assert not np_table["overflow"].any(), "config5 overflow"
    for d in range(n_check):
        obs = MergeTreeClient("obs")
        obs.start_collaboration("obs")
        for msg in raw[d % base]:
            if msg.type == MessageType.OPERATION:
                obs.apply_msg(msg)
        if use_host_tier:
            got = state.text(d, prep[d % base]["enc"])
        else:
            got = extract_text(np_table, prep[d % base]["enc"], d)
        assert got == obs.get_text(), (
            f"config5 pipeline/oracle divergence doc {d}"
        )

    lat_ms = sorted(x * 1000 for x in lat)
    return {
        "docs": docs,
        "sessions": docs * clients,
        "rounds": rounds,
        "serving_route": (
            f"device-xla/{route_executor}" if on_tpu
            else "host-native-tier" if use_host_tier
            # XLA-on-CPU stand-in for the device kernel — NOT the
            # honest CPU product route (see r4: 0.52x scalar python)
            else f"emulation/{route_executor}"
        ),
        "dispatch_executor": route_executor,
        **({"host_tier_error": host_tier_error}
           if host_tier_error else {}),
        "pipeline_ops_per_sec": round(total_real / best, 1),
        "kernel_ops_per_sec": round(total_real / best, 1),
        "py_baseline_ops_per_sec": round(py_ops_s, 1),
        "cpp_baseline_ops_per_sec": None,
        "real_ops": total_real,
        "elapsed_s": round(best, 3),
        "elapsed_all_s": [round(t, 3) for t in times],
        **_dist(times),
        "round_latency_p50_ms": round(
            _pct(lat_ms, 0.5), 2) if lat_ms else None,
        "round_latency_p99_ms": round(
            _pct(lat_ms, 0.99), 2) if lat_ms else None,
        "parity": f"text-verified x{n_check}",
    }


def stage_config6(scale: str, reps: int, cooldown: float) -> dict:
    """Capacity-edge cliffs: the costs the steady
    state hides, measured — (a) per-apply latency while the slab fits,
    (b) the REGROW event (2x slab + full stream re-replay), (c) host
    EVICTION at the ladder top, (d) the evicted document's host-path
    read. Sized so the ladder + eviction are guaranteed to fire."""
    import numpy as np

    from fluidframework_tpu.drivers import LocalDocumentServiceFactory
    from fluidframework_tpu.loader import Container
    from fluidframework_tpu.service import LocalServer, TpuMergeSidecar

    docs, rounds, max_cap, chunk = {
        "full": (8, 220, 128, "abcdefghij"),
        "cpu": (4, 170, 128, "abcdefgh"),
        "smoke": (2, 80, 64, "abcdef"),
    }[scale]

    server = LocalServer()
    # pipeline=False: this stage ATTRIBUTES costs to individual rounds
    # (steady vs compact vs grow vs evict); the pipelined default
    # defers recovery to the next settle, which would smear an event's
    # cost into its successor round (config7 measures the pipeline)
    sidecar = TpuMergeSidecar(max_docs=docs, capacity=32,
                              max_capacity=max_cap, pipeline=False)
    # compile the whole capacity ladder up front (the regrow cliff
    # was an XLA-compile cliff; prewarm + the persistent cache turn a
    # warm regrow into ~one steady apply)
    prewarm_s = sidecar.prewarm()
    factory = LocalDocumentServiceFactory(server)
    sessions = []
    for d in range(docs):
        doc = f"doc-{d}"
        sidecar.subscribe(server, doc, "ds", "ch")
        c = Container.load(factory.create_document_service(doc),
                           client_id=f"w{d}")
        s = c.runtime.create_datastore("ds").create_channel(
            "sharedstring", "ch")
        sessions.append((c, s))

    steady_ms, compact_ms, grow_events, evict_events = [], [], [], []
    for i in range(rounds):
        for c, s in sessions:
            s.insert_text(0, chunk)
            c.flush()
            if i % 3 == 2 and s.get_length() > 6:
                s.remove_text(2, 5)
                c.flush()
        grows0, evicts0 = sidecar.grow_count, sidecar.evict_count
        compacting = (sidecar._applies + 1) % sidecar._compact_every == 0
        t0 = time.perf_counter()
        sidecar.apply()
        np.asarray(sidecar._table.count)  # force device completion
        ms = (time.perf_counter() - t0) * 1e3
        if sidecar.evict_count > evicts0:
            evict_events.append(ms)
        elif sidecar.grow_count > grows0:
            grow_events.append(ms)
        elif compacting:
            # the zamboni dispatch rides every Nth apply: report it as
            # its own population instead of poisoning the steady p95
            compact_ms.append(ms)
        else:
            steady_ms.append(ms)

    # parity after the full ladder + eviction
    for d, (c, s) in enumerate(sessions):
        assert sidecar.text(f"doc-{d}", "ds", "ch") == s.get_text(), (
            f"config6 divergence doc {d}"
        )
    # host-path read latency for an evicted doc
    t0 = time.perf_counter()
    _ = sidecar.text("doc-0", "ds", "ch")
    read_ms = (time.perf_counter() - t0) * 1e3

    steady = sorted(steady_ms)
    med = steady[len(steady) // 2] if steady else None
    cpt = sorted(compact_ms)
    return {
        "docs": docs,
        "rounds": rounds,
        "dispatch_executor": sidecar.executor,
        "pipeline": False,
        "prewarm_s": round(prewarm_s, 2),
        "steady_apply_ms_median": round(med, 2) if med else None,
        "steady_apply_ms_p95": round(
            steady[int(len(steady) * 0.95)], 2) if steady else None,
        "p50_ms": round(med, 2) if med else None,
        "p99_ms": round(
            steady[min(len(steady) - 1, int(len(steady) * 0.99))], 2
        ) if steady else None,
        "compact_rounds": len(cpt),
        "compact_ms_median": round(
            cpt[len(cpt) // 2], 2) if cpt else None,
        "compact_ms_max": round(cpt[-1], 2) if cpt else None,
        "grow_count": sidecar.grow_count,
        "grow_event_ms": [round(g, 1) for g in grow_events],
        "grow_vs_steady_ratio": round(
            max(grow_events) / med, 1) if grow_events and med else None,
        "evict_count": sidecar.evict_count,
        "evict_event_ms": [round(e, 1) for e in evict_events],
        "host_docs_after": sidecar.host_mode_docs(),
        "evicted_read_ms": round(read_ms, 2),
        "parity": f"text-verified x{docs}",
    }


def stage_config7(scale: str, reps: int, cooldown: float) -> dict:
    """Dispatch-pipeline overlap (the sidecar serving loop, measured):
    many docs x small per-round windows — the steady-state serving
    shape — driven through the REAL TpuMergeSidecar apply path under
    four configurations:

      pipelined      the serving default: backend-aware executor
                     route, vectorized pack, deferred settle (host
                     packs round N+1 while the device computes N)
      synced         same route, settle every round (per-round
                     latency percentiles come from this pass)
      other-route    the escape-hatch executor, synced (chunked vs
                     scan resolved per backend IN the record, not by
                     assertion)
      r5-route       scan + per-round sync + the r5 scalar
                     per-op-per-field pack loop — the faithful
                     round-5 serving baseline the speedup is against

    Pack/compute overlap is reported separately: ``host_pack_s`` (the
    host half) vs ``device_wait_s`` (time blocked in the settle
    boundary), plus the wall delta the deferred settle actually buys.
    """
    import numpy as np

    from fluidframework_tpu.models.mergetree import MergeTreeClient
    from fluidframework_tpu.ops.host_bridge import OP_FIELDS
    from fluidframework_tpu.ops.segment_table import KIND_NOOP
    from fluidframework_tpu.protocol.messages import MessageType
    from fluidframework_tpu.service import tpu_sidecar as sc_mod
    from fluidframework_tpu.service.tpu_sidecar import (
        TpuMergeSidecar,
        default_executor,
    )

    docs, base, steps, clients, capacity, round_ops = {
        "full": (2048, 16, 160, 3, 512, 8),
        "cpu": (256, 8, 96, 3, 256, 8),
        "smoke": (32, 4, 40, 2, 128, 8),
    }[scale]
    raw, encs = _build_streams(base, steps, clients, seed0=4100)
    rounds = (max(len(e.ops) for e in encs) + round_ops - 1) \
        // round_ops

    def legacy_pack(n_rows, ops_by_row, bucket_floor=16):
        """The r5 _pack_rows: nested per-op per-field Python loops
        with scalar stores (kept verbatim as the baseline's pack)."""
        window = max((len(v) for v in ops_by_row.values()), default=0)
        bucket = bucket_floor
        while bucket < window:
            bucket *= 2
        arrays = {f: np.zeros((n_rows, bucket), np.int32)
                  for f in OP_FIELDS}
        arrays["kind"][:] = KIND_NOOP
        for row, ops in ops_by_row.items():
            for w, op in enumerate(ops):
                for f in OP_FIELDS:
                    arrays[f][row, w] = op[f]
        return arrays

    def run(executor, pipeline, pack=None, sync_each_round=False):
        orig_pack = sc_mod._pack_rows
        if pack is not None:
            sc_mod._pack_rows = pack
        try:
            sidecar = TpuMergeSidecar(
                max_docs=docs, capacity=capacity,
                max_capacity=capacity * 4, executor=executor,
                pipeline=pipeline,
            )
            for d in range(docs):
                slot = sidecar.track(f"doc-{d}", "d", "s")
                # the canonical stream IS the encoded corpus stream
                # (payload table included); rounds feed its op slices
                # through the queue exactly as ingest would
                sidecar._streams[slot] = encs[d % base]
            total = 0
            lat = []
            t0 = time.perf_counter()
            for r in range(rounds):
                tr = time.perf_counter()
                lo, hi = r * round_ops, (r + 1) * round_ops
                for d in range(docs):
                    sl = encs[d % base].ops[lo:hi]
                    if sl:
                        sidecar._queued[d].extend(sl)
                total += sidecar.apply()
                if sync_each_round:
                    sidecar.sync()
                    lat.append(time.perf_counter() - tr)
            sidecar.sync()
            np.asarray(sidecar._table.count)  # transfer-forced
            return sidecar, total, time.perf_counter() - t0, lat
        finally:
            sc_mod._pack_rows = orig_pack

    executor = default_executor()
    other = "scan" if executor == "chunked" else "chunked"

    n_reps = max(2, reps // 2)

    def best_of(fn):
        # every route gets the SAME best-of-N + cooldown treatment:
        # comparing a best-of-N headline against single-shot baselines
        # would bias every ratio (vs_r5_route included) toward the
        # headline on any one-off GC/thermal hiccup
        best_w = None
        keep = None
        for _ in range(n_reps):
            time.sleep(min(cooldown, 2.0))
            out = fn()
            if best_w is None or out[2] < best_w:
                best_w, keep = out[2], out
        return keep

    _, _, warm_s, _ = run(executor, True)         # compile
    sidecar, total, best, _ = best_of(lambda: run(executor, True))
    sc_sync, _, wall_sync, lat = best_of(
        lambda: run(executor, False, sync_each_round=True))
    run(other, False)                             # compile other route
    _, _, wall_other, _ = best_of(lambda: run(other, False))
    _, _, wall_r5, _ = best_of(
        lambda: run("scan", False, pack=legacy_pack))

    assert sidecar.host_mode_docs() == 0, "config7 unexpected eviction"
    # parity: served text vs scalar oracle replay, both routes
    for d in range(min(4, base)):
        obs = MergeTreeClient("oracle")
        obs.start_collaboration("oracle")
        for msg in raw[d % base]:
            if msg.type == MessageType.OPERATION:
                obs.apply_msg(msg)
        want = obs.get_text()
        assert sidecar.text(f"doc-{d}", "d", "s") == want, (
            f"config7 pipeline/oracle divergence doc {d}")
        assert sc_sync.text(f"doc-{d}", "d", "s") == want, (
            f"config7 synced/oracle divergence doc {d}")

    lat_ms = sorted(x * 1000 for x in lat)
    pack_s = sidecar.stats["pack_s"]
    wait_s = sidecar.stats["settle_s"]
    # honest overlap accounting: the pipelined-vs-synced wall delta
    # mixes eliminated per-round sync overhead with genuinely hidden
    # pack time, so hidden pack is CAPPED at the total pack time (it
    # cannot exceed what there was to hide); the uncapped delta is
    # reported separately as what the deferred settle bought in toto
    sync_delta_s = max(0.0, wall_sync - best)
    pack_hidden_s = min(pack_s, sync_delta_s)
    return {
        "docs": docs,
        "rounds": rounds,
        "round_ops": round_ops,
        "dispatch_executor": executor,
        "pipeline_ops_per_sec": round(total / best, 1),
        "kernel_ops_per_sec": round(total / best, 1),
        "synced_ops_per_sec": round(total / wall_sync, 1),
        f"{other}_route_ops_per_sec": round(total / wall_other, 1),
        "r5_route_ops_per_sec": round(total / wall_r5, 1),
        "vs_r5_route": round(wall_r5 / best, 2),
        "real_ops": total,
        "best_wall_s": round(best, 3),
        "compile_run_s": round(warm_s, 2),
        # pack/compute overlap, separately reported: the host half,
        # the time actually blocked at the settle boundary (the
        # device-bound share of the pipelined wall), the total wall
        # the deferred settle bought, and the pack time hidden by it
        # (capped at host_pack_s — the delta also contains eliminated
        # sync overhead, which is NOT overlap)
        "host_pack_s": round(pack_s, 3),
        "device_wait_s": round(wait_s, 3),
        "device_bound_pct": round(100 * wait_s / best, 1),
        "sync_elimination_s": round(sync_delta_s, 3),
        "pack_hidden_s": round(pack_hidden_s, 3),
        "pack_hidden_pct": round(
            100 * pack_hidden_s / pack_s, 1) if pack_s else None,
        "round_latency_p50_ms": round(
            _pct(lat_ms, 0.5), 2) if lat_ms else None,
        "round_latency_p99_ms": round(
            _pct(lat_ms, 0.99), 2) if lat_ms else None,
        "p50_ms": round(_pct(lat_ms, 0.5), 2) if lat_ms else None,
        "p99_ms": round(_pct(lat_ms, 0.99), 2) if lat_ms else None,
        "parity": f"text-verified x{min(4, base)} x2 routes",
    }


def stage_config8(scale: str, reps: int, cooldown: float) -> dict:
    """Goodput vs offered load through the REAL ingress dispatch
    path, throttler ON vs OFF (the qos acceptance curve): mixed
    writer / slow-reader / summary traffic at 1x..10x the admission
    capacity, driven deterministically under a manual clock
    (tools/stress.run_overload — no sockets, no timing races).

    The claim this stage records: with the throttler, goodput
    PLATEAUS at capacity while memory stays bounded and admitted
    writers keep acking (graceful degradation); without it, the
    server "keeps up" only by letting per-session outbound depth (=
    memory) grow with the offered load — the collapse axis. Wall
    time per offered op is reported for both."""
    from fluidframework_tpu.tools.stress import (
        OverloadConfig,
        run_overload,
    )

    capacity, duration = {
        "full": (400.0, 4.0),
        "cpu": (200.0, 3.0),
        "smoke": (100.0, 1.0),
    }[scale]
    multiples = (1.0, 2.0, 5.0, 10.0)

    def sweep(throttle: bool) -> list[dict]:
        out = []
        for m in multiples:
            t0 = time.perf_counter()
            rep = run_overload(OverloadConfig(
                offered_multiple=m,
                capacity_ops_per_s=capacity,
                duration_s=duration,
                throttle=throttle,
                # the unprotected baseline gets an effectively
                # unbounded queue so the depth growth (the pre-qos
                # failure mode) is measurable, not masked by the
                # always-on slow-consumer bound
                outbound_depth=600 if throttle else 10 ** 7,
                outbound_soft=510 if throttle else 10 ** 7 - 1,
            ))
            wall = time.perf_counter() - t0
            out.append({
                "offered_multiple": m,
                "offered_ops": rep.offered_ops,
                "admitted_ops": rep.admitted_ops,
                "acked_ops": rep.acked_ops,
                "goodput_ops_per_sim_s": round(
                    rep.goodput_ops_per_s, 1),
                "throttle_nacks": rep.throttle_nacks,
                "shed": rep.shed,
                "outbound_dropped": rep.outbound_dropped,
                "peak_outbound_depth": rep.peak_outbound_depth,
                "max_pressure_tier": rep.max_pressure_tier,
                "wall_s": round(wall, 3),
                "wall_us_per_offered_op": round(
                    1e6 * wall / max(1, rep.offered_ops), 2),
            })
        return out

    throttled = sweep(True)
    baseline = sweep(False)
    # the headline: once saturated (>= 2x), throttled goodput is FLAT
    # — 10x offers 5x more than 2x yet goodput holds (plateau, not
    # collapse) — while the baseline's peak queue depth (= memory)
    # scales with the offered load
    g1 = throttled[1]["goodput_ops_per_sim_s"]
    g10 = throttled[-1]["goodput_ops_per_sim_s"]
    return {
        "capacity_ops_per_s": capacity,
        "duration_sim_s": duration,
        "multiples": list(multiples),
        "throttled": throttled,
        "unprotected": baseline,
        "goodput_plateau_ratio_10x_vs_2x": round(
            g10 / g1, 3) if g1 else None,
        "throttled_peak_depth_10x": throttled[-1][
            "peak_outbound_depth"],
        "unprotected_peak_depth_10x": baseline[-1][
            "peak_outbound_depth"],
        "kernel_ops_per_sec": g10,
        "deterministic": "manual clock, direct dispatch, no sockets",
    }


def stage_config9(scale: str, reps: int, cooldown: float) -> dict:
    """Open-loop serving benchmark, SLO-graded (ROADMAP item 5): a
    Poisson arrival process over the real ingress dispatch path with
    a mixed host-tier/sidecar route split, tens of thousands of
    sessions at full scale, qos on, deterministic under the manual
    clock (tools/serve_bench.py). Two load points:

      steady    ~0.8x capacity — every objective should hold
      overload  3x capacity — the latency + goodput objectives must
                BREACH (an SLO engine that can't see this overload
                isn't measuring anything)

    The stage also measures the continuous profiler's end-to-end
    cost honestly: the steady config runs profiler-off and
    profiler-on (best-of-N walls each), and the record carries the
    measured overhead — the <2% claim is a number here, not an
    assertion. Run-to-run determinism of the simulated plane is
    asserted between the two steady runs."""
    from fluidframework_tpu.tools.serve_bench import (
        ServeBenchConfig,
        run_serve_bench,
    )

    n_docs, readers, duration, capacity, sc_docs = {
        "full": (6000, 3, 6.0, 3000.0, 256),
        "cpu": (400, 3, 4.0, 600.0, 16),
        "smoke": (48, 2, 2.0, 200.0, 4),
    }[scale]

    def cfg(multiple: float, profile: bool,
            sidecar: bool = True) -> ServeBenchConfig:
        return ServeBenchConfig(
            n_docs=n_docs, readers_per_doc=readers,
            duration_s=duration, capacity_ops_per_s=capacity,
            offered_multiple=multiple, seed=90, profile=profile,
            sidecar_docs=sc_docs if sidecar else 0,
        )

    def record(rep) -> dict:
        return {
            "offered_ops": rep.offered_ops,
            "acked_ops": rep.acked_ops,
            "shed_ops": rep.shed_ops,
            "goodput_ops_per_sim_s": round(rep.goodput_ops_per_s, 1),
            "latency_p50_ms": round(rep.latency_p50_ms, 2)
            if rep.latency_p50_ms is not None else None,
            "latency_p99_ms": round(rep.latency_p99_ms, 2)
            if rep.latency_p99_ms is not None else None,
            "backlog_peak": rep.backlog_peak,
            "max_pressure_tier": rep.max_pressure_tier,
            "sessions": rep.sessions,
            "sidecar_rounds": rep.sidecar_rounds,
            "sidecar_ops": rep.sidecar_ops,
            "sidecar_round_p99_ms": round(rep.sidecar_round_p99_ms, 2)
            if rep.sidecar_round_p99_ms is not None else None,
            "route_split_sidecar": round(rep.route_split_sidecar, 4),
            "slo_report": rep.slo_report,
            "slo_breach_evaluations": rep.slo_breach_evaluations,
            "slo_breached_objectives": rep.slo_breached_objectives,
            "wall_s": round(rep.wall_s, 3),
        }

    # profiler overhead: best-of-N walls of the identical steady
    # config, off vs on (min-of-N filters one-off scheduler noise —
    # a single pair can easily read noise bigger than the signal)
    n_walls = max(2, reps // 2)
    off_runs = [run_serve_bench(cfg(0.8, False))
                for _ in range(n_walls)]
    on_runs = [run_serve_bench(cfg(0.8, True))
               for _ in range(n_walls)]
    wall_off = min(r.wall_s for r in off_runs)
    wall_on = min(r.wall_s for r in on_runs)
    overhead_pct = 100.0 * (wall_on - wall_off) / wall_off
    # the simulated plane must not care whether the profiler rode
    # along (or which repeat it was): bit-equal counts/verdicts
    for r in off_runs[1:] + on_runs:
        assert r.deterministic_fields() == \
            off_runs[0].deterministic_fields(), (
                "config9 determinism violation: "
                f"{r.deterministic_fields()} != "
                f"{off_runs[0].deterministic_fields()}")

    overload = run_serve_bench(cfg(3.0, False))
    steady_verdicts = {
        o["name"]: o["verdict"]
        for o in on_runs[0].slo_report["objectives"]
    }
    overload_verdicts = {
        o["name"]: o["verdict"]
        for o in overload.slo_report["objectives"]
    }
    # BOTH must see it: an unbounded open-loop backlog collapses p99
    # AND caps acked/offered at 1/3 — an objective blind to either
    # half (unobserved histogram, mis-snapped threshold) fails here
    assert overload_verdicts["goodput-floor"] == "breach" and \
        overload_verdicts["submit-ack-p99"] == "breach", (
            f"config9: 3x overload graded {overload_verdicts} — the "
            "SLO engine failed to see a real overload")

    steady = record(on_runs[0])
    prof = on_runs[0].profiler or {}
    return {
        "docs": n_docs,
        "sessions": on_runs[0].sessions,
        "duration_sim_s": duration,
        "capacity_ops_per_s": capacity,
        "steady": steady,
        "overload": record(overload),
        "steady_verdicts": steady_verdicts,
        "overload_verdicts": overload_verdicts,
        "slo_report": steady["slo_report"],
        "kernel_ops_per_sec": steady["goodput_ops_per_sim_s"],
        "profiler_overhead_pct": round(overhead_pct, 3),
        "profiler_overhead_under_2pct": overhead_pct < 2.0,
        "profiler_wall_off_s": round(wall_off, 3),
        "profiler_wall_on_s": round(wall_on, 3),
        "profiler_samples": prof.get("samples"),
        "profiler_by_component": prof.get("by_component"),
        "profiler_own_overhead_pct": prof.get("overhead_pct"),
        "deterministic": "manual clock, seeded poisson, "
                         f"x{2 * n_walls} steady runs bit-equal "
                         "(sim plane; overload is a different "
                         "config, run once)",
    }


def stage_config10(scale: str, reps: int, cooldown: float) -> dict:
    """Mesh-sharded pool scaling (ROADMAP item 1): docs/s vs shard
    count on the doc-sharded ``MeshShardedPool``, weak scaling — the
    PER-SHARD member population is fixed and shards are added, which
    is the capacity claim the pool makes (capacity scales with the
    mesh; per-chip throughput holds).

    EFFICIENCY BASIS, stated in the record: on the CPU backend the
    emulated devices of ``--xla_force_host_platform_device_count``
    execute essentially SERIALLY (measured ~k x wall at k shards for
    constant per-shard work), so wall-clock parallel speedup cannot
    exist on this backend by construction. What the emulation CAN
    measure — and what transfers to a real mesh, where shards run
    concurrently — is whether the PER-SHARD dispatch cost stays flat
    as shards are added (the shard_map body has no cross-shard
    collectives, so it should): scaling_efficiency =
    min(1, k * round_wall(1) / round_wall(k)). On a real TPU mesh the
    record instead reports measured-rate efficiency
    rate(k) / (k * rate(1)). Raw walls ride the record either way.

    A hot-spot phase then pins the MIGRATION route at the max shard
    count: one viral member heats its shard until a live migration
    fires, and every member's served text must stay bit-identical to
    a never-migrated single-shard pool fed the same streams.
    """
    import dataclasses

    import numpy as np

    from fluidframework_tpu.ops import DocStream, extract_text
    from fluidframework_tpu.parallel import make_mesh
    from fluidframework_tpu.service.tpu_sidecar import select_pool

    members_per_shard, rounds, ops_round, steps = {
        "full": (16, 40, 4, 80),
        "cpu": (8, 24, 4, 60),
        "smoke": (4, 10, 4, 40),
    }[scale]
    capacity = 128
    import jax

    devices = jax.devices()
    backend = jax.default_backend()
    shard_counts = [k for k in (1, 2, 4) if k <= len(devices)]
    kmax = shard_counts[-1]

    _, encs = _build_streams(
        members_per_shard * kmax, steps, clients=2, seed0=4200)

    def prefixed(n: int) -> tuple[list, list]:
        """Fresh DocStreams truncated to a base prefix + the full op
        lists to feed incrementally (payload/intern tables shared —
        read-only here)."""
        streams, fulls = [], []
        for i in range(n):
            enc = encs[i % len(encs)]
            full = list(enc.ops)
            base = max(8, len(full) - rounds * ops_round)
            streams.append(dataclasses.replace(
                enc, ops=list(full[:base])))
            fulls.append(full)
        return streams, fulls

    def feed(streams, fulls, per_member) -> bool:
        moved = False
        for i, stream in enumerate(streams):
            have = len(stream.ops)
            nxt = fulls[i][have:have + per_member]
            if nxt:
                stream.ops.extend(nxt)
                moved = True
        return moved

    def run_rate(k: int) -> tuple[float, float, int]:
        pool = select_pool(make_mesh(devices[:k]), capacity,
                           route="mesh")
        n = members_per_shard * k
        streams, fulls = prefixed(n)
        pool.admit(list(range(n)), streams)
        feed(streams, fulls, 1)          # warm the incremental shape
        pool.dispatch_pending(streams)
        t0 = time.perf_counter()
        done = 0
        for _ in range(rounds):
            if not feed(streams, fulls, ops_round):
                break
            pool.dispatch_pending(streams)
            done += 1
        np.asarray(pool._table.count)    # transfer-forced
        wall = time.perf_counter() - t0
        done = max(done, 1)
        return n * done / wall, wall / done, done

    def best_of(fn):
        best = None
        for _ in range(max(2, reps // 2)):
            time.sleep(min(cooldown, 2.0))
            out = fn()
            if best is None or out[1] < best[1]:
                best = out
        return best

    rate, round_wall, done = {}, {}, {}
    for k in shard_counts:
        run_rate(k)                      # compile
        rate[k], round_wall[k], done[k] = best_of(lambda k=k: run_rate(k))

    if backend == "cpu":
        basis = (
            "per-shard dispatch cost ratio min(1, k*wall(1)/wall(k)) "
            "— emulated CPU devices execute serially, so wall-clock "
            "parallel speedup cannot exist on this backend; flat "
            "per-shard cost is what transfers to a concurrent mesh"
        )
        eff = {
            k: min(1.0, k * round_wall[shard_counts[0]] / round_wall[k])
            for k in shard_counts
        }
    else:
        basis = "measured-rate efficiency rate(k) / (k * rate(1))"
        eff = {
            k: rate[k] / (k * rate[shard_counts[0]])
            for k in shard_counts
        }

    # ---- hot-spot migration phase + single-shard route parity ------
    n_par = members_per_shard * kmax - 1   # leaves one open row
    pool = select_pool(make_mesh(devices[:kmax]), capacity,
                       route="mesh")
    oracle = select_pool(make_mesh(devices[:1]), capacity,
                         route="mesh")
    streams, fulls = prefixed(n_par)
    pool.admit(list(range(n_par)), streams)
    oracle.admit(list(range(n_par)), streams)
    migr_rounds = 0
    while pool.migration_count == 0 and migr_rounds < 4 * rounds:
        # viral member 0 (hot shard 0, full) vs a trickle elsewhere
        feed(streams[:1], fulls[:1], 2 * ops_round)
        feed(streams[1:], fulls[1:], 1)
        pool.dispatch_pending(streams)
        oracle.dispatch_pending(streams)
        migr_rounds += 1
    assert kmax == 1 or pool.migration_count > 0, (
        "config10 hot-spot phase never migrated"
    )
    assert oracle.migration_count == 0
    fetched, o_fetched = pool.fetch(), oracle.fetch()
    for slot in range(n_par):
        got = extract_text(fetched, streams[slot], pool.row_of[slot])
        want = extract_text(
            o_fetched, streams[slot], oracle.row_of[slot])
        assert got == want, (
            f"config10 migration/single-shard divergence slot {slot}"
        )

    return {
        "shard_counts": shard_counts,
        "shard_count": kmax,
        "members_per_shard": members_per_shard,
        "pool_capacity": capacity,
        "incremental_ops_per_round": ops_round,
        "rounds": {str(k): done[k] for k in shard_counts},
        "docs_per_s_emulated": {
            str(k): round(rate[k], 1) for k in shard_counts},
        "round_ms": {
            str(k): round(round_wall[k] * 1000, 3)
            for k in shard_counts},
        "per_shard_round_ms": {
            str(k): round(round_wall[k] * 1000 / k, 3)
            for k in shard_counts},
        "scaling_efficiency": round(eff[kmax], 3),
        "scaling_efficiency_by_k": {
            str(k): round(eff[k], 3) for k in shard_counts},
        "efficiency_basis": basis,
        "efficiency_ok": eff[kmax] >= 0.7,
        "migrations_total": pool.migration_count,
        "migration_rounds": migr_rounds,
        "parity": f"text-verified x{n_par} vs single-shard pool "
                  "(hot-spot, migrated)",
    }


def stage_config11(scale: str, reps: int, cooldown: float) -> dict:
    """Robustness under chaos (docs/ROBUSTNESS.md): the seeded fault
    storm over the real AlfredServer dispatch path — steady phase,
    then the standard schedule armed at EVERY registered injection
    site, then recovery — reporting the goodput DIP during the storm
    and the RECOVERY TIME back to the steady SLO floor (>=95% rolling
    goodput held for a full window), both on the step clock, so
    robustness regressions show up as BENCH_* deltas next to
    metrics_delta/fluidlint_findings. A convergence leg runs two
    seeded schedules (one with a full crash-restart + torn state)
    against the fault-free oracle and asserts bit-equality — a bench
    round with a divergent chaos run must FAIL, not record it."""
    from fluidframework_tpu.testing.chaos import (
        crash_plan,
        run_chaos,
        run_chaos_storm,
    )

    steps, storm = {
        "full": (240, (80, 160)),
        "cpu": (120, (40, 80)),
        "smoke": (60, (20, 40)),
    }[scale]

    # --- storm leg: goodput dip + recovery time ----------------------
    # failsan rides the storm: every injected fault must map to an
    # observable signal (docs/ROBUSTNESS.md fault-to-signal
    # accounting) — a silent absorb fails the bench round by site
    from fluidframework_tpu.testing import failsan

    failsan.install()
    try:
        failsan.reset()
        t0 = time.perf_counter()
        storm_rep = run_chaos_storm(seed=11, steps=steps, storm=storm)
        storm_wall = time.perf_counter() - t0
        failsan.flush()
        fail_trips = failsan.trips()
        signal_coverage = failsan.signal_coverage()
    finally:
        failsan.reset()
        failsan.uninstall()
    assert storm_rep.converged, (
        f"config11 storm diverged: {storm_rep.failures}")
    assert not fail_trips and signal_coverage == 1.0, (
        "config11 fault-to-signal accounting failed:\n"
        + "\n".join(t.describe() for t in fail_trips))
    # run-to-run determinism on the step clock (config9 discipline)
    again = run_chaos_storm(seed=11, steps=steps, storm=storm)
    assert again.deterministic_fields() == \
        storm_rep.deterministic_fields(), (
            "config11 determinism violation: "
            f"{again.deterministic_fields()} != "
            f"{storm_rep.deterministic_fields()}")

    # --- convergence leg: seeded differential vs the oracle ----------
    oracle = run_chaos(0, faults=False)
    assert oracle.converged, oracle.failures
    diff = []
    # seed 3: odd => crash-restart, and crash_plan(3) tears the
    # checkpoint tmp — the torn-state leg the docstring promises
    for seed in (0, 3):
        rep = run_chaos(seed)
        assert rep.converged and \
            rep.alpha_text == oracle.alpha_text and \
            rep.beta_text == oracle.beta_text, (
                f"config11 convergence differential FAILED for seed "
                f"{seed} (reproduce: run_chaos({seed})): "
                f"{rep.failures}")
        diff.append({
            "seed": seed,
            "fired": len(rep.fired),
            "crashes": rep.crashes,
            "tear": rep.tear,
            "tear_applied": rep.tear_applied,
            "sidecar_tier": rep.sidecar_tier,
        })
    assert any(d["crashes"] for d in diff) and \
        any(d["tear_applied"] for d in diff), (
            "config11's crash seed must crash-restart WITH a torn "
            "state ACTUALLY applied "
            f"(crash_plan: {crash_plan(3, 40)}, runs: {diff})")

    return {
        "steps": steps,
        "storm_window": list(storm),
        "offered_ops": storm_rep.offered_ops,
        "acked_ops": storm_rep.acked_ops,
        "goodput_steady": round(storm_rep.goodput_steady, 4),
        "goodput_dip": round(storm_rep.goodput_dip, 4),
        "recovery_steps": storm_rep.recovery_steps,
        "recovery_time_s": storm_rep.recovery_time_s,
        "faults_fired": storm_rep.fired,
        "chaos_counts": storm_rep.chaos_counts,
        "signal_coverage": signal_coverage,
        "convergence_runs": diff,
        "kernel_ops_per_sec": round(
            storm_rep.acked_ops / max(storm_wall, 1e-9), 1),
        "wall_s": round(storm_wall, 3),
        "deterministic": "step clock, seeded schedule, x2 storm "
                         "runs bit-equal; convergence leg asserts "
                         "oracle equality (incl. one crash-restart)",
    }


def stage_config12(scale: str, reps: int, cooldown: float) -> dict:
    """Replicated-sequencer failover under chaos (ROADMAP item 3,
    docs/ROBUSTNESS.md "Replication & failover"): the config11 storm
    over the REPLICATED plane with the leader KILLED mid-storm —
    reporting ``failover_time_s`` (step clock from host loss to the
    first post-failover ack, measured off the fleet timeline) DECOMPOSED
    into ``failover_phases`` (detection / anti-entropy / promotion /
    first-ack — must sum to within one step of the headline number),
    the federated ``fleet_metrics`` snapshot, and ``repl_lag_max``
    next to ``goodput_dip``/``recovery_time_s``, x2 runs bit-equal. A
    convergence leg runs the kill-the-leader differential (one seed
    per enumerated kill mode: mid-batch, promotion under replication
    lag, deposed-leader fenced write) against the fault-free oracle
    and FAILS the round on any divergence."""
    from fluidframework_tpu.testing.chaos import (
        failover_plan,
        run_chaos,
        run_chaos_failover,
        run_chaos_storm,
    )

    steps, storm = {
        "full": (240, (80, 160)),
        "cpu": (120, (40, 80)),
        "smoke": (60, (20, 40)),
    }[scale]
    kill_step = sum(storm) // 2  # mid-storm: the interesting window

    # --- storm leg: failover time next to goodput dip ----------------
    t0 = time.perf_counter()
    storm_rep = run_chaos_storm(seed=12, steps=steps, storm=storm,
                                kill_leader_step=kill_step)
    storm_wall = time.perf_counter() - t0
    assert storm_rep.converged, (
        f"config12 storm diverged: {storm_rep.failures}")
    assert storm_rep.failover_time_s is not None and \
        storm_rep.failovers >= 1, (
            "config12's leader kill never failed over")
    # the causal decomposition (obs/timeline.py): the four phases
    # must reconcile with the headline number to within one step
    phases = storm_rep.failover_phases
    assert phases is not None, "kill ran but no failover_phases"
    phase_sum = (phases["detection_s"] + phases["anti_entropy_s"]
                 + phases["promotion_s"] + phases["first_ack_s"])
    assert abs(phase_sum - storm_rep.failover_time_s) <= 0.05 + 1e-9, (
        f"config12 failover_phases sum {phase_sum} does not "
        f"reconcile with failover_time_s "
        f"{storm_rep.failover_time_s} (phases: {phases})")
    assert storm_rep.fleet_metrics, (
        "config12 storm produced no federated fleet snapshot")
    again = run_chaos_storm(seed=12, steps=steps, storm=storm,
                            kill_leader_step=kill_step)
    assert again.deterministic_fields() == \
        storm_rep.deterministic_fields(), (
            "config12 determinism violation: "
            f"{again.deterministic_fields()} != "
            f"{storm_rep.deterministic_fields()}")

    # --- convergence leg: one seed per enumerated kill mode ----------
    oracle = run_chaos(0, faults=False)
    assert oracle.converged, oracle.failures
    # seeds 1/2/6: mid_batch, under_lag, deposed_race (failover_plan
    # is a pure function of the seed — asserted, not assumed)
    diff = []
    want_modes = {"mid_batch", "under_lag", "deposed_race"}
    for seed in (1, 2, 6):
        rep = run_chaos_failover(seed)
        assert rep.converged and \
            rep.alpha_text == oracle.alpha_text and \
            rep.beta_text == oracle.beta_text, (
                f"config12 failover differential FAILED for seed "
                f"{seed} (reproduce: run_chaos_failover({seed})): "
                f"{rep.failures}")
        diff.append({
            "seed": seed,
            "kill_mode": rep.kill_mode,
            "failovers": rep.failovers,
            "fenced_writes": rep.fenced_writes,
            "repl_lag_max": rep.repl_lag_max,
            "fired": len(rep.fired),
        })
    got_modes = {d["kill_mode"] for d in diff}
    assert got_modes == want_modes, (
        f"config12 kill-mode coverage: {got_modes} != {want_modes} "
        f"(failover_plan: {[failover_plan(s, 40) for s in (1, 2, 6)]})")
    deposed = [d for d in diff if d["kill_mode"] == "deposed_race"]
    assert deposed and deposed[0]["fenced_writes"] > 0, (
        "the deposed-leader seed must record fenced writes — the "
        "epoch fence refusing the split-brain candidate IS the test")

    return {
        "steps": steps,
        "storm_window": list(storm),
        "kill_leader_step": kill_step,
        "failover_time_s": storm_rep.failover_time_s,
        "failover_phases": storm_rep.failover_phases,
        "fleet_metrics": storm_rep.fleet_metrics,
        "failovers": storm_rep.failovers,
        "repl_lag_max": storm_rep.repl_lag_max,
        "offered_ops": storm_rep.offered_ops,
        "acked_ops": storm_rep.acked_ops,
        "goodput_steady": round(storm_rep.goodput_steady, 4),
        "goodput_dip": round(storm_rep.goodput_dip, 4),
        "recovery_steps": storm_rep.recovery_steps,
        "recovery_time_s": storm_rep.recovery_time_s,
        "faults_fired": storm_rep.fired,
        "chaos_counts": storm_rep.chaos_counts,
        "failover_runs": diff,
        "kernel_ops_per_sec": round(
            storm_rep.acked_ops / max(storm_wall, 1e-9), 1),
        "wall_s": round(storm_wall, 3),
        "deterministic": "step clock, seeded schedule, x2 "
                         "kill-leader storms bit-equal; failover "
                         "differential asserts oracle equality for "
                         "every enumerated kill mode",
    }


def stage_config13(scale: str, reps: int, cooldown: float) -> dict:
    """Partition tolerance under chaos (docs/ROBUSTNESS.md "Partition
    tolerance & degraded mode"): the config11 storm over the
    replicated plane with the LEADER PARTITIONED away from its quorum
    mid-storm (lease on its side: no election, pure quorum loss) —
    reporting ``unavailability_s`` (the degraded window on the step
    clock: writes nacked retriable-unavailable, reads clamped at the
    committed watermark) and ``degraded_read_s`` (until the first
    post-heal ack) next to ``goodput_dip``/``recovery_time_s``
    (config12's ``failover_time_s`` sibling numbers), x2 runs
    bit-equal. A convergence leg runs one seed per enumerated split
    mode (minority-leader election+fencing+rejoin, symmetric with
    grace-detach+rejoin, lease isolation, flap with a mid-split
    crash, wipe+rejoin) against the fault-free oracle and FAILS the
    round on any divergence — each seed also plants a mid-file
    bit-rot state the scrubber must read-repair."""
    from fluidframework_tpu.testing.chaos import (
        netsplit_plan,
        run_chaos,
        run_chaos_netsplit,
        run_chaos_storm,
    )

    steps, storm = {
        "full": (240, (80, 160)),
        "cpu": (120, (40, 80)),
        "smoke": (60, (20, 40)),
    }[scale]
    quarter = (storm[1] - storm[0]) // 4
    window = (storm[0] + quarter, storm[1] - quarter)

    # --- storm leg: unavailability window next to goodput dip --------
    # failsan rides the netsplit storm too: partition-era absorbs
    # (lag deferrals, ack retries, degraded nacks) must each leave a
    # visible mark or the round fails by site
    from fluidframework_tpu.testing import failsan

    failsan.install()
    try:
        failsan.reset()
        t0 = time.perf_counter()
        storm_rep = run_chaos_storm(seed=13, steps=steps, storm=storm,
                                    netsplit=window)
        storm_wall = time.perf_counter() - t0
        failsan.flush()
        fail_trips = failsan.trips()
        signal_coverage = failsan.signal_coverage()
    finally:
        failsan.reset()
        failsan.uninstall()
    assert storm_rep.converged, (
        f"config13 storm diverged: {storm_rep.failures}")
    assert not fail_trips and signal_coverage == 1.0, (
        "config13 fault-to-signal accounting failed:\n"
        + "\n".join(t.describe() for t in fail_trips))
    assert storm_rep.unavailability_s is not None and \
        storm_rep.unavailability_s > 0, (
            "config13's netsplit never entered degraded mode")
    assert storm_rep.degraded_read_s is not None and \
        storm_rep.degraded_read_s >= storm_rep.unavailability_s - 1e-9
    assert storm_rep.unavailable_nacks > 0
    assert storm_rep.failovers == 0, (
        "config13 is the no-election mode: the lease stays with the "
        "leader — a failover means the scenario drifted")
    again = run_chaos_storm(seed=13, steps=steps, storm=storm,
                            netsplit=window)
    assert again.deterministic_fields() == \
        storm_rep.deterministic_fields(), (
            "config13 determinism violation: "
            f"{again.deterministic_fields()} != "
            f"{storm_rep.deterministic_fields()}")

    # --- convergence leg: one seed per enumerated split mode ---------
    oracle = run_chaos(0, faults=False)
    assert oracle.converged, oracle.failures
    # seeds 0/1/2/3/7: minority_leader, symmetric, lease_isolated,
    # flap(+crash), wipe_rejoin(+crash) — netsplit_plan is a pure
    # function of the seed, asserted below, not assumed
    diff = []
    seeds = (0, 1, 2, 3, 7)
    for seed in seeds:
        rep = run_chaos_netsplit(seed)
        assert rep.converged and \
            rep.alpha_text == oracle.alpha_text and \
            rep.beta_text == oracle.beta_text, (
                f"config13 netsplit differential FAILED for seed "
                f"{seed} (reproduce: run_chaos_netsplit({seed})): "
                f"{rep.failures}")
        assert rep.scrub_repairs >= 1, (
            f"seed {seed}: the planted bit-rot state was never "
            "scrub-repaired — the leg went vacuous")
        diff.append({
            "seed": seed,
            "mode": rep.netsplit_mode,
            "partitions": rep.partitions,
            "unavailable_nacks": rep.unavailable_nacks,
            "degraded_s": rep.degraded_s,
            "rejoins": rep.rejoins,
            "scrub_repairs": rep.scrub_repairs,
            "fenced_writes": rep.fenced_writes,
            "fired": len(rep.fired),
        })
    got_modes = {d["mode"] for d in diff}
    from fluidframework_tpu.testing.chaos import SPLIT_MODES

    assert got_modes == set(SPLIT_MODES), (
        f"config13 split-mode coverage: {got_modes} != "
        f"{set(SPLIT_MODES)} (netsplit_plan: "
        f"{[netsplit_plan(s, 40)['mode'] for s in seeds]})")
    minority = [d for d in diff if d["mode"] == "minority_leader"]
    assert minority and minority[0]["fenced_writes"] > 0 and \
        minority[0]["rejoins"] >= 1, (
            "the minority-leader seed must record fenced writes AND "
            "a post-heal rejoin — the deposed leader staying fenced "
            "IS the test")

    return {
        "steps": steps,
        "storm_window": list(storm),
        "netsplit_window": list(window),
        "unavailability_s": storm_rep.unavailability_s,
        "degraded_read_s": storm_rep.degraded_read_s,
        "unavailable_nacks": storm_rep.unavailable_nacks,
        "offered_ops": storm_rep.offered_ops,
        "acked_ops": storm_rep.acked_ops,
        "goodput_steady": round(storm_rep.goodput_steady, 4),
        "goodput_dip": round(storm_rep.goodput_dip, 4),
        "recovery_steps": storm_rep.recovery_steps,
        "recovery_time_s": storm_rep.recovery_time_s,
        "faults_fired": storm_rep.fired,
        "chaos_counts": storm_rep.chaos_counts,
        "signal_coverage": signal_coverage,
        "netsplit_runs": diff,
        "kernel_ops_per_sec": round(
            storm_rep.acked_ops / max(storm_wall, 1e-9), 1),
        "wall_s": round(storm_wall, 3),
        "deterministic": "step clock, seeded schedule, x2 netsplit "
                         "storms bit-equal; netsplit differential "
                         "asserts oracle equality for every "
                         "enumerated split mode + scrub repair",
    }


def stage_config14(scale: str, reps: int, cooldown: float) -> dict:
    """Per-route single-chip executor comparison (ROADMAP item 1 /
    the egwalker PR): the REAL TpuMergeSidecar serving loop driven
    through all THREE executor routes — scan, chunked, egwalker — on
    three corpora chosen by their event-graph structure:

      sequential-heavy  fully-sequential multi-client editing (every
                        op critical: the walker's fast path and most
                        real traffic — testing.record_sequential_stream)
      concurrent-heavy  blind multi-client typing (process_weight
                        0.05: almost every op concurrent — the walker
                        degenerates to its scan suffix)
      mixed             the standard bench fuzz mix (process 0.15)
      remove_heavy      sequential editing at remove_weight 0.45 —
                        the corpus where committed-tombstone aging
                        boundaries land mid-span, so event-splitting
                        (ops/event_graph.py) is what keeps the span
                        chain short; span_splits_per_doc in the graph
                        stats is the direct evidence

    plus the scalar-Python and C++ -O2 proxy baselines on the same
    streams. Per corpus the record carries per-route ops/s, the
    event-graph sequentiality stats (critical fraction, walker spans
    per window vs chunked chunks — the kernel-launch count a
    launch-taxed backend pays), and parity is text-verified against
    the scalar oracle for every route. ALSO the current standing for
    the r3/r5 "1.18M ops/s ≈ 0.18x C++" single-chip number, which
    predates the pipelined dispatch and this route.

    ACCEPTANCE (CPU): the egwalker route must beat the chunked
    route's ops/s on the sequential-heavy corpus at equal batch —
    asserted below, not just recorded."""
    import numpy as np

    from fluidframework_tpu.models.mergetree import MergeTreeClient
    from fluidframework_tpu.ops import encode_stream
    from fluidframework_tpu.ops.event_graph import build_event_graph
    from fluidframework_tpu.ops.merge_chunk import (
        CHUNK_K,
        build_chunked,
    )
    from fluidframework_tpu.ops.host_bridge import OP_FIELDS
    from fluidframework_tpu.ops.segment_table import OpBatch
    from fluidframework_tpu.protocol.messages import MessageType
    from fluidframework_tpu.service.tpu_sidecar import (
        TpuMergeSidecar,
        default_executor,
        executor_flip,
    )
    from fluidframework_tpu.testing import (
        FuzzConfig,
        record_op_stream,
        record_sequential_stream,
    )

    docs, base, steps, clients, capacity, round_ops = {
        "full": (1024, 16, 120, 3, 512, 8),
        "cpu": (192, 8, 60, 3, 256, 8),
        "smoke": (32, 4, 30, 2, 128, 8),
    }[scale]

    def corpus_streams(kind: str):
        raw, encs = [], []
        for i in range(base):
            if kind == "sequential":
                _, stream = record_sequential_stream(
                    seed=14000 + i, n_clients=clients, n_steps=steps)
            elif kind == "remove_heavy":
                _, stream = record_sequential_stream(
                    seed=14300 + i, n_clients=clients, n_steps=steps,
                    remove_weight=0.45)
            elif kind == "concurrent":
                _, stream = record_op_stream(FuzzConfig(
                    n_clients=max(clients, 4), n_steps=steps,
                    seed=14100 + i, insert_weight=0.55,
                    remove_weight=0.25, annotate_weight=0.05,
                    process_weight=0.05,
                ))
            else:
                _, stream = record_op_stream(FuzzConfig(
                    n_clients=clients, n_steps=steps, seed=14200 + i,
                    insert_weight=0.55, remove_weight=0.25,
                    annotate_weight=0.05, process_weight=0.15,
                ))
            raw.append(stream)
            encs.append(encode_stream(stream))
        return raw, encs

    n_reps = max(2, reps // 2)

    def best_of(fn):
        best_w = None
        keep = None
        for _ in range(n_reps):
            time.sleep(min(cooldown, 2.0))
            out = fn()
            if best_w is None or out[2] < best_w:
                best_w, keep = out[2], out
        return keep

    def run(encs, executor):
        """config7's round-based serving drive, one route."""
        rounds = (max(len(e.ops) for e in encs) + round_ops - 1) \
            // round_ops
        sidecar = TpuMergeSidecar(
            max_docs=docs, capacity=capacity,
            max_capacity=capacity * 4, executor=executor,
        )
        for d in range(docs):
            slot = sidecar.track(f"doc-{d}", "d", "s")
            sidecar._streams[slot] = encs[d % base]
        total = 0
        t0 = time.perf_counter()
        for r in range(rounds):
            lo, hi = r * round_ops, (r + 1) * round_ops
            for d in range(docs):
                sl = encs[d % base].ops[lo:hi]
                if sl:
                    sidecar._queued[d].extend(sl)
            total += sidecar.apply()
        sidecar.sync()
        np.asarray(sidecar._table.count)  # transfer-forced
        return sidecar, total, time.perf_counter() - t0

    def graph_stats(encs):
        """Event-graph structure of the corpus at full-window width:
        critical fraction + walker spans vs chunked chunks per doc
        (the per-window kernel-launch counts)."""
        from fluidframework_tpu.ops.host_bridge import (
            coalesce_noops as _cn,
        )

        packed = [_cn(e.ops) for e in encs]
        W = max(len(p) for p in packed)
        arrays = {f: np.zeros((base, W), np.int32) for f in OP_FIELDS}
        arrays["kind"][:] = 3  # KIND_NOOP
        for d, ops in enumerate(packed):
            for f in OP_FIELDS:
                arrays[f][d, :len(ops)] = np.fromiter(
                    (op[f] for op in ops), np.int32, len(ops))
        program = build_event_graph(arrays)
        g = program["graph"]
        real = arrays["kind"] != 3
        crit = float((g.critical.astype(bool) & real).sum()
                     / max(real.sum(), 1))
        spans = (float(
            program["prefix"]["chunk_start"].sum() / base)
            if program["prefix"] is not None else 0.0)
        chunked = build_chunked(OpBatch(**arrays), K=CHUNK_K)
        chunks = float(chunked["chunk_start"].sum() / base)
        return {
            "critical_fraction": round(crit, 4),
            "walker_spans_per_doc": round(spans, 1),
            # events split (not broken into extra spans) at
            # min_seq-aging / committed-tombstone boundaries: each
            # split is exactly one span break absorbed, so with
            # splitting on, walker_spans_per_doc sits strictly BELOW
            # the pre-split count by this amount
            "span_splits_per_doc": round(
                float(program["span_splits"].sum() / base), 1),
            "chunked_chunks_per_doc": round(chunks, 1),
            "docs_with_concurrent_suffix": int(
                (g.prefix_len < np.int32(W)).sum()),
        }

    routes = ("scan", "chunked", "egwalker")
    record: dict = {
        "docs": docs,
        "streams": base,
        "round_ops": round_ops,
        "capacity": capacity,
        "executor_route": default_executor(),
        # the data-driven default decision AND its inputs (recorded
        # launches/window per route x the launch cost) — the flip is
        # auditable from the record alone, not a constant to trust
        "executor_flip": executor_flip(),
        "corpora": {},
    }
    kernel_best = 0.0
    for kind in ("sequential", "concurrent", "mixed", "remove_heavy"):
        raw, encs = corpus_streams(kind)
        per_route = {}
        sidecars = {}
        for route in routes:
            run(encs, route)  # compile pass
            sc, total, wall = best_of(lambda r=route: run(encs, r))
            sidecars[route] = sc
            per_route[route] = {
                "ops_per_sec": round(total / wall, 1),
                "real_ops": total,
                "best_wall_s": round(wall, 3),
            }
        # parity: every route serves the scalar oracle's text
        for d in range(min(4, base)):
            obs = MergeTreeClient("oracle")
            obs.start_collaboration("oracle")
            for msg in raw[d % base]:
                if msg.type == MessageType.OPERATION:
                    obs.apply_msg(msg)
            want = obs.get_text()
            for route in routes:
                got = sidecars[route].text(f"doc-{d}", "d", "s")
                assert got == want, (
                    f"config14 {kind}/{route} oracle divergence "
                    f"doc {d}")
        py_ops_s = _py_baseline(raw, seconds=1.0)
        cpp_ops_s, _ = _cpp_baseline(encs)
        record["corpora"][kind] = {
            "routes": per_route,
            "graph": graph_stats(encs),
            "python_baseline_ops_per_sec": round(py_ops_s, 1),
            "cpp_baseline_ops_per_sec": (
                round(cpp_ops_s, 1) if cpp_ops_s else None),
            "parity": f"text-verified x{min(4, base)} x3 routes",
        }
        kernel_best = max(
            kernel_best,
            max(r["ops_per_sec"] for r in per_route.values()))

    record["kernel_ops_per_sec"] = round(kernel_best, 1)
    seq = record["corpora"]["sequential"]["routes"]
    record["egwalker_vs_chunked_sequential"] = round(
        seq["egwalker"]["ops_per_sec"] / seq["chunked"]["ops_per_sec"],
        2)
    record["egwalker_vs_scan_sequential"] = round(
        seq["egwalker"]["ops_per_sec"] / seq["scan"]["ops_per_sec"], 2)
    import jax

    if jax.default_backend() == "cpu":
        # the PR's acceptance criterion, enforced per run
        assert seq["egwalker"]["ops_per_sec"] > \
            seq["chunked"]["ops_per_sec"], (
                "config14: the egwalker route must beat chunked on "
                f"the sequential-heavy corpus on CPU, got {seq}")
    # event-splitting acceptance: the remove-heavy corpus must
    # actually exercise splits (each one is a span break absorbed, so
    # a positive count == walker_spans_per_doc strictly lower than
    # the pre-split chain). Corpus-structural, so backend-independent.
    rh = record["corpora"]["remove_heavy"]["graph"]
    assert rh["span_splits_per_doc"] > 0, (
        "config14: remove-heavy corpus produced no event splits — "
        f"the span chain is not being split, got {rh}")
    return record


def stage_config15(scale: str, reps: int, cooldown: float) -> dict:
    """Pack-stage microbench (the wire-1.3 columnar ingress PR):
    host-side ops/s for the two submitOp ingest paths at three batch
    sizes, timed decode->lower->pack (frame parsing excluded — both
    forms arrive pre-parsed, exactly what the read loop hands the
    dispatcher):

      row decode  the 1.0-1.2 boxcar: per-op JSON -> DocumentMessage
                  -> sequence stamp -> DocStream._add_op dict rows ->
                  pack_rows' fromiter pass
      columnar    the 1.3 payload IS the column layout: validated
                  once, sliced to one [n, 12] int32 block
                  (host_bridge.lower_columns), pack_rows degrades to
                  array concatenation — zero per-op Python

    Pure host work: no jax, identical numbers on either backend.

    ACCEPTANCE (non-smoke): the columnar path must be >=5x the row
    path's ops/s at the largest batch size — asserted below, after a
    bit-identity differential proves both paths pack the same window.
    """
    import random

    import numpy as np

    from fluidframework_tpu.models.mergetree.ops import (
        InsertOp,
        RemoveOp,
    )
    from fluidframework_tpu.ops.host_bridge import (
        DocStream,
        OP_FIELDS,
        lower_columns,
        pack_rows,
    )
    from fluidframework_tpu.protocol.columnar import (
        encode_columns,
        validate_columns,
    )
    from fluidframework_tpu.protocol.constants import mark_batch
    from fluidframework_tpu.protocol.messages import (
        DocumentMessage,
        MessageType,
        SequencedMessage,
    )
    from fluidframework_tpu.service.ingress import (
        document_message_from_json,
        document_message_to_json,
    )

    total_ops = {"full": 131072, "cpu": 49152, "smoke": 4096}[scale]
    sizes = (8, 64, 512)

    def make_batch(n: int, seed: int):
        """One columnar-expressible batch (plain INSERT/REMOVE, one
        client, untraced) in BOTH wire forms, pre-built outside the
        timed region."""
        rng = random.Random(seed)
        ops, doc_len = [], 0
        for j in range(n):
            if doc_len >= 4 and rng.random() < 0.3:
                p = rng.randrange(doc_len - 2)
                op: object = RemoveOp(pos1=p, pos2=p + 2)
                doc_len -= 2
            else:
                text = "abcdefgh"[:2 + rng.randrange(6)]
                op = InsertOp(
                    pos1=rng.randrange(doc_len + 1), text=text)
                doc_len += len(text)
            # canonical batchManager marks (first/last) — required
            # for the batch to be columnar-expressible
            meta = None
            if n > 1 and j == 0:
                meta = mark_batch(None, True)
            elif n > 1 and j == n - 1:
                meta = mark_batch(None, False)
            ops.append(DocumentMessage(
                client_sequence_number=j + 1,
                reference_sequence_number=0,
                type=MessageType.OPERATION,
                contents=op,
                metadata=meta,
            ))
        cols = encode_columns(ops)
        assert cols is not None and cols["n"] == n, (
            "config15 generator left the columnar subset")
        rows = [document_message_to_json(op) for op in ops]
        return rows, cols

    def row_pack(rows, seq0: int = 1):
        stream = DocStream()
        for j, od in enumerate(rows):
            dm = document_message_from_json(od)
            stream.add_message(SequencedMessage(
                client_id="c0",
                sequence_number=seq0 + j,
                minimum_sequence_number=0,
                client_sequence_number=dm.client_sequence_number,
                reference_sequence_number=dm.reference_sequence_number,
                type=dm.type,
                contents=dm.contents,
                metadata=dm.metadata,
            ))
        return pack_rows(1, {0: stream.ops}), stream.payloads

    def col_pack(cols, seq0: int = 1):
        validate_columns(cols)
        block, payloads = lower_columns(cols, seq0=seq0, client=0)
        return pack_rows(1, {0: block}), payloads

    n_reps = max(2, reps)
    record: dict = {
        "total_ops_per_path": total_ops,
        "batch_sizes": list(sizes),
        "paths": {},
    }
    for n in sizes:
        rows, cols = make_batch(n, seed=15000 + n)
        # differential BEFORE timing: both paths must pack the same
        # window bit-for-bit (client "c0" interns to slot 0, matching
        # lower_columns' client=0)
        ra, rp = row_pack(rows)
        ca, cp = col_pack(cols)
        assert rp == cp, f"config15 n={n}: payload slices diverge"
        for f in OP_FIELDS:
            assert np.array_equal(ra[f], ca[f]), (
                f"config15 n={n}: packed field {f!r} diverges")
        iters = max(1, total_ops // n)

        def timed(fn, arg):
            best = None
            for _ in range(n_reps):
                time.sleep(min(cooldown, 0.2))
                t0 = time.perf_counter()
                for _ in range(iters):
                    fn(arg)
                wall = time.perf_counter() - t0
                best = wall if best is None else min(best, wall)
            return (iters * n) / best

        row_ops_s = timed(row_pack, rows)
        col_ops_s = timed(col_pack, cols)
        record["paths"][str(n)] = {
            "batches": iters,
            "row_decode_ops_per_sec": round(row_ops_s, 1),
            "columnar_ops_per_sec": round(col_ops_s, 1),
            "columnar_speedup": round(col_ops_s / row_ops_s, 2),
        }
    record["parity"] = (
        "bit-identical packed OP_FIELDS windows + payload slices "
        f"x{len(sizes)} batch sizes")
    top = record["paths"][str(sizes[-1])]
    record["kernel_ops_per_sec"] = top["columnar_ops_per_sec"]
    if scale != "smoke":
        # the PR's acceptance criterion, enforced per run
        assert top["columnar_speedup"] >= 5.0, (
            "config15: the columnar pack path must be >=5x row "
            f"decode at batch {sizes[-1]}, got {top}")
    return record


def stage_config16(scale: str, reps: int, cooldown: float) -> dict:
    """Heat & cost attribution (obs/heat.py): the same serve_bench
    sidecar slice with the attribution plane OFF and ON, so the
    plane's cost is a number and its output is pinned.

    Differentials BEFORE timing:

      x2 bit-equality  two attribution-on runs of one config must
                       agree on every deterministic field — heat
                       table top-k and attributed totals included
                       (the step attribution clock is what makes
                       the heat plane clock-independent).
      conservation     the per-document ledger total must equal the
                       aggregate heat_doc_ms_total counter delta to
                       float tolerance (two independent sums of the
                       same per-round charges).

    ACCEPTANCE (non-smoke): attribution overhead on the sidecar
    dispatch rounds — best-of-N summed round walls, on vs off —
    stays under 2%.
    """
    from fluidframework_tpu.tools.serve_bench import (
        ServeBenchConfig,
        run_serve_bench,
    )

    n_docs, duration, capacity, sc_docs, sc_steps = {
        "full": (256, 6.0, 1200.0, 64, 120),
        "cpu": (64, 4.0, 400.0, 16, 80),
        "smoke": (16, 2.0, 200.0, 4, 30),
    }[scale]

    def cfg(heat: bool) -> ServeBenchConfig:
        return ServeBenchConfig(
            n_docs=n_docs, readers_per_doc=2, duration_s=duration,
            capacity_ops_per_s=capacity, seed=160,
            sidecar_docs=sc_docs, sidecar_steps=sc_steps,
            heat=heat,
        )

    # --- x2 determinism differential (attribution on) ---------------
    r_on = run_serve_bench(cfg(heat=True))
    r_on2 = run_serve_bench(cfg(heat=True))
    assert r_on.deterministic_fields() == r_on2.deterministic_fields(), (
        "config16: same-seed attribution runs diverged — the heat "
        "plane leaked wall-clock into the deterministic fields"
    )
    assert r_on.heat_top_docs, (
        "config16 is vacuous: no device time was attributed")

    # --- conservation: ledger total vs aggregate counter ------------
    metric_ms = r_on.metrics_delta.get("heat_doc_ms_total", 0.0)
    err = abs(r_on.heat_attributed_ms - metric_ms)
    tol = 1e-6 * max(1.0, r_on.heat_attributed_ms)
    assert err <= tol, (
        f"config16: attributed device-time not conserved — ledger "
        f"sum {r_on.heat_attributed_ms} vs heat_doc_ms_total delta "
        f"{metric_ms} (err {err})"
    )

    # --- overhead: best-of-N summed sidecar round walls, on vs off --
    n_reps = max(3, reps)

    def best_wall(heat: bool) -> float:
        best = None
        for _ in range(n_reps):
            time.sleep(min(cooldown, 0.2))
            wall = run_serve_bench(cfg(heat=heat)).sidecar_rounds_wall_ms
            best = wall if best is None else min(best, wall)
        return best

    off_ms = best_wall(False)
    on_ms = best_wall(True)
    overhead = (on_ms - off_ms) / off_ms if off_ms > 0 else 0.0

    record = {
        "sidecar_rounds": r_on.sidecar_rounds,
        "sidecar_ops": r_on.sidecar_ops,
        "heat_top_docs": [
            [k, round(v, 6)] for k, v in r_on.heat_top_docs],
        "heat_top_tenants": [
            [k, round(v, 6)] for k, v in r_on.heat_top_tenants],
        "heat_attributed_ms": round(r_on.heat_attributed_ms, 6),
        "heat_doc_ms_total_delta": round(metric_ms, 6),
        "conservation_err_ms": round(err, 9),
        "parity": "x2 deterministic-field bit-equality (heat top-k "
                  "included) + ledger-vs-counter conservation",
        "rounds_wall_ms_off": round(off_ms, 3),
        "rounds_wall_ms_on": round(on_ms, 3),
        "attribution_overhead_pct": round(100.0 * overhead, 2),
        "kernel_ops_per_sec": round(
            r_on.sidecar_ops / (on_ms / 1000.0), 1)
        if on_ms > 0 else 0.0,
    }
    if scale != "smoke":
        assert overhead < 0.02, (
            f"config16: attribution overhead {overhead:.2%} >= 2% "
            f"(off {off_ms:.3f}ms, on {on_ms:.3f}ms)"
        )
    return record


def stage_config17(scale: str, reps: int, cooldown: float) -> dict:
    """Tree serving plane (service/tree_sidecar.py): SharedTree
    documents served doc-parallel through the sidecar's pipelined
    pack -> dispatch -> settle loop.

    Corpus: REAL service traffic — per document, concurrent writer
    containers author move-bearing changesets (testing/tree_fuzz's
    shared generator) through LocalServer's total order, and the
    captured sequenced streams are replayed into fresh TreeSidecars,
    the identical ingest feed a live subscription delivers.

    Differentials BEFORE timing, per executor route:
      - the served signature equals the scalar EditManager oracle on
        EVERY document (service-level end state, not kernel-level)
      - no document fell off the device path (host_mode_docs == 0)
      - non-smoke: the capacity grow ladder was exercised

    Metric: sequenced tree commits applied per second through the
    full ingest + dispatch + settle loop, per route, vs the scalar
    EditManager replaying the identical streams (vs_python).
    """
    import copy
    import random

    from fluidframework_tpu.drivers import LocalDocumentServiceFactory
    from fluidframework_tpu.loader import Container
    from fluidframework_tpu.models.tree.editmanager import (
        Commit,
        EditManager,
    )
    from fluidframework_tpu.ops.tree_apply import TREE_EXECUTOR_ROUTES
    from fluidframework_tpu.protocol.messages import MessageType
    from fluidframework_tpu.protocol.tree_payload import (
        tree_change_from_json,
    )
    from fluidframework_tpu.service import LocalServer, TreeSidecar
    from fluidframework_tpu.service.tree_sidecar import (
        default_tree_executor,
    )
    from fluidframework_tpu.testing.tree_fuzz import (
        random_change_with_moves,
    )

    docs, rounds, writers = {
        "full": (32, 24, 3),
        "cpu": (12, 12, 3),
        "smoke": (4, 6, 2),
    }[scale]
    rng = random.Random(1700)

    # --- corpus: real dispatch-loop traffic, captured per doc -------
    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    streams: dict[str, list] = {}
    for d in range(docs):
        doc = f"tree{d}"
        cap: list = []
        server.get_orderer(doc).broadcaster.subscribe(
            f"bench-capture/{doc}", cap.append)
        streams[doc] = cap
        c1 = Container.load(factory.create_document_service(doc),
                            client_id=f"{doc}-w0")
        t1 = c1.runtime.create_datastore("d").create_channel(
            "sharedtree", "t")
        c1.flush()
        conts = [(c1, t1)]
        for w in range(1, writers):
            cw = Container.load(factory.create_document_service(doc),
                                client_id=f"{doc}-w{w}")
            conts.append(
                (cw, cw.runtime.get_datastore("d").get_channel("t")))
        for rnd in range(rounds):
            # all writers author against the round-start state, THEN
            # the flushes race in shuffled order: every round carries
            # genuine concurrency for the device rebase to resolve
            for i, (c, t) in enumerate(conts):
                t.apply_changeset(random_change_with_moves(
                    rng, t.get_field(("root",)),
                    f"{doc}-r{rnd}w{i}"))
            order = list(conts)
            rng.shuffle(order)
            for c, _ in order:
                c.flush()
    commits = docs * rounds * writers

    def _changes_of(m):
        env = m.contents if isinstance(m.contents, dict) else {}
        if m.type != MessageType.OPERATION \
                or env.get("kind", "op") != "op" \
                or env.get("address") != "d" \
                or env.get("channel") != "t":
            return None
        return tree_change_from_json(env.get("contents"))

    def _sig(nodes) -> str:
        return json.dumps({"root": nodes}, sort_keys=True,
                          default=str)

    def oracle_replay() -> dict:
        sigs = {}
        for doc, msgs in streams.items():
            em = EditManager(session_id=f"oracle-{doc}")
            for m in msgs:
                changes = _changes_of(m)
                if changes is None:
                    continue
                em.add_sequenced_change(Commit(
                    m.client_id or "", m.sequence_number,
                    m.reference_sequence_number,
                    copy.deepcopy(changes)), False)
            sigs[doc] = _sig(em.forest().content().get("root", []))
        return sigs

    def sidecar_replay(route: str):
        sc = TreeSidecar(max_docs=docs, capacity=64,
                         max_capacity=512, executor=route)
        for doc in streams:
            sc.track(doc, "d", "t")
        sc.prewarm()
        length = max(len(v) for v in streams.values())
        t0 = time.perf_counter()
        for i in range(length):
            for doc, msgs in streams.items():
                if i < len(msgs):
                    sc.ingest(doc, msgs[i])
            # one dispatch round per authored round, doc-parallel:
            # docs x writers commits per packed window
            if (i + 1) % writers == 0:
                sc.apply()
        sc.apply()
        sc.sync()
        return sc, time.perf_counter() - t0

    # --- parity BEFORE timing ---------------------------------------
    expect = oracle_replay()
    grow_counts = {}
    for route in TREE_EXECUTOR_ROUTES:
        sc, _ = sidecar_replay(route)
        for doc in streams:
            got = sc.signature(doc, "d", "t")
            assert got == expect[doc], (
                f"config17 parity FAILED: route {route} diverged "
                f"from the scalar oracle on {doc}"
            )
        assert sc.host_mode_docs() == 0, (
            f"config17 vacuous on route {route}: "
            f"{sc.host_mode_docs()} doc(s) evicted off the device"
        )
        if scale != "smoke":
            assert sc.grow_count >= 1, (
                f"config17: route {route} never exercised the "
                "capacity grow ladder"
            )
        grow_counts[route] = sc.grow_count

    # --- timing ------------------------------------------------------
    n_reps = max(2, reps)

    def best_of(fn) -> float:
        best = None
        for _ in range(n_reps):
            time.sleep(min(cooldown, 0.2))
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    py_s = best_of(oracle_replay)
    route_s = {}
    for route in TREE_EXECUTOR_ROUTES:
        best = None
        for _ in range(n_reps):
            time.sleep(min(cooldown, 0.2))
            _, dt = sidecar_replay(route)
            best = dt if best is None else min(best, dt)
        route_s[route] = best

    default_route = default_tree_executor()
    kernel_s = route_s[default_route]
    return {
        "docs": docs, "rounds": rounds, "writers": writers,
        "commits": commits,
        "parity": "both routes == scalar EditManager oracle on every "
                  "doc (captured real service streams); "
                  "host_mode_docs == 0",
        "grow_count": grow_counts,
        "python_baseline_s": round(py_s, 4),
        "python_ops_per_sec": round(commits / py_s, 1),
        "route_ops_per_sec": {
            r: round(commits / s, 1) for r, s in route_s.items()},
        "kernel_ops_per_sec": round(commits / kernel_s, 1),
        "vs_python": round(py_s / kernel_s, 2),
        # comparability: the route a default-constructed TreeSidecar
        # serves with — route_ops_per_sec carries the full table
        "executor_route": default_route,
    }


STAGE_FNS = {
    "probe": stage_probe,
    "fuzz": stage_fuzz,
    "config1": stage_config1,
    "config2": stage_config2,
    "config3": stage_config3,
    "config4": stage_config4,
    "config5": stage_config5,
    "config6": stage_config6,
    "config7": stage_config7,
    "config8": stage_config8,
    "config9": stage_config9,
    "config10": stage_config10,
    "config11": stage_config11,
    "config12": stage_config12,
    "config13": stage_config13,
    "config14": stage_config14,
    "config15": stage_config15,
    "config16": stage_config16,
    "config17": stage_config17,
}


_FLUIDLINT_CACHE: dict | None = None
_FLUIDLINT_RAN = False


def _fluidlint_counts() -> dict | None:
    """Per-family fluidlint finding counts (post-suppression, split
    live vs allowlisted) — the finding TRAJECTORY, machine-readable
    alongside metrics_registry in every stage record. Computed once
    per stage process (the tree doesn't change mid-bench); None if
    the analyzer fails (a broken linter must not lose a measured
    stage)."""
    global _FLUIDLINT_CACHE, _FLUIDLINT_RAN
    if _FLUIDLINT_RAN:
        return _FLUIDLINT_CACHE
    _FLUIDLINT_RAN = True
    try:
        from fluidframework_tpu.analysis import core as lint

        allow = lint.load_allowlist()
        findings = lint.run_analysis(families=lint.FAMILIES)
        kept, _stale = lint.apply_allowlist(findings, allow)
        kept_ids = {id(f) for f in kept}
        out: dict = {
            fam: {"findings": 0, "allowlisted": 0}
            for fam in lint.FAMILIES
        }
        for f in findings:
            fam = lint.RULE_FAMILY.get(f.rule)
            if fam not in out:
                continue
            bucket = "findings" if id(f) in kept_ids else "allowlisted"
            out[fam][bucket] += 1
        _FLUIDLINT_CACHE = out
    except Exception:  # noqa: BLE001 - counts are best-effort
        _FLUIDLINT_CACHE = None
    return _FLUIDLINT_CACHE


def _wire_schema_hash() -> str | None:
    """Content hash of the WIRE_SCHEMA registry
    (protocol/constants.py) — rides every stage record next to
    fluidlint_findings so a cross-PR frame-schema change surfaces as
    a BENCH_* delta, not just as the WIRE_SCHEMA.json golden diff.
    None if protocol fails to import (best-effort, like the lint
    counts)."""
    try:
        from fluidframework_tpu.protocol.constants import (
            wire_schema_hash,
        )

        return wire_schema_hash()
    except Exception:  # noqa: BLE001 - the hash is best-effort
        return None


def _pack_path() -> str | None:
    """Which host pack path wire ingress can take in this build —
    "columnar+rows" when the submitOp registry entry carries the
    wire-1.3 "cols" field, "rows" otherwise. Rides every stage record
    next to wire_schema_hash/jax_compiles so a pack-path change
    surfaces as a BENCH_* delta. None if protocol fails to import
    (best-effort, like the hash)."""
    try:
        from fluidframework_tpu.protocol.constants import (
            wire_schema_fields,
        )

        fields = wire_schema_fields("submitOp")
        return "columnar+rows" if "cols" in fields else "rows"
    except Exception:  # noqa: BLE001 - the stamp is best-effort
        return None


def _registry_snapshot() -> dict | None:
    """The obs metrics registry, or None if obs failed to import (a
    broken registry must not lose a measured stage)."""
    try:
        from fluidframework_tpu.obs import metrics as _obs_metrics

        return _obs_metrics.REGISTRY.snapshot()
    except Exception:  # noqa: BLE001 - snapshot is best-effort
        return None


def _jax_compiles() -> dict | None:
    """Per-root XLA compile counts for this stage's process — the
    jitsan cache-size probe (testing/jitsan.py), which also advances
    ``jax_compiles_total{root}`` in the registry snapshot above. A
    recompile regression (an unladdered shape sneaking onto the
    serving path) shows up as a BENCH_* delta here, not just in the
    fluidlint gate. None if the probe fails (best-effort, like the
    lint counts)."""
    try:
        from fluidframework_tpu.testing import jitsan

        return jitsan.publish_compiles()
    except Exception:  # noqa: BLE001 - counts are best-effort
        return None


def run_stage(name: str, backend: str, scale: str, reps: int,
              cooldown: float, out_path: str | None) -> None:
    _stage_env_setup(backend, name)
    import jax

    t0 = time.perf_counter()
    result = STAGE_FNS[name](scale, reps, cooldown)
    # probe BEFORE the registry snapshot so the jax_compiles_total
    # counter it advances is visible in metrics_registry too
    jax_compiles = _jax_compiles()
    device = jax.devices()[0]
    result.update({
        "backend": jax.default_backend(),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "scale": scale,
        "corpus": STAGE_CORPUS.get(name),
        "stage_elapsed_s": round(time.perf_counter() - t0, 1),
        # the unified metrics registry's view of everything this
        # stage's process did (sidecar rounds, sequencer tickets,
        # pack/settle histograms...) — per-stage attribution comes
        # free because each stage runs in its own subprocess
        "metrics_registry": _registry_snapshot(),
        "fluidlint_findings": _fluidlint_counts(),
        "wire_schema_hash": _wire_schema_hash(),
        "pack_path": _pack_path(),
        "jax_compiles": jax_compiles,
    })
    # persist the full-scale result BEFORE the fixed-scale companion:
    # if the companion pushes the child past the subprocess timeout,
    # the completed result must not be lost (code-review r3)
    if out_path is None:
        # direct `--stage X` invocation without --out: the record goes
        # to stdout (running a stage for minutes then crashing on
        # open(None) would discard the measurement)
        print(json.dumps(result))
        return
    with open(out_path, "w") as f:
        json.dump(result, f)
    if scale == "full" and name != "probe":
        # fixed-size companion record at the reduced "cpu" scale dims,
        # on the same device, so trends stay readable across sizes
        t1 = time.perf_counter()
        fixed = STAGE_FNS[name]("cpu", max(1, reps // 2), 0.5)
        fixed["corpus"] = STAGE_CORPUS.get(name)
        fixed["stage_elapsed_s"] = round(time.perf_counter() - t1, 1)
        fixed["jax_compiles"] = _jax_compiles()
        fixed["metrics_registry"] = _registry_snapshot()
        fixed["fluidlint_findings"] = _fluidlint_counts()
        fixed["wire_schema_hash"] = _wire_schema_hash()
        fixed["pack_path"] = _pack_path()
        result["fixed_scale"] = fixed
        with open(out_path, "w") as f:
            json.dump(result, f)


# ======================================================================
# parent orchestration (stdlib only — must never touch jax)

def _spawn(stage: str, backend: str, scale: str, reps: int,
           cooldown: float, timeout: float) -> tuple[dict | None, str]:
    fd, out_path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    cmd = [
        sys.executable, os.path.abspath(__file__),
        "--stage", stage, "--backend", backend, "--scale", scale,
        "--reps", str(reps), "--cooldown", str(cooldown),
        "--out", out_path,
    ]
    def salvage(err):
        # run_stage persists the main result BEFORE the fixed-scale
        # companion; if the child died in the companion (timeout or
        # crash), the completed full-scale record is still on disk
        try:
            with open(out_path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            return None, err
        data["companion_failure"] = err
        return data, ""

    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if proc.returncode != 0:
            return salvage(f"rc={proc.returncode}: {proc.stderr[-800:]}")
        with open(out_path) as f:
            return json.load(f), ""
    except subprocess.TimeoutExpired:
        return salvage(
            f"timeout after {timeout:.0f}s (backend={backend})"
        )
    except (OSError, json.JSONDecodeError) as e:
        return None, f"{type(e).__name__}: {e}"
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass


def orchestrate(smoke: bool, stages: list[str], reps: int,
                cooldown: float | None, tpu_timeout: float,
                cpu_timeout: float) -> dict:
    """Run each stage once in its own subprocess: on the CPU at smoke
    scale under ``--smoke``, otherwise on the TPU at full scale. A
    failed stage is recorded in ``failures`` and has no record in
    ``stages`` — there is no retry on another backend."""
    results: dict[str, dict] = {}
    failures: dict[str, list[str]] = {}
    for stage in stages:
        if smoke:
            backend, scale, r, cd, tmo = "cpu", "smoke", 1, 0.2, cpu_timeout
        else:
            cd = cooldown if cooldown is not None else 20.0
            backend, scale, r, cd, tmo = "tpu", "full", reps, cd, \
                tpu_timeout
        got, err = _spawn(stage, backend, scale, r, cd, tmo)
        if got is not None:
            results[stage] = got
        else:
            failures[stage] = [f"{backend}/{scale}: {err}"]
    return {"stages": results, "failures": failures}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--stage", choices=STAGES)
    parser.add_argument("--backend", choices=("tpu", "cpu"),
                        default="tpu")
    parser.add_argument("--scale", choices=("full", "cpu", "smoke"),
                        default="full")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--cooldown", type=float, default=None)
    parser.add_argument("--out", default=None)
    parser.add_argument("--stages", default=None,
                        help="comma list (default: all)")
    parser.add_argument("--tpu-timeout", type=float, default=420.0)
    parser.add_argument("--cpu-timeout", type=float, default=420.0)
    args = parser.parse_args(argv)

    if args.stage:  # child mode
        run_stage(args.stage, args.backend, args.scale, args.reps,
                  args.cooldown if args.cooldown is not None else 0.5,
                  args.out)
        return

    stages = (args.stages.split(",") if args.stages else list(STAGES))
    detail = orchestrate(args.smoke, stages, args.reps, args.cooldown,
                         args.tpu_timeout, args.cpu_timeout)

    # correctness poisoning: a failed
    # correctness stage must flip the RUN's status — top-level flag
    # next to the headline AND a nonzero exit — never sit buried in
    # `failures` under rc 0 while the headline reads green.
    correctness_failures: list[str] = []
    fuzz_res = detail["stages"].get("fuzz")
    if "fuzz" in stages and (
        fuzz_res is None
        or fuzz_res.get("result") != "all-signatures-match"
    ):
        # missing entirely also poisons: a run with no fuzz evidence
        # cannot claim its kernel numbers are of a correct kernel
        correctness_failures.append("fuzz")
    for stage, attempts in detail["failures"].items():
        # an AssertionError is a kernel/parity divergence
        if stage in correctness_failures:
            continue
        if any("AssertionError" in a for a in attempts):
            correctness_failures.append(stage)
    for stage, res in detail["stages"].items():
        # salvage() keeps the main record when the fixed-scale
        # companion dies; a companion ASSERT is still a recorded
        # divergence and must poison the run like any other
        comp = res.get("companion_failure", "")
        if "AssertionError" in comp and stage not in \
                correctness_failures:
            correctness_failures.append(stage)

    # outside --smoke a stage that failed on the chip fails the run
    stage_failures = [] if args.smoke else sorted(detail["failures"])

    def emit(payload: dict) -> None:
        payload["correctness_failed"] = bool(correctness_failures)
        if correctness_failures:
            payload["correctness_failures"] = correctness_failures
        if stage_failures:
            payload["stage_failures"] = stage_failures
        print(json.dumps(payload))
        if correctness_failures or stage_failures:
            sys.exit(1)

    primary = detail["stages"].get("config2") or next(
        (v for k, v in detail["stages"].items()
         if "kernel_ops_per_sec" in v), None
    )
    if primary is None:
        if not detail["stages"] and not correctness_failures:
            # nothing at all ran — no evidence, poison the run (a
            # probe/fuzz-only invocation with green results is fine)
            correctness_failures.append("all-stages-failed")
        note = ("all stages failed" if not detail["stages"]
                else "no perf stage in this invocation")
        emit({
            "metric": "mergetree_batched_ops_per_sec",
            "value": 0,
            "unit": "ops/s",
            "vs_baseline": 0,
            "detail": {
                "error": note,
                **detail,
            },
        })
        return

    value = primary["kernel_ops_per_sec"]
    cpp = primary.get("cpp_baseline_ops_per_sec")
    py = primary.get("py_baseline_ops_per_sec")
    if cpp:
        vs = value / cpp
        baseline_kind = (
            "C++ -O2 scalar replay, same semantics/host (proxy for the "
            "reference's Node.js merge-tree; no Node in this image — "
            "V8 is bounded above by compiled C++ here, so this factor "
            "is conservative)"
        )
    else:
        vs = value / py if py else 0
        baseline_kind = "in-repo scalar Python replay (C++ unavailable)"
    emit({
        "metric": "mergetree_batched_ops_per_sec",
        "value": round(value, 1),
        "unit": "ops/s",
        "vs_baseline": round(vs, 2),
        "detail": {
            "baseline": baseline_kind,
            **detail,
        },
    })


if __name__ == "__main__":
    main()
