"""The four-chip relay cell on 4 CPU devices at its rehearsal sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

``relay.mesh4`` runs the sidecar's primary plane split by document over
the cell's chips; here each run is a child process with 4 virtual CPU
devices, so the mesh is the cell's. The check is the harness's: the
served answers against the plain reference, and the control in the
program's place reads incorrect.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run as bench_run

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
SEED = 3_000_000_023          # past 32 signed bits
CELL = "relay.mesh4"
READERS = ("merge_roofline.relay4", "device_idle_share.relay4",
           "place_ms_per_round.relay4", "shard_skew.relay4",
           "settle_ms_per_round.relay4", "pack_ms_per_round.relay4",
           "ticket_us_per_op.relay4", "ingest_us_per_op.relay4")


def run_on_four(*args: str) -> tuple[dict, dict]:
    """The cell's run in a child with 4 CPU devices: (result line, the
    record line run.py prints on stderr)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(SEED), "--seconds", "2", "--rehearsal", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    record = next(json.loads(line) for line in proc.stderr.splitlines()
                  if line.startswith('{"run"'))
    return json.loads(proc.stdout.strip().splitlines()[-1]), record


@pytest.fixture(scope="module")
def traced():
    return run_on_four("--trace", "1")


def test_rehearsal_on_four_devices_is_correct(traced):
    result, record = traced
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    assert record["chips"] == 4 and record["table_devices"] == 4
    assert len(record["memory_peak_per_device"]) == 4
    stats = record["sidecar_stats"]
    assert len(stats["shard_real_ops"]) == 4
    assert sum(stats["shard_real_ops"]) == stats["real_ops"] > 0
    assert record["compiles_in_window"]["compile_requests"] == 0


def test_control_is_not_correct_on_four_devices():
    result, _ = run_on_four("--control", "tip_view")
    assert result["correct"] is False
    assert result["checks"]["text_mismatch_docs"]["value"] > 0


def test_readers_give_a_number_or_nothing(traced):
    """On the CPU the trace is not reduced: the trace readers give
    nothing, the counter readers a number; a record without the keys
    the mesh traffic adds gives nothing."""
    result, record = traced
    assert {m: m in result["metrics"] for m in READERS} == {
        "merge_roofline.relay4": False, "device_idle_share.relay4": False,
        "place_ms_per_round.relay4": True, "shard_skew.relay4": True,
        "settle_ms_per_round.relay4": True,
        "pack_ms_per_round.relay4": True, "ticket_us_per_op.relay4": True,
        "ingest_us_per_op.relay4": True}
    assert result["metrics"]["shard_skew.relay4"]["value"] >= 1.0
    # the host layers in front of the mesh read the record's own keys
    for name in ("pack_ms_per_round.relay4", "ticket_us_per_op.relay4",
                 "ingest_us_per_op.relay4"):
        assert result["metrics"][name]["value"] > 0.0, name
    bare = {"loop": "open", "sidecar_rounds": 0, "settle_s": 0.0}
    for name in READERS:
        reader = bench_run.load_module(BENCH / "metrics" / f"{name}.py",
                                       name)
        assert reader.read(bare) is None, name


def test_roofline_divides_by_every_chip():
    reader = bench_run.load_module(
        BENCH / "metrics" / "merge_roofline.relay4.py", "roofline")
    rec = {"loop": "open", "chips": 4,
           "peak": {"hbm_bytes_per_s": 1e9},
           "traced_rounds": [{0: (1, 10)}],
           "trace": {"module_s": {"jit_run": 1e-6, "jit_other": 5.0}}}
    # 48 B x (2 x 10 segments + 1 op) over 4 x 1e9 B/s, in 1 us
    assert reader.read(rec) == pytest.approx(100.0 * 1008 / 4e9 / 1e-6)
    assert reader.read(dict(rec, chips=None)) is None
