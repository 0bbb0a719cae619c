"""The harness on the CPU at its rehearsal sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

What a cell's run does here is what it does on the chip, at the
configuration's tiny sizes: the same generators, the sidecar on the
CPU, the same check. Faults are planted in the program underneath and
must turn ``correct`` false.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import SliceTracer, Spans
from benchmark.sequential import TypingSessions
from benchmark.traffic import open_loop

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
SEED = 3_000_000_019          # past 32 signed bits, as the driver's are
CELLS = [c["name"] for c in bench_run.load_json(ROOT / "BENCHMARK.json")
         ["workloads"]]
# The replay cell waits for the egwalker fault (PERF.md §7, first row);
# its files are in benchmark/, and these are the entries that add it.
REPLAY = "replay1280.sequential"
REPLAY_ENTRIES = {
    "configs": [{"name": "string_replay_1280",
                 "source": "BASELINE.json configs[0]",
                 "file": "benchmark/configs/string_replay_1280.json",
                 "reduced": ["trace_length", "initial_text"],
                 "why": "replay-driver document loads"}],
    "workloads": [{"name": REPLAY, "config": "string_replay_1280",
                   "traffic": "sequential", "chips": 1,
                   "why": "closed loop, next 64 ops of 1280 docs a round"}],
    "end_to_end": [{"name": "replay_ops_per_s", "unit": "ops/s",
                    "better": "higher", "bound": 0.25,
                    "source": "host_clock", "workloads": [REPLAY]}],
    "per_layer": [{"name": f"{m}.replay", "unit": u, "better": "lower",
                   "source": "host_clock", "layer": m,
                   "moves": "replay_ops_per_s", "workloads": [REPLAY]}
                  for m, u in (("ingest_us_per_op", "us"),
                               ("pack_ms_per_round", "ms"),
                               ("settle_ms_per_round", "ms"),
                               ("device_idle_share", "%"),
                               ("merge_roofline", "%"))],
}
ALL_CELLS = CELLS + [REPLAY]


@pytest.fixture(autouse=True)
def with_replay_entries(monkeypatch):
    """BENCHMARK.json as a later PR that adds the replay cell has it."""
    real = bench_run.load_json

    def load_json(path):
        data = real(path)
        if Path(path).name == "BENCHMARK.json":
            for key, entries in REPLAY_ENTRIES.items():
                data[key] = data[key] + entries
        return data
    monkeypatch.setattr(bench_run, "load_json", load_json)


def run_cell(capsys, cell: str, *extra: str, seconds: str = "2") -> dict:
    rc = bench_run.main(["--workload", cell, "--seed", str(SEED),
                         "--seconds", seconds, "--rehearsal", *extra])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_every_cell_is_found_by_name():
    bench = bench_run.load_json(ROOT / "BENCHMARK.json")
    for cell in bench["workloads"]:
        wl = bench_run.load_json(BENCH / "workloads" / f"{cell['name']}.json")
        assert (wl["config"], wl["traffic"]) == (cell["config"],
                                                 cell["traffic"])
        cfg = bench_run.load_json(BENCH / "configs" / f"{wl['config']}.json")
        assert set(cfg["limits"]) and cfg["control"]
        assert (BENCH / "traffic" / f"{wl['generator']}.py").exists()
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            reader = bench_run.load_module(
                BENCH / "metrics" / f"{m['name']}.py", m["name"])
            assert callable(reader.read)
    names = {c["name"] for c in bench["configs"]}
    assert names == {c["config"] for c in bench["workloads"]}
    assert (bench_run.load_json(BENCH / "peaks.json")["devices"]
            ["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9)


def _open_traffic(seed: int):
    wl = bench_run.load_json(BENCH / "workloads" / "pages1k.typing.json")
    cfg = bench_run.load_json(BENCH / "configs" / "string_pages_1k.json")
    sizes = {**cfg["sizes"], **cfg["rehearsal"]}
    spans = Spans()
    t = open_loop.Traffic(cfg, wl, sizes, seed, 2.0, spans,
                          SliceTracer(False, "", spans, 0, 0, 0))
    t.schedule()
    t.record_sessions()
    return t


def test_generation_is_deterministic_per_seed():
    a, b, c = _open_traffic(SEED), _open_traffic(SEED), _open_traffic(SEED + 1)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.doc, b.doc)
    assert a.sessions == b.sessions
    assert not np.array_equal(a.due, c.due)
    assert len(a.due) == len(c.due)          # every seed: the same work
    params = bench_run.load_json(
        BENCH / "configs" / "string_replay_1280.json")["typing"]
    r1 = TypingSessions(SEED, 8, params).round(64)
    r2 = TypingSessions(SEED, 8, params).round(64)
    assert all(np.array_equal(x, y) for x, y in zip(r1, r2))


def test_reference_agrees_with_the_programs_scalar_oracle():
    """A second witness for the reference: the program's own merge-tree
    client, fed the same sequenced ops, serves the same text."""
    from fluidframework_tpu.models.mergetree import MergeTreeClient
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )
    from benchmark.harness import envelope

    t = _open_traffic(SEED)
    for sess in t.sessions:
        obs = MergeTreeClient("observer")
        obs.start_collaboration("observer")
        for client, csn, ref, seq, msn, op in sess:
            obs.apply_msg(SequencedMessage(
                client_id=f"c{client}", sequence_number=seq,
                minimum_sequence_number=msn, client_sequence_number=csn,
                reference_sequence_number=ref, type=MessageType.OPERATION,
                contents=envelope(op)["contents"]))
        ref_doc = open_loop.MergeDoc()
        for client, _csn, ref, seq, _msn, op in sess:
            ref_doc.apply(op, seq, ref, client)
        assert obs.get_text() == ref_doc.text()


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_rehearsal_run_is_correct(capsys, cell):
    result = run_cell(capsys, cell)
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"]
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_control_is_not_correct(capsys, cell):
    cfg = bench_run.load_json(BENCH / "configs" / (
        bench_run.load_json(BENCH / "workloads" / f"{cell}.json")["config"]
        + ".json"))
    result = run_cell(capsys, cell, "--control", cfg["control"])
    assert result["correct"] is False
    assert result["checks"]["text_mismatch_docs"]["value"] > 0


def _one_op_dropped(monkeypatch):
    """Each op reaches the sidecar when the next one does, so the run's
    last op never does (an early op can be erased by later removes)."""
    from fluidframework_tpu.protocol.messages import MessageType
    from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar

    real = TpuMergeSidecar.ingest
    held = []

    def ingest(self, document_id, msg):
        if msg.type != MessageType.OPERATION:
            return real(self, document_id, msg)
        if held:
            real(self, *held.pop())
        held.append((document_id, msg))
        return None
    monkeypatch.setattr(TpuMergeSidecar, "ingest", ingest)


def _state_unchanged(monkeypatch):
    from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar

    monkeypatch.setattr(TpuMergeSidecar, "_apply_program",
                        lambda self, table, program, dead=None: table)


def _half_the_batch(monkeypatch):
    from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar

    real = TpuMergeSidecar.ingest
    seen = []

    def ingest(self, document_id, msg):
        if msg.type.name == "OPERATION":
            seen.append(msg)
            if len(seen) % 2:
                return None           # every other op of the batch
        return real(self, document_id, msg)
    monkeypatch.setattr(TpuMergeSidecar, "ingest", ingest)


def _answer_altered(monkeypatch):
    from fluidframework_tpu.service import tpu_sidecar

    real = tpu_sidecar.extract_text

    def extract_text(table_np, stream, doc):
        text = real(table_np, stream, doc)
        return text[:-1] + ("a" if text[-1:] != "a" else "b") if doc == 0 \
            else text
    monkeypatch.setattr(tpu_sidecar, "extract_text", extract_text)


@pytest.mark.parametrize("fault", [_one_op_dropped, _state_unchanged,
                                   _half_the_batch, _answer_altered])
@pytest.mark.parametrize("cell", ALL_CELLS)
def test_fault_underneath_is_not_correct(capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    assert run_cell(capsys, cell)["correct"] is False


def _copy_checkout(dst: Path, with_program: bool) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if with_program:
        (dst / "fluidframework_tpu").symlink_to(ROOT / "fluidframework_tpu")


def _run(cwd: Path, *args: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--seed", str(SEED),
         "--seconds", "2", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=600)


def test_a_new_cell_runs_from_added_files_alone(tmp_path):
    _copy_checkout(tmp_path, with_program=True)
    wl = bench_run.load_json(BENCH / "workloads" / "pages1k.typing.json")
    wl.update(traffic="burst", rate_ops_per_s=wl["rate_ops_per_s"] * 2)
    (tmp_path / "benchmark/workloads/pages1k.burst.json").write_text(
        json.dumps(wl))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "pages1k.burst",
                               "config": "string_pages_1k",
                               "traffic": "burst", "chips": 1,
                               "why": "added by files alone"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "pages1k.typing" in m.get("workloads", []):
            m["workloads"].append("pages1k.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = _run(tmp_path, "--workload", "pages1k.burst", "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert "merged_p50_ms" in result["metrics"]


def test_without_a_tpu_the_run_refuses():
    proc = _run(ROOT, "--workload", CELLS[0])
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""


def test_without_the_program_the_run_refuses(tmp_path):
    _copy_checkout(tmp_path, with_program=False)
    proc = _run(tmp_path, "--workload", CELLS[0], "--rehearsal")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.xfail(strict=False, reason="egwalker fault, PERF.md section 7")
def test_egwalker_serves_replay_seed_5121_doc_950():
    """The program fault that keeps the replay cell out: in
    ``replay1280.sequential`` at seed 5121, document 950's fifth round
    types a run at 182..195 and then a second run at 182; the egwalker
    route serves 'lkqjtqm...' where the reference, the program's scan
    route and its scalar merge-tree client all give 'lkjztqm...'. One
    document alone, on the CPU, shows it."""
    from fluidframework_tpu.models.mergetree.ops import InsertOp, RemoveOp
    from fluidframework_tpu.ops.bucket_ladder import BucketLadder
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )
    from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar
    from benchmark.reference import SequentialDoc

    cfg = bench_run.load_json(BENCH / "configs" / "string_replay_1280.json")
    gen = TypingSessions(5121, 1280, cfg["typing"])
    log = [gen.round(64) for _ in range(5)]
    sc = TpuMergeSidecar(max_docs=1, capacity=1024, max_capacity=1024,
                         executor="egwalker",
                         ladder=BucketLadder(window_floor=64, max_bucket=64))
    sc.track("doc", "d", "s")
    ref = SequentialDoc()
    seq = 1
    for ins, pos, letter in log:
        for j in range(64):
            seq += 1
            p = int(pos[950, j])
            op = (InsertOp(pos1=p, text=str(letter[950, j])) if ins[950, j]
                  else RemoveOp(pos1=p, pos2=p + 1))
            ref.apply(("ins", p, 0, str(letter[950, j]), None) if ins[950, j]
                      else ("rem", p, p + 1, None, None))
            sc.ingest("doc", SequencedMessage(
                client_id="w", sequence_number=seq,
                minimum_sequence_number=seq - 1,
                client_sequence_number=seq - 1,
                reference_sequence_number=seq - 1,
                type=MessageType.OPERATION,
                contents={"kind": "op", "address": "d", "channel": "s",
                          "contents": op}))
        sc.apply()
    assert sc.text("doc", "d", "s") == ref.text()
