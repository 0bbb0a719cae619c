"""The trace reduction and the byte count, on a small trace recorded
on the chip (``data/tiny.xplane.pb``: a rehearsal-size run of
``pages1k.typing`` with ``--trace 1`` on a TPU v5 lite, PR 22, kept
gzipped) and on
hand-made events."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from benchmark.roofline import RECORD_BYTES, round_bytes, roofline_pct
from benchmark.trace_reduce import reduce_events, reduce_slice, union

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_and_clips():
    assert union([(0, 2), (1, 3), (5, 6), (-1, 0.5)], 0, 5.5) == [[0, 3],
                                                                  [5, 5.5]]


def test_reduce_events_by_hand():
    devices = {"/device:TPU:0": {
        "ops": [("%w = while", 1.0, 2.5), ("%a = x", 1.0, 1.5),
                ("%b = y", 1.5, 2.5), ("%c", 4.0, 4.5)],
        "modules": [("jit_run(7)", 1.0, 2.5), ("jit_compact(9)", 4.0, 4.5)]}}
    host = [("bench:ingest", 0.0, 3.0), ("bench:apply", 3.0, 6.0),
            ("sidecar:dispatch", 3.5, 3.9)]
    r = reduce_events(devices, host)
    assert r["window_s"] == 6.0
    assert r["busy_s"] == pytest.approx(2.0)
    assert r["module_s"] == pytest.approx({"jit_run": 1.5, "jit_compact": 0.5})
    assert dict(r["device_ops"]) == pytest.approx(   # the while's body only
        {"jit_run/%a": 0.5, "jit_run/%b": 1.0, "jit_compact/%c": 0.5})
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"bench:ingest": 1.5, "bench:apply": 2.1,
                                  "sidecar:dispatch": 0.4})
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reduce_a_trace_recorded_on_the_chip(tmp_path):
    (tmp_path / "t").mkdir()
    (tmp_path / "t" / "tiny.xplane.pb").write_bytes(
        gzip.decompress((DATA / "tiny.xplane.pb.gz").read_bytes()))
    r = reduce_slice(str(tmp_path))
    assert 0 < r["busy_s"] < r["window_s"]
    merge = {"jit_run", "jit_apply_window_impl", "jit_compact"}
    assert merge <= set(r["module_s"])
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1]
    assert sum(v for _, v in r["device_ops"]) == pytest.approx(
        r["busy_s"], rel=0.02)


def test_round_bytes_and_share():
    docs = {0: (3, 10), 1: (0, 50), 2: (1, 1)}
    assert round_bytes(docs) == RECORD_BYTES * (2 * 10 + 3 + 2 * 1 + 1)
    peak = {"hbm_bytes_per_s": 1e9}
    share = roofline_pct([docs], {"jit_run": 1e-3, "jit_squeeze": 5.0},
                         ("jit_run",), peak)
    assert share == pytest.approx(100 * round_bytes(docs) / 1e9 / 1e-3)
    assert roofline_pct([docs], {"jit_squeeze": 1.0}, ("jit_run",),
                        peak) is None
