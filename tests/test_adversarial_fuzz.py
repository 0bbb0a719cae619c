"""Adversarial convergence scale-up:

1. 100+ seeded differential-fuzz runs of the batched kernel against
   the scalar oracle at larger scale (8 clients, 200+ steps,
   overlap-remove / annotate / marker storms, deep concurrency so msn
   boundary crossings happen constantly).
2. Directed regression scenarios transcribed from the behaviors the
   reference's merge-tree suites pin (packages/dds/merge-tree/src/
   test: tie-break insert storms, overlapping removes, annotate over
   concurrent remove, zamboni-boundary edits) — hand-written, not
   ported code.

Marked to run in CI; seeds are deterministic so failures repro.
"""
import pytest

from fluidframework_tpu.ops import (
    build_batch,
    encode_stream,
    extract_signature,
    extract_text,
    fetch,
    make_table,
)
from fluidframework_tpu.ops.merge_kernel import apply_window
from fluidframework_tpu.testing import (
    FuzzConfig,
    MockCollabSession,
    record_op_stream,
)
from fluidframework_tpu.models.mergetree import MergeTreeClient
from fluidframework_tpu.protocol.messages import MessageType


def run_kernel(streams, capacity=1024):
    encs = [encode_stream(s) for s in streams]
    batch = build_batch(encs)
    table = apply_window(make_table(len(encs), capacity), batch)
    return encs, fetch(table)


def oracle_replay(stream):
    obs = MergeTreeClient("oracle")
    obs.start_collaboration("oracle")
    for msg in stream:
        if msg.type == MessageType.OPERATION:
            obs.apply_msg(msg)
    return obs


def oracle_signature(obs, enc):
    from fluidframework_tpu.ops.host_bridge import interned_signature

    return interned_signature(obs, enc)


def check_stream(stream):
    encs, np_table = run_kernel([stream])
    obs = oracle_replay(stream)
    assert extract_text(np_table, encs[0], 0) == obs.get_text()
    assert extract_signature(np_table, encs[0], 0) == \
        oracle_signature(obs, encs[0])


# ----------------------------------------------------------------------
# 1. scale-up fuzz: 120 seeds across four adversarial mixes


def _smoke(n, keep):
    """range(n) with every seed outside ``keep`` slow-marked — tier-1
    runs a smoke subset of the sweep, the full sweep is slow-lane."""
    return [
        s if s in keep else pytest.param(s, marks=pytest.mark.slow)
        for s in range(n)
    ]

@pytest.mark.parametrize("seed", _smoke(40, {0, 1, 2, 3, 4}))
def test_fuzz_eight_clients_deep_concurrency(seed):
    _, stream = record_op_stream(FuzzConfig(
        n_clients=8, n_steps=220, seed=10_000 + seed * 13,
        insert_weight=0.45, remove_weight=0.3, annotate_weight=0.1,
        process_weight=0.15,
    ))
    check_stream(stream)


@pytest.mark.parametrize("seed", _smoke(30, {0, 1, 2, 3, 4}))
def test_fuzz_overlap_remove_storm(seed):
    """Remove-heavy with rare processing: most removes overlap
    concurrently (the overlapRemove bookkeeping,
    partialLengths.ts:125-135)."""
    _, stream = record_op_stream(FuzzConfig(
        n_clients=6, n_steps=200, seed=20_000 + seed * 7,
        insert_weight=0.3, remove_weight=0.55, annotate_weight=0.05,
        process_weight=0.1,
    ))
    check_stream(stream)


@pytest.mark.parametrize("seed", _smoke(30, {0, 1, 2, 3, 4}))
def test_fuzz_annotate_storm_with_insert_props(seed):
    _, stream = record_op_stream(FuzzConfig(
        n_clients=5, n_steps=200, seed=30_000 + seed * 11,
        insert_weight=0.35, remove_weight=0.15, annotate_weight=0.35,
        process_weight=0.15, insert_props_weight=0.5,
    ))
    check_stream(stream)


@pytest.mark.parametrize("seed", _smoke(20, {0, 1, 2, 3, 4}))
def test_fuzz_msn_boundary_churn(seed):
    """Heavy processing keeps the msn advancing through the op storm,
    so zamboni-eligible tombstones cross the window constantly."""
    _, stream = record_op_stream(FuzzConfig(
        n_clients=4, n_steps=250, seed=40_000 + seed * 3,
        insert_weight=0.4, remove_weight=0.25, annotate_weight=0.05,
        process_weight=0.3,
    ))
    check_stream(stream)


# ----------------------------------------------------------------------
# 2. directed regression scenarios (reference-suite behaviors)

def _session():
    log = []
    s = MockCollabSession(["A", "B", "C"], stream_log=log)
    return s, log


def test_directed_same_position_insert_storm():
    """Three clients insert at position 0 concurrently, twice over:
    later-sequenced wins the left slot at every tie (breakTie,
    mergeTree.ts:1705)."""
    s, log = _session()
    s.do("A", "insert_text_local", 0, "a1")
    s.do("B", "insert_text_local", 0, "b1")
    s.do("C", "insert_text_local", 0, "c1")
    s.process_all()
    s.do("A", "insert_text_local", 0, "a2")
    s.do("B", "insert_text_local", 0, "b2")
    s.do("C", "insert_text_local", 0, "c2")
    s.process_all()
    expected = s.assert_converged()
    check_stream(log)
    encs, np_table = run_kernel([log])
    assert extract_text(np_table, encs[0], 0) == expected


def test_directed_overlapping_removes_with_interleaved_insert():
    """A and B remove overlapping ranges while C inserts inside the
    doomed region (markRangeRemoved overlap tracking +
    insert-into-removed placement)."""
    s, log = _session()
    s.do("A", "insert_text_local", 0, "0123456789")
    s.process_all()
    s.do("A", "remove_range_local", 2, 8)
    s.do("B", "remove_range_local", 4, 10)
    s.do("C", "insert_text_local", 5, "XYZ")
    s.process_all()
    s.assert_converged()
    check_stream(log)


def test_directed_annotate_vs_concurrent_remove():
    """Annotate over a range another client concurrently removes: the
    annotation lands on tombstones and must not resurrect them."""
    s, log = _session()
    s.do("A", "insert_text_local", 0, "hello world")
    s.process_all()
    s.do("A", "annotate_range_local", 0, 11, {"bold": 1})
    s.do("B", "remove_range_local", 5, 11)
    s.process_all()
    s.assert_converged()
    check_stream(log)


def test_directed_insert_at_zamboni_boundary():
    """Edits target positions adjacent to below-msn tombstones: the
    insert walk's stop-eligibility must exclude them
    (mergeTree.ts:1003-1025 new length calculations)."""
    s, log = _session()
    s.do("A", "insert_text_local", 0, "abcdef")
    s.process_all()
    s.do("A", "remove_range_local", 0, 3)
    s.process_all()  # removal fully acked; msn advances past it
    s.do("B", "insert_text_local", 0, "B")  # before the tombstone run
    s.do("C", "insert_text_local", 3, "C")  # at the end
    s.process_all()
    s.assert_converged()
    check_stream(log)


def test_directed_remove_then_same_spot_insert_race():
    """B inserts into the middle of a range A removed concurrently;
    the insert survives inside the tombstone gap."""
    s, log = _session()
    s.do("A", "insert_text_local", 0, "0123456789")
    s.process_all()
    s.do("A", "remove_range_local", 3, 7)
    s.do("B", "insert_text_local", 5, "!!")
    s.do("C", "remove_range_local", 6, 9)
    s.process_all()
    s.assert_converged()
    check_stream(log)