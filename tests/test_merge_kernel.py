"""Differential tests: batched merge kernel vs scalar oracle on
identical sequenced streams (SURVEY §4, pillar (d)).

Every fuzz stream is applied both by a fresh oracle client (pure remote
apply) and by the kernel; final text and per-position property
signatures must match exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.models.mergetree import MergeTreeClient
from fluidframework_tpu.ops import (
    NOT_REMOVED,
    PROP_CHANNELS,
    SegmentTable,
    apply_window,
    build_batch,
    compact,
    encode_stream,
    extract_signature,
    extract_text,
    fetch,
    make_table,
)
from fluidframework_tpu.testing import FuzzConfig, record_op_stream


def oracle_replay(stream):
    """Fresh observer client applying the whole sequenced stream."""
    obs = MergeTreeClient("kernel-observer")
    obs.start_collaboration("kernel-observer")
    for msg in stream:
        obs.apply_msg(msg)
    return obs


def oracle_signature(obs, enc):
    """Observer's visible content with properties interned the same way
    the encoder interned them for the kernel."""
    from fluidframework_tpu.ops.host_bridge import interned_signature

    return interned_signature(obs, enc)


def run_kernel(streams, capacity=512):
    encs = [encode_stream(s) for s in streams]
    batch = build_batch(encs)
    table = make_table(len(encs), capacity)
    table = apply_window(table, batch)
    np_table = fetch(table)
    assert not np_table["overflow"].any(), "capacity overflow"
    return encs, np_table


def test_kernel_basic_insert_remove():
    from fluidframework_tpu.testing import MockCollabSession

    stream = []
    s = MockCollabSession(["A"], stream_log=stream)
    s.do("A", "insert_text_local", 0, "hello world")
    s.do("A", "remove_range_local", 5, 11)
    s.do("A", "insert_text_local", 5, "!")
    s.process_all()
    encs, np_table = run_kernel([stream])
    assert extract_text(np_table, encs[0], 0) == "hello!"


def test_kernel_concurrent_inserts_tiebreak():
    from fluidframework_tpu.testing import MockCollabSession

    stream = []
    s = MockCollabSession(["A", "B"], stream_log=stream)
    s.do("A", "insert_text_local", 0, "aaa")
    s.do("B", "insert_text_local", 0, "bbb")
    s.process_all()
    assert s.assert_converged() == "bbbaaa"
    encs, np_table = run_kernel([stream])
    assert extract_text(np_table, encs[0], 0) == "bbbaaa"


@pytest.mark.parametrize("seed", range(25))
def test_kernel_differential_fuzz(seed):
    text, stream = record_op_stream(FuzzConfig(
        n_clients=3, n_steps=120, seed=seed * 31 + 7,
        remove_weight=0.3, annotate_weight=0.15,
    ))
    encs, np_table = run_kernel([stream])
    assert extract_text(np_table, encs[0], 0) == text
    obs = oracle_replay(stream)
    assert extract_signature(np_table, encs[0], 0) == oracle_signature(
        obs, encs[0]
    )


def test_kernel_multidoc_batch():
    """Independent docs, one dispatch, padded window."""
    cases = [
        record_op_stream(FuzzConfig(n_clients=3, n_steps=80,
                                    seed=900 + i))
        for i in range(8)
    ]
    streams = [stream for _, stream in cases]
    encs, np_table = run_kernel(streams)
    for d, (text, _) in enumerate(cases):
        assert extract_text(np_table, encs[d], d) == text, f"doc {d}"


def test_kernel_compaction_preserves_content():
    text, stream = record_op_stream(FuzzConfig(
        n_clients=3, n_steps=150, seed=77, remove_weight=0.4,
    ))
    encs = [encode_stream(stream)]
    batch = build_batch(encs)
    table = make_table(1, 512)
    table = apply_window(table, batch)
    before = fetch(table)
    table = compact(table)
    after = fetch(table)
    assert extract_text(after, encs[0], 0) == text
    assert int(after["count"][0]) <= int(before["count"][0])
    # everything below the window is gone
    cnt = int(after["count"][0])
    removed = after["removed_seq"][0, :cnt]
    assert not ((removed != NOT_REMOVED)
                & (removed <= int(after["min_seq"][0]))).any()


@jax.jit
def _compact_by_argsort(table):
    """The argsort + gather formulation of ``compact``, kept as the
    oracle its gather-free form must match bit for bit."""

    def one(tab):
        j = jnp.arange(tab.capacity, dtype=jnp.int32)
        alive = j < tab.count
        drop = alive & (tab.removed_seq != NOT_REMOVED) & (
            tab.removed_seq <= tab.min_seq
        )
        keep = alive & ~drop
        src = jnp.argsort(~keep, stable=True)
        return tab._replace(
            length=tab.length[src],
            seq=tab.seq[src],
            client=tab.client[src],
            removed_seq=tab.removed_seq[src],
            removers=tab.removers[src],
            op_id=tab.op_id[src],
            op_off=tab.op_off[src],
            is_marker=tab.is_marker[src],
            prop=tab.prop[src],
            count=keep.sum().astype(jnp.int32),
        )

    return jax.vmap(one)(table)


def _compaction_cases(C: int, seed: int) -> SegmentTable:
    """One document per case: a random mix, count 0, count = C, no
    drops, all dropped, drops at the head, at the tail and in runs,
    and removals at min_seq (dropped) and min_seq + 1 (kept). Every
    other field is random over its whole range, removers with bit 31
    set, so a row that lands in the wrong slot shows."""
    rng = np.random.default_rng(seed)
    D = 9
    i32 = np.iinfo(np.int32)

    def rand(shape):
        return rng.integers(i32.min, i32.max, shape, dtype=np.int64,
                            endpoint=True).astype(np.int32)

    min_seq = rng.integers(10, 1000, D).astype(np.int32)
    ms = min_seq[:, None]
    dropped = ms - rng.integers(0, 10, (D, C))   # <= min_seq
    kept = ms + rng.integers(1, 10, (D, C))      # removed above it
    live = np.full((D, C), NOT_REMOVED, np.int64)
    removed = np.where(rng.random((D, C)) < 0.5, dropped,
                       np.where(rng.random((D, C)) < 0.5, kept, live))
    count = rng.integers(0, C + 1, D)
    j = np.arange(C)
    count[1] = 0
    count[2] = C
    removed[3] = np.where(removed[3] <= ms[3], kept[3], removed[3])
    count[3] = C
    removed[4] = dropped[4]
    count[4] = C
    k = rng.integers(1, C + 1)
    removed[5] = np.where(j < k, dropped[5], live[5])
    count[5] = C
    count[6] = rng.integers(1, C + 1)
    k = rng.integers(1, count[6] + 1)
    removed[6] = np.where((j >= count[6] - k) & (j < count[6]),
                          dropped[6], kept[6])
    runs = np.cumsum(rng.random(C) < 0.2) % 2 == 1  # alternating runs
    removed[7] = np.where(runs, dropped[7], live[7])
    removed[8] = ms[8] + rng.integers(0, 2, C)
    count[8] = rng.integers(C // 2, C + 1)
    removers = rng.integers(0, 2**32, (D, C), dtype=np.uint64)
    removers[:, ::3] |= 1 << 31
    return SegmentTable(
        length=jnp.asarray(rand((D, C))),
        seq=jnp.asarray(rand((D, C))),
        client=jnp.asarray(rand((D, C))),
        removed_seq=jnp.asarray(removed.astype(np.int32)),
        removers=jnp.asarray(removers.astype(np.uint32)),
        op_id=jnp.asarray(rand((D, C))),
        op_off=jnp.asarray(rand((D, C))),
        is_marker=jnp.asarray(rand((D, C))),
        prop=jnp.asarray(rand((D, C, PROP_CHANNELS))),
        count=jnp.asarray(count.astype(np.int32)),
        min_seq=jnp.asarray(min_seq),
        overflow=jnp.asarray(rng.integers(0, 2, D).astype(np.int32)),
    )


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("capacity", [16, 48, 64, 1024])
def test_compact_matches_argsort_form(capacity, seed):
    """The whole table, every field and every row past ``count`` too,
    equals the argsort + gather formulation bit for bit."""
    table = _compaction_cases(capacity, seed * 7919 + capacity)
    got, want = fetch(compact(table)), fetch(_compact_by_argsort(table))
    for f in SegmentTable._fields:
        assert got[f].dtype == want[f].dtype, f
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)
    # the cases reach what they are named for
    assert list(want["count"][[1, 4]]) == [0, 0]
    assert want["count"][3] == capacity


def test_compact_lowers_without_sort_or_gather():
    """Compaction moves rows by static lane shifts and selects: the
    lowered program holds no sort and no gather."""
    text = compact.lower(make_table(3, 48)).as_text()
    assert "stablehlo.sort" not in text
    assert "stablehlo.gather" not in text


def test_kernel_overflow_flag():
    text, stream = record_op_stream(FuzzConfig(n_clients=2, n_steps=60,
                                               seed=5))
    encs = [encode_stream(stream)]
    batch = build_batch(encs)
    table = make_table(1, 8)  # deliberately tiny
    table = apply_window(table, batch)
    assert int(fetch(table)["overflow"][0]) == 1
