"""Egwalker route vs sequential executor: differential bit-equality of
the live slot state on identical fuzzed op windows.

The sequential scan (itself differential-fuzzed against the scalar
oracle and the C++ replayer) is the ground truth; the egwalker route
(ops/event_graph.py: the walker over each document's critical prefix,
the scan over its concurrent suffix) must reproduce its live rows
bit-for-bit (garbage rows beyond `count` may differ: the walker's row
merge parks different garbage than the scan's shift). These are the
route's largest sweeps; tests/test_event_graph.py owns the graph
semantics and the span compiler's break conditions."""
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.ops import build_batch, encode_stream, make_table
from fluidframework_tpu.ops.event_graph import (
    EG_K,
    PREFIX_FIELDS,
    _chunk_state,
    _chunk_unstate,
    _get_jit_pingpong,
    _walker_loop,
    apply_batch_egwalker,
    apply_suffix,
    apply_window_egwalker,
    build_event_graph,
)
from fluidframework_tpu.ops.merge_kernel import (
    apply_window,
    apply_window_impl,
    pad_capacity,
)
from fluidframework_tpu.ops.segment_table import (
    KIND_INSERT,
    KIND_NOOP,
    KIND_REMOVE,
    OpBatch,
)
from fluidframework_tpu.testing import FuzzConfig, record_op_stream

LIVE_FIELDS = (
    "length", "seq", "client", "removed_seq", "removers",
    "op_id", "op_off", "is_marker",
)


def smoke_seeds(n, keep):
    """range(n), with every seed outside ``keep`` slow-marked: the
    tier-1 lane (-m 'not slow') runs a cheap smoke subset of each
    differential sweep, the full sweep stays on the slow lane."""
    return [
        s if s in keep else pytest.param(s, marks=pytest.mark.slow)
        for s in range(n)
    ]


def assert_live_equal(seq_tab, eg_tab, ctx=""):
    ns, ne = {}, {}
    for f in seq_tab._fields:
        ns[f] = np.asarray(getattr(seq_tab, f))
        ne[f] = np.asarray(getattr(eg_tab, f))
    assert np.array_equal(ns["count"], ne["count"]), (
        f"{ctx}: count {ns['count']} vs {ne['count']}"
    )
    assert np.array_equal(ns["min_seq"], ne["min_seq"]), ctx
    assert np.array_equal(ns["overflow"], ne["overflow"]), ctx
    D = ns["count"].shape[0]
    for d in range(D):
        if ns["overflow"][d]:
            continue  # post-overflow application intentionally differs
        n = int(ns["count"][d])
        for f in LIVE_FIELDS:
            assert np.array_equal(ns[f][d, :n], ne[f][d, :n]), (
                f"{ctx}: doc {d} field {f}\n"
                f"seq:      {ns[f][d, :n]}\n"
                f"egwalker: {ne[f][d, :n]}"
            )
        assert np.array_equal(
            ns["prop"][d, :n], ne["prop"][d, :n]
        ), f"{ctx}: doc {d} props"


def run_both(streams, capacity=256):
    """Both routes on one window: returns (scan, egwalker)."""
    batch = build_batch([encode_stream(s) for s in streams])
    D = len(streams)
    seq_tab = apply_window_impl(make_table(D, capacity), batch)
    eg_tab = apply_batch_egwalker(make_table(D, capacity), batch)
    return seq_tab, eg_tab


@pytest.mark.parametrize("seed", smoke_seeds(30, {1, 2, 3}))
def test_differential_fuzz(seed):
    """Concurrent multi-client streams: the bread-and-butter gate."""
    _, stream = record_op_stream(FuzzConfig(
        n_clients=4, n_steps=90, seed=seed,
        insert_weight=0.55, remove_weight=0.25,
        annotate_weight=0.05, process_weight=0.15,
    ))
    seq_tab, eg_tab = run_both([stream])
    assert_live_equal(seq_tab, eg_tab, f"seed {seed}")


@pytest.mark.parametrize("seed", smoke_seeds(10, {2, 8}))
def test_differential_fuzz_heavy_process(seed):
    """High process weight => refseq advances often => many visible
    cross-client pairs, short critical runs and long concurrent
    suffixes; exactness must survive."""
    _, stream = record_op_stream(FuzzConfig(
        n_clients=3, n_steps=80, seed=1000 + seed,
        insert_weight=0.45, remove_weight=0.3,
        annotate_weight=0.1, process_weight=0.3,
    ))
    seq_tab, eg_tab = run_both([stream])
    assert_live_equal(seq_tab, eg_tab, f"hp seed {seed}")


@pytest.mark.parametrize("seed", smoke_seeds(10, {7, 8}))
def test_differential_fuzz_single_client_chain(seed):
    """One client typing+backspacing: every op is critical, so the
    whole window is walker spans (the host chain does all the
    position arithmetic)."""
    _, stream = record_op_stream(FuzzConfig(
        n_clients=1, n_steps=70, seed=2000 + seed,
        insert_weight=0.55, remove_weight=0.3,
        annotate_weight=0.1, process_weight=0.05,
    ))
    seq_tab, eg_tab = run_both([stream])
    assert_live_equal(seq_tab, eg_tab, f"chain seed {seed}")


@pytest.mark.parametrize("seed", smoke_seeds(8, {3, 5}))
def test_differential_fuzz_multidoc(seed):
    """Several docs with different shapes share one dispatch; per-doc
    cursors advance independently."""
    streams = []
    for i in range(5):
        _, s = record_op_stream(FuzzConfig(
            n_clients=1 + (seed + i) % 4, n_steps=30 + 10 * i,
            seed=3000 + 10 * seed + i,
            insert_weight=0.5, remove_weight=0.25,
            annotate_weight=0.1, process_weight=0.15,
        ))
        streams.append(s)
    seq_tab, eg_tab = run_both(streams)
    assert_live_equal(seq_tab, eg_tab, f"multidoc seed {seed}")


def _raw(ops_rows):
    """[1, W] OpBatch field arrays for one doc from raw op dicts."""
    base = dict(kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0,
                client=0, op_id=0, length=0, is_marker=0,
                prop_key=0, prop_val=0, min_seq=0)
    rows = [dict(base, **r) for r in ops_rows]
    arrs = {
        f: np.zeros((1, len(rows)), np.int32) for f in OpBatch._fields
    }
    for w, r in enumerate(rows):
        for f in OpBatch._fields:
            arrs[f][0, w] = r[f]
    return arrs


def _spans(rows):
    """The walker's span starts over the window's critical prefix."""
    program = build_event_graph(_raw(rows))
    starts = program["prefix"][PREFIX_FIELDS.index("chunk_start")]
    return starts[0].tolist()[:len(rows)]


def _run_raw(rows, capacity=64):
    batch = OpBatch(**_raw(rows))
    seq_tab = apply_window_impl(make_table(1, capacity), batch)
    eg_tab = apply_batch_egwalker(make_table(1, capacity), batch)
    return seq_tab, eg_tab


@pytest.mark.slow
def test_same_client_typing_burst_is_one_span():
    """abcdef typed one char at a time: one span, one walker step."""
    rows = [
        dict(kind=KIND_INSERT, pos1=i, seq=i + 1, refseq=0,
             client=0, op_id=i, length=1)
        for i in range(6)
    ]
    assert _spans(rows) == [1, 0, 0, 0, 0, 0]
    seq_tab, eg_tab = _run_raw(rows)
    assert_live_equal(seq_tab, eg_tab, "typing burst")
    # the whole burst resolves to six slots in order
    assert int(np.asarray(eg_tab.count)[0]) == 6


def test_backspace_run_stays_one_span():
    """Type 4 chars then backspace 2: own-chain removes compose."""
    rows = [
        dict(kind=KIND_INSERT, pos1=i, seq=i + 1, refseq=0,
             client=0, op_id=i, length=1)
        for i in range(4)
    ] + [
        dict(kind=KIND_REMOVE, pos1=3, pos2=4, seq=5, refseq=0,
             client=0),
        dict(kind=KIND_REMOVE, pos1=2, pos2=3, seq=6, refseq=0,
             client=0),
    ]
    assert _spans(rows) == [1, 0, 0, 0, 0, 0]
    seq_tab, eg_tab = _run_raw(rows)
    assert_live_equal(seq_tab, eg_tab, "backspace run")


@pytest.mark.slow
def test_concurrent_same_position_inserts_order():
    """Two blind clients at position 0: later sequenced lands left
    (breakTie: sequenced seq exceeds slot seq)."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=2),
        dict(kind=KIND_INSERT, pos1=0, seq=2, refseq=0, client=1,
             op_id=1, length=3),
        dict(kind=KIND_INSERT, pos1=0, seq=3, refseq=0, client=2,
             op_id=2, length=1),
    ]
    seq_tab, eg_tab = _run_raw(rows)
    assert_live_equal(seq_tab, eg_tab, "same-pos storm")


def test_cross_client_visible_dependency_breaks_span():
    """Client 1 saw client 0's insert (refseq >= its seq) and inserts
    strictly inside its text: every op is critical, but an anchor
    inside another in-span op's text breaks the span. It must still
    converge bit-identically."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=4),
        dict(kind=KIND_INSERT, pos1=2, seq=2, refseq=1, client=1,
             op_id=1, length=2),
        dict(kind=KIND_REMOVE, pos1=1, pos2=3, seq=3, refseq=2,
             client=0),
    ]
    assert _spans(rows)[:2] == [1, 1]
    seq_tab, eg_tab = _run_raw(rows)
    assert_live_equal(seq_tab, eg_tab, "cross visible")


def test_remove_then_insert_at_tombstone_boundary():
    """Insert lands exactly at an own fresh tombstone: breakTie puts
    it BEFORE the removed text. No span assertion: where the walker
    breaks here is the span compiler's business
    (tests/test_event_graph.py), the placement is this test's."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=6),
        dict(kind=KIND_REMOVE, pos1=2, pos2=4, seq=2, refseq=1,
             client=0),
        dict(kind=KIND_INSERT, pos1=2, seq=3, refseq=1, client=0,
             op_id=1, length=1),
    ]
    seq_tab, eg_tab = _run_raw(rows)
    assert_live_equal(seq_tab, eg_tab, "tombstone boundary")


def test_annotate_lww_within_span():
    # no span assertion: last-writer-wins is a property of the stamps,
    # whichever spans the annotates fall into
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=5),
        dict(kind=2, pos1=0, pos2=5, seq=2, refseq=1, client=0,
             prop_key=1, prop_val=7),
        dict(kind=2, pos1=1, pos2=3, seq=3, refseq=1, client=0,
             prop_key=1, prop_val=9),
    ]
    seq_tab, eg_tab = _run_raw(rows)
    assert_live_equal(seq_tab, eg_tab, "annotate lww")


@pytest.mark.slow
def test_overflow_flags_match_and_doc_parks():
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=i + 1, refseq=0,
             client=0, op_id=i, length=1)
        for i in range(10)
    ]
    seq_tab, eg_tab = _run_raw(rows, capacity=4)
    assert int(np.asarray(seq_tab.overflow)[0]) == 1
    assert int(np.asarray(eg_tab.overflow)[0]) == 1


def test_min_seq_advance_rides_noops():
    # no span assertion: the noop's min_seq is applied in order
    # whichever span it lands in; the differential is the property
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=3),
        dict(kind=KIND_NOOP, min_seq=1),
        dict(kind=KIND_REMOVE, pos1=0, pos2=1, seq=2, refseq=1,
             client=0, min_seq=1),
    ]
    seq_tab, eg_tab = _run_raw(rows)
    assert_live_equal(seq_tab, eg_tab, "noop min_seq")


@pytest.mark.slow
def test_mid_span_tombstone_aging_splits_ops():
    """A committed tombstone ages (min_seq crosses its removed seq)
    between two same-position inserts: in one span the two events
    would anchor at different slots and the breakTie rank group would
    split across the tombstone (seed-90007 divergence class). The
    second insert never saw the first, so it is not critical: it goes
    to the concurrent suffix, never into the first insert's span."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=2),                       # "ab"
        dict(kind=KIND_REMOVE, pos1=1, pos2=2, seq=2, refseq=1,
             client=1),                                # tombstone 'b'
        dict(kind=KIND_INSERT, pos1=1, seq=3, refseq=2, client=2,
             op_id=1, length=1, min_seq=2),            # anchors AT tomb
        dict(kind=KIND_INSERT, pos1=1, seq=4, refseq=2, client=3,
             op_id=2, length=1),                       # tomb now below
    ]
    program = build_event_graph(_raw(rows))
    assert program["graph"].critical[0].tolist()[:4] == [1, 1, 1, 0]
    seq_tab, eg_tab = _run_raw(rows)
    assert_live_equal(seq_tab, eg_tab, "mid-span aging")
    seqs = np.asarray(seq_tab.seq)[0, :4].tolist()
    assert seqs == [1, 4, 3, 1], seqs  # a | op3 | op2 | tomb-b


@pytest.mark.slow
def test_regression_seed_90007():
    """A 120-step stream whose min_seq advance mid-window aged a
    committed tombstone between two same-position inserts."""
    _, stream = record_op_stream(FuzzConfig(
        n_clients=4, n_steps=120, seed=90007,
        insert_weight=0.5, remove_weight=0.3,
        annotate_weight=0.1, process_weight=0.1,
    ))
    seq_tab, eg_tab = run_both([stream], capacity=1024)
    assert_live_equal(seq_tab, eg_tab, "seed 90007")


@pytest.mark.parametrize("steps,seed0", [
    pytest.param(120, 90000, marks=pytest.mark.slow),
    pytest.param(160, 90020, marks=pytest.mark.slow),
    (200, 90040),
])
def test_differential_fuzz_deep(steps, seed0):
    """Deep sweep, doc-batched (12 seeds per call) so the suite stays
    bounded on 1 CPU; the long-stream regime is where the seed-90007
    divergence lived (90-step streams were too shallow)."""
    streams = []
    for seed in range(seed0, seed0 + 12):
        _, s = record_op_stream(FuzzConfig(
            n_clients=4, n_steps=steps, seed=seed,
            insert_weight=0.5, remove_weight=0.3,
            annotate_weight=0.1, process_weight=0.1,
        ))
        streams.append(s)
    seq_tab, eg_tab = run_both(streams, capacity=2048)
    assert_live_equal(seq_tab, eg_tab, f"deep {steps}/{seed0}")


# ======================================================================
# the walker program takes and returns the table: the same tables, bit
# for bit, as the conversion done op by op around a state-dict program


@functools.lru_cache(maxsize=None)
def _state_walker(K: int):
    return jax.jit(lambda st, ops: _walker_loop(st, ops, K))


def _conversion_path(table, prefix, K=EG_K):
    """The walker stage as it ran before the conversion moved into the
    program: eager ``_chunk_state``, the prefix's fields sent one by one,
    the loop over state dicts, eager ``_chunk_unstate``."""
    ops_w = {f: jnp.asarray(a) for f, a in zip(PREFIX_FIELDS, prefix)}
    st = _state_walker(K)(_chunk_state(table), ops_w)
    return _chunk_unstate(dict(st))


def _folded(form: str, table, prefix):
    if form == "plain":
        return apply_window_egwalker(table, prefix)
    # the donating jit itself (on the CPU the wrapper takes the plain
    # form): a fresh dead table of the output's shapes as fodder
    dead = make_table(table.docs, table.capacity)
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", "Some donated buffers")
        return _get_jit_pingpong(EG_K)(dead, table,
                                       jax.device_put(prefix))


def assert_tables_identical(want, got, ctx=""):
    """Every field of the whole table, rows past ``count`` included."""
    for f in want._fields:
        w, g = np.asarray(getattr(want, f)), np.asarray(getattr(got, f))
        assert w.dtype == g.dtype and w.shape == g.shape, (ctx, f)
        assert np.array_equal(w, g), f"{ctx}: field {f}"


def _windows(streams, width=16):
    """The streams' ops as consecutive [D, width] rounds."""
    batch = build_batch([encode_stream(s) for s in streams])
    full = {f: np.array(getattr(batch, f), np.int32)
            for f in OpBatch._fields}
    for w0 in range(0, full["kind"].shape[1], width):
        yield {f: np.ascontiguousarray(a[:, w0:w0 + width])
               for f, a in full.items()}


def _apply_rounds(form, streams, capacity):
    """Both paths round by round over the same programs: the walker
    folded or converted op by op, the suffix's scan fed one packed
    buffer or twelve arrays; the tables compared after each stage.
    Returns (rounds with a suffix, regrows)."""
    D = len(streams)
    want = got = make_table(D, capacity)
    head = np.zeros(D, np.int64)
    suffixes = grows = 0
    for r, arrays in enumerate(_windows(streams)):
        program = build_event_graph(arrays, base_head=head)
        real = arrays["kind"] != KIND_NOOP
        head = np.maximum(head, np.where(real, arrays["seq"], 0).max(1))
        prefix, suffix = program["prefix"], program["suffix"]
        if prefix is not None:
            assert prefix.dtype == np.int32 and prefix.flags.c_contiguous
            assert prefix.shape[:2] == (len(PREFIX_FIELDS), D)
        suffixes += suffix is not None
        base_w, base_g = want, got
        while True:
            want, got = base_w, base_g
            if prefix is not None:
                want = _conversion_path(want, prefix)
                got = _folded(form, got, prefix)
                assert_tables_identical(want, got, f"{form} round {r}")
            if suffix is not None:
                want = apply_window(want, OpBatch(*suffix))
                got = apply_suffix(got, suffix)
                assert_tables_identical(want, got, f"{form} suffix {r}")
            if not np.asarray(want.overflow).any():
                break
            # the sidecar's regrow: pad the pre-dispatch snapshot and
            # re-apply the same program at twice the capacity
            grows += 1
            base_w = pad_capacity(base_w, 2 * base_w.capacity)
            base_g = pad_capacity(base_g, 2 * base_g.capacity)
    return suffixes, grows


def _fuzz_streams(seed, n=6, steps=60):
    return [record_op_stream(FuzzConfig(
        n_clients=1 + (seed + i) % 4, n_steps=steps + 8 * i,
        seed=7000 + 10 * seed + i,
        insert_weight=0.5, remove_weight=0.25,
        annotate_weight=0.1, process_weight=0.15,
    ))[1] for i in range(n)]


@pytest.mark.parametrize("form", ["plain", "pingpong"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_folded_walker_equals_the_conversion_path(form, seed):
    """Seeded multi-document rounds, concurrent ones among them (a
    suffix rides the scan after the walker): the program that takes
    and returns the table gives the tables the eager conversion gave,
    every field bit for bit."""
    suffixes, grows = _apply_rounds(form, _fuzz_streams(seed), 256)
    assert suffixes >= 1 and grows == 0


@pytest.mark.parametrize("form", ["plain", "pingpong"])
def test_folded_walker_regrow_reapply_equals_the_conversion_path(form):
    """Rounds that overflow a small slab: the pre-dispatch snapshot is
    padded and the SAME packed prefix re-applied, as the sidecar's
    regrow does with its ``_last_program``."""
    suffixes, grows = _apply_rounds(form, _fuzz_streams(5, 4, 70), 16)
    assert grows >= 1
