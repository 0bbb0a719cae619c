"""Event-graph (Eg-walker) executor: graph structure + differential
bit-equality against the sequential executor.

The sequential scan (itself differential-fuzzed against the scalar
oracle and the C++ replayer) is the ground truth; the egwalker route —
shared-chain critical-prefix composition + walker macro-steps + the
scan suffix for genuinely concurrent tails — must reproduce its live
rows bit-for-bit (garbage rows beyond ``count`` may differ: the
walker's row merge parks different garbage than the scan's shift).
The walker's merge itself is held to the stable sort it replaces, bit
for bit over every row it keeps. The largest fuzz sweeps live in
test_walker_differential.py;
this suite owns the graph semantics (criticality, frontier, parents,
prefix split), the span-compiler break conditions, and the route
validation discipline.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from fluidframework_tpu.ops import build_batch, encode_stream, make_table
from fluidframework_tpu.ops.event_graph import (
    EG_K,
    EXECUTOR_ROUTES,
    PREFIX_FIELDS,
    _merge_rows,
    apply_batch_egwalker,
    apply_window_egwalker,
    build_event_graph,
    compiled_window,
)
from fluidframework_tpu.ops.merge_kernel import apply_window_impl
from fluidframework_tpu.ops.segment_table import (
    KIND_INSERT,
    KIND_NOOP,
    KIND_REMOVE,
    OpBatch,
)
from fluidframework_tpu.testing import (
    FuzzConfig,
    record_op_stream,
    record_sequential_stream,
)

LIVE_FIELDS = (
    "length", "seq", "client", "removed_seq", "removers",
    "op_id", "op_off", "is_marker",
)


def assert_live_equal(seq_tab, eg_tab, ctx=""):
    ns, nc = {}, {}
    for f in seq_tab._fields:
        ns[f] = np.asarray(getattr(seq_tab, f))
        nc[f] = np.asarray(getattr(eg_tab, f))
    assert np.array_equal(ns["count"], nc["count"]), (
        f"{ctx}: count {ns['count']} vs {nc['count']}"
    )
    assert np.array_equal(ns["min_seq"], nc["min_seq"]), ctx
    assert np.array_equal(ns["overflow"], nc["overflow"]), ctx
    for d in range(ns["count"].shape[0]):
        if ns["overflow"][d]:
            continue  # post-overflow application intentionally differs
        n = int(ns["count"][d])
        for f in LIVE_FIELDS:
            assert np.array_equal(ns[f][d, :n], nc[f][d, :n]), (
                f"{ctx}: doc {d} field {f}\n"
                f"seq: {ns[f][d, :n]}\neg:  {nc[f][d, :n]}"
            )
        assert np.array_equal(
            ns["prop"][d, :n], nc["prop"][d, :n]
        ), f"{ctx}: doc {d} props"


def _arrays(batch: OpBatch) -> dict:
    return {f: np.array(getattr(batch, f), np.int32)
            for f in OpBatch._fields}


def _pref(program, f: str) -> np.ndarray:
    """One field's [D, P] row of the program's packed prefix."""
    return program["prefix"][PREFIX_FIELDS.index(f)]


def run_both(streams, capacity=512):
    batch = build_batch([encode_stream(s) for s in streams])
    D = len(streams)
    seq_tab = apply_window_impl(make_table(D, capacity), batch)
    eg_tab = apply_batch_egwalker(make_table(D, capacity), batch)
    return seq_tab, eg_tab, batch


# ======================================================================
# the graph itself: parents / frontier / criticality


def _raw(ops_rows, window=None):
    base = dict(kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0,
                client=0, op_id=0, length=0, is_marker=0,
                prop_key=0, prop_val=0, min_seq=0)
    rows = [dict(base, **r) for r in ops_rows]
    W = window or len(rows)
    arrs = {f: np.zeros((1, W), np.int32) for f in OpBatch._fields}
    arrs["kind"][:] = KIND_NOOP
    for w, r in enumerate(rows):
        for f in OpBatch._fields:
            arrs[f][0, w] = r[f]
    return OpBatch(**arrs)


def test_graph_frontier_and_parents():
    """Three clients: the per-op frontier is (refseq head, own prior
    op) and criticality is one compare against the max OTHER-client
    seq."""
    batch = _raw([
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=2),
        # client 1 saw nothing: concurrent with op 1 BUT critical at
        # its application point only if refseq >= other-head (=1)
        dict(kind=KIND_INSERT, pos1=0, seq=2, refseq=0, client=1,
             op_id=1, length=1),
        # client 0 again: own prior op is lane 0; other head is seq 2
        dict(kind=KIND_INSERT, pos1=1, seq=3, refseq=2, client=0,
             op_id=2, length=1),
    ])
    g = build_event_graph(_arrays(batch))["graph"]
    assert g.parent_own[0].tolist() == [-1, -1, 0]
    assert g.frontier_other[0].tolist() == [0, 1, 2]
    assert g.critical[0].tolist() == [1, 0, 1]
    assert g.parent_seq[0].tolist() == [0, 0, 2]
    # the split happens at the FIRST non-critical op
    assert g.prefix_len.tolist() == [1]


def test_same_client_burst_is_fully_critical():
    """A blind same-client burst (refseq frozen) stays critical: the
    unseen ops are its OWN, which are always visible."""
    batch = _raw([
        dict(kind=KIND_INSERT, pos1=i, seq=i + 1, refseq=0, client=0,
             op_id=i, length=1)
        for i in range(6)
    ])
    g = build_event_graph(_arrays(batch))["graph"]
    assert g.critical[0].tolist() == [1] * 6
    assert g.prefix_len.tolist() == [6]
    assert g.parent_own[0].tolist() == [-1, 0, 1, 2, 3, 4]


def test_base_head_gates_history_criticality():
    """base_head folds already-applied history in conservatively: an
    op whose refseq predates the applied head is demoted to the scan
    suffix (correct either way; the fast path just narrows)."""
    rows = [dict(kind=KIND_INSERT, pos1=0, seq=5, refseq=3, client=0,
                 op_id=0, length=1)]
    arrays = _arrays(_raw(rows))
    fresh = build_event_graph(arrays)["graph"]
    assert fresh.critical[0].tolist() == [1]  # head 0 <= refseq 3
    applied = build_event_graph(
        arrays, base_head=np.array([4], np.int64))["graph"]
    assert applied.critical[0].tolist() == [0]  # head 4 > refseq 3
    assert applied.prefix_len.tolist() == [0]


def test_sequential_stream_is_all_critical_and_suffix_free():
    _, stream = record_sequential_stream(seed=3, n_steps=60)
    batch = build_batch([encode_stream(stream)])
    program = build_event_graph(_arrays(batch))
    W = batch.kind.shape[1]
    assert program["graph"].prefix_len.tolist() == [W]
    assert program["suffix"] is None
    assert program["prefix"] is not None


def test_concurrent_stream_routes_to_the_suffix():
    _, stream = record_op_stream(FuzzConfig(
        n_clients=4, n_steps=60, seed=9,
        insert_weight=0.6, remove_weight=0.25,
        annotate_weight=0.05, process_weight=0.05,
    ))
    batch = build_batch([encode_stream(stream)])
    program = build_event_graph(_arrays(batch))
    W = batch.kind.shape[1]
    assert int(program["graph"].prefix_len[0]) < W
    assert program["suffix"] is not None


# ======================================================================
# span composition: cross-client chains a per-client composition breaks


def test_cross_client_visible_dependency_shares_a_span():
    """A per-client composition's main break — a cross-client VISIBLE
    dependency — never breaks a critical span: that is where the
    egwalker throughput comes from."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=4),
        # client 1 SAW client 0's insert and types right after it —
        # a per-client chain breaks here (cross-client visible
        # ins/rm); the shared critical chain composes it exactly
        dict(kind=KIND_INSERT, pos1=4, seq=2, refseq=1, client=1,
             op_id=1, length=2),
        # client 0 removes across BOTH clients' in-span text
        dict(kind=KIND_REMOVE, pos1=0, pos2=6, seq=3, refseq=2,
             client=0),
    ]
    batch = _raw(rows)
    program = build_event_graph(_arrays(batch))
    assert program["suffix"] is None
    # ONE span: no chunk_start past lane 0
    assert _pref(program, "chunk_start")[0, :3].tolist() == [1, 0, 0]
    # the remove covers both in-span events via the host bitmask
    assert _pref(program, "ev_cover")[0, 2] == 0b11
    seq_tab = apply_window_impl(make_table(1, 64), batch)
    eg_tab = apply_batch_egwalker(make_table(1, 64), batch)
    assert_live_equal(seq_tab, eg_tab, "cross-client span")


def test_cross_client_same_anchor_orders_by_walk_replay():
    """B types at the END of A's in-span text (saw it): the shared
    chain's pred machinery must order the events across clients."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=1),
        dict(kind=KIND_INSERT, pos1=1, seq=2, refseq=1, client=1,
             op_id=1, length=1),
        dict(kind=KIND_INSERT, pos1=0, seq=3, refseq=2, client=2,
             op_id=2, length=1),
    ]
    seq_tab, eg_tab, _ = (
        apply_window_impl(make_table(1, 64), _raw(rows)),
        apply_batch_egwalker(make_table(1, 64), _raw(rows)),
        None,
    )
    assert_live_equal(seq_tab, eg_tab, "cross-client anchors")


def test_anchor_inside_foreign_event_text_breaks_the_span():
    """An anchor strictly inside ANOTHER op's in-span text cannot be
    composed (events don't split); the span must break and still
    converge bit-identically."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=4),
        dict(kind=KIND_INSERT, pos1=2, seq=2, refseq=1, client=1,
             op_id=1, length=1),  # strictly inside "aaaa"
    ]
    batch = _raw(rows)
    program = build_event_graph(_arrays(batch))
    assert _pref(program, "chunk_start")[0, :2].tolist() == [1, 1]
    assert_live_equal(
        apply_window_impl(make_table(1, 64), batch),
        apply_batch_egwalker(make_table(1, 64), batch),
        "mid-event anchor",
    )


def test_open_span_remove_aging_splits_the_event():
    """An in-span remove whose seq falls at/below a later op's
    min_seq ages into `below` mid-span. Event splitting absorbs what
    used to be a mandatory span break: the chain splits the aged
    tombstone segment out of the anchor walk (``_locate`` with the
    exclusive ms watermark) and the span keeps composing — the
    absorbed break is counted in ``span_splits``."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=3),
        dict(kind=KIND_REMOVE, pos1=1, pos2=2, seq=2, refseq=1,
             client=1),
        dict(kind=KIND_INSERT, pos1=1, seq=3, refseq=2, client=2,
             op_id=1, length=1, min_seq=2),  # ms crosses the remove
    ]
    batch = _raw(rows)
    program = build_event_graph(_arrays(batch))
    # the aging boundary no longer breaks the span (only the
    # anchor-inside-event break at w=1 remains)
    assert _pref(program, "chunk_start")[0, 2] == 0
    assert program["span_splits"][0] == 1
    assert_live_equal(
        apply_window_impl(make_table(1, 64), batch),
        apply_batch_egwalker(make_table(1, 64), batch),
        "open-span aging",
    )


def test_aged_tombstone_anchor_passes_through():
    """The split's SEMANTIC half: an insert AT an aged tombstone's
    coordinate must land past it (the sequential stop mask passes an
    aged tombstone), while an insert before aging stops at it — both
    composed inside one surviving span where possible."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=4),
        dict(kind=KIND_REMOVE, pos1=1, pos2=3, seq=2, refseq=1,
             client=1),
        # min_seq crosses the remove, then an insert maps exactly to
        # the tombstone's view coordinate
        dict(kind=KIND_NOOP, min_seq=2),
        dict(kind=KIND_INSERT, pos1=1, seq=3, refseq=2, client=2,
             op_id=1, length=2),
    ]
    batch = _raw(rows)
    program = build_event_graph(_arrays(batch))
    assert program["span_splits"][0] == 1
    assert_live_equal(
        apply_window_impl(make_table(1, 64), batch),
        apply_batch_egwalker(make_table(1, 64), batch),
        "aged anchor pass-through",
    )


def test_committed_tombstone_aging_collision_still_breaks():
    """The seed-90007 residue: a committed tombstone's below-status
    flips mid-span AND two same-coordinate inserts straddle the flip
    — their same-anchor rank groups would split across the aged
    tombstone, so the compiler still closes the span at the second
    insert (the narrow break event splitting cannot absorb)."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=2),
        dict(kind=KIND_REMOVE, pos1=1, pos2=2, seq=2, refseq=1,
             client=1),
        dict(kind=KIND_INSERT, pos1=1, seq=3, refseq=2, client=2,
             op_id=1, length=1, min_seq=2),
        dict(kind=KIND_INSERT, pos1=1, seq=4, refseq=3, client=3,
             op_id=2, length=1),
    ]
    batch = _raw(rows)
    program = build_event_graph(_arrays(batch))
    assert _pref(program, "chunk_start")[0, 3] == 1
    seq_tab = apply_window_impl(make_table(1, 64), batch)
    eg_tab = apply_batch_egwalker(make_table(1, 64), batch)
    assert_live_equal(seq_tab, eg_tab, "committed aging")
    seqs = np.asarray(seq_tab.seq)[0, :4].tolist()
    assert seqs == [1, 4, 3, 1], seqs


def test_remove_heavy_sequential_spans_shrink():
    """The config14 remove-heavy claim in miniature: a typing burst
    interleaved with aging removes used to break at every aging
    boundary; with event splitting the span count drops to the
    k_max ceiling and every absorbed boundary is counted."""
    rows = []
    seq = 0
    for i in range(6):
        seq += 1
        rows.append(dict(kind=KIND_INSERT, pos1=i, seq=seq,
                         refseq=seq - 1, client=0, op_id=i, length=1,
                         min_seq=max(0, seq - 2)))
        seq += 1
        rows.append(dict(kind=KIND_REMOVE, pos1=0, pos2=1, seq=seq,
                         refseq=seq - 1, client=0,
                         min_seq=max(0, seq - 2)))
    batch = _raw(rows)
    program = build_event_graph(_arrays(batch))
    starts = int(_pref(program, "chunk_start")[0].sum())
    # 12 ops at EG_K=16: one span, several absorbed aging breaks
    assert starts == 1, starts
    assert program["span_splits"][0] >= 3
    assert_live_equal(
        apply_window_impl(make_table(1, 64), batch),
        apply_batch_egwalker(make_table(1, 64), batch),
        "remove-heavy burst",
    )


def test_noops_advance_min_seq_through_spans():
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=1, refseq=0, client=0,
             op_id=0, length=3),
        dict(kind=KIND_NOOP, min_seq=1),
        dict(kind=KIND_REMOVE, pos1=0, pos2=1, seq=2, refseq=1,
             client=0, min_seq=1),
    ]
    batch = _raw(rows)
    assert_live_equal(
        apply_window_impl(make_table(1, 64), batch),
        apply_batch_egwalker(make_table(1, 64), batch),
        "noop min_seq",
    )


def test_overflow_flags_match_and_doc_parks():
    """Walker overflow semantics: flag + park; the
    sidecar's snapshot re-apply recovery absorbs the difference."""
    rows = [
        dict(kind=KIND_INSERT, pos1=0, seq=i + 1, refseq=i, client=0,
             op_id=i, length=1)
        for i in range(10)
    ]
    batch = _raw(rows)
    seq_tab = apply_window_impl(make_table(1, 4), batch)
    eg_tab = apply_batch_egwalker(make_table(1, 4), batch)
    assert int(np.asarray(seq_tab.overflow)[0]) == 1
    assert int(np.asarray(eg_tab.overflow)[0]) == 1


# ======================================================================
# differential sweeps (the scan executor is ground truth)


def _smoke(n, keep):
    """range(n) with every seed outside ``keep`` slow-marked — tier-1
    runs a smoke subset of the sweep, the full sweep is slow-lane."""
    return [
        s if s in keep else pytest.param(s, marks=pytest.mark.slow)
        for s in range(n)
    ]


@pytest.mark.parametrize("seed", _smoke(12, {0, 1}))
def test_differential_sequential(seed):
    """The fast-path corpus proper: fully-sequential multi-client
    traffic — every op critical, no suffix, spans crossing client
    boundaries."""
    _, stream = record_sequential_stream(seed=seed, n_steps=80)
    seq_tab, eg_tab, batch = run_both([stream])
    program = build_event_graph(_arrays(batch))
    assert program["suffix"] is None  # non-vacuity: fast path taken
    assert_live_equal(seq_tab, eg_tab, f"sequential {seed}")


@pytest.mark.parametrize("seed", _smoke(12, {0, 1}))
def test_differential_concurrent_mix(seed):
    """The bread-and-butter concurrent mix: most ops route to the
    scan suffix; the split point itself must be seam-free."""
    _, stream = record_op_stream(FuzzConfig(
        n_clients=4, n_steps=90, seed=seed,
        insert_weight=0.55, remove_weight=0.25,
        annotate_weight=0.05, process_weight=0.15,
    ))
    seq_tab, eg_tab, _ = run_both([stream])
    assert_live_equal(seq_tab, eg_tab, f"mix {seed}")


@pytest.mark.parametrize("seed", _smoke(6, {0, 1}))
def test_differential_multidoc_mixed_routes(seed):
    """Sequential and concurrent docs sharing one dispatch: some rows
    ride the walker end-to-end while others split to the suffix."""
    streams = []
    for i in range(3):
        _, s = record_sequential_stream(
            seed=4000 + 10 * seed + i, n_steps=40 + 10 * i)
        streams.append(s)
    for i in range(3):
        _, s = record_op_stream(FuzzConfig(
            n_clients=1 + (seed + i) % 4, n_steps=40 + 10 * i,
            seed=5000 + 10 * seed + i,
            insert_weight=0.5, remove_weight=0.25,
            annotate_weight=0.1, process_weight=0.15,
        ))
        streams.append(s)
    seq_tab, eg_tab, _ = run_both(streams)
    assert_live_equal(seq_tab, eg_tab, f"multidoc {seed}")


def test_walker_prefix_applies_without_the_convenience_wrapper():
    """apply_window_egwalker on the program's prefix half alone
    equals the scan over the same (critical) window."""
    _, stream = record_sequential_stream(seed=77, n_steps=50)
    batch = build_batch([encode_stream(stream)])
    program = build_event_graph(_arrays(batch))
    assert program["suffix"] is None
    P = _pref(program, "kind").shape[1]
    eg_tab = apply_window_egwalker(make_table(1, 256), program["prefix"])
    # pad the batch to the prefix bucket so shapes line up
    padded = {f: np.zeros((1, P), np.int32) for f in OpBatch._fields}
    padded["kind"][:] = KIND_NOOP
    W = batch.kind.shape[1]
    for f in OpBatch._fields:
        padded[f][:, :W] = np.array(getattr(batch, f), np.int32)
    seq_tab = apply_window_impl(make_table(1, 256), OpBatch(**padded))
    assert_live_equal(seq_tab, eg_tab, "prefix-only")


# ======================================================================
# the walker's row merge: the stable sort it replaces, bit for bit


@functools.lru_cache(maxsize=None)
def _jit_merge(K: int):
    return jax.jit(lambda b, x, s, m: _merge_rows(b, x, s, m, K))


def _sorted_rows(base, extra, slot, minor, K):
    """The order the walker used to build: a stable sort of base + extra
    rows by (slot, minor), base row j keyed (j, K), each field permuted
    by take_along_axis; the first C rows."""
    D, C = base[0].shape
    keys = (
        jnp.concatenate([jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                          (D, C)), slot], axis=1),
        jnp.concatenate([jnp.full((D, C), K, jnp.int32), minor], axis=1),
        jnp.broadcast_to(jnp.arange(C + slot.shape[1], dtype=jnp.int32),
                         (D, C + slot.shape[1])),
    )
    perm = lax.sort(keys, dimension=-1, is_stable=True, num_keys=2)[2]
    return [jnp.take_along_axis(jnp.concatenate([b, x], axis=1), perm,
                                axis=1)[:, :C]
            for b, x in zip(base, extra)]


def _walker_keys(rng, D, C, K):
    """Keys of the 3K extra rows as the walker builds them: 2K tails
    (valid: a live slot, offset > 0, minor (2 off + 1) K; invalid: slot
    C + 1, offset 0) then K events (valid: a slot up to ``count``, the
    event slot at ``count`` included, offset 0 or > 0, rank < K, minor
    2 off K + rank; invalid: slot C + 1). Slots come from four per
    document, so many rows share a slot, and some share a whole key."""
    count = rng.integers(1, C + 1, D)
    hot = rng.integers(0, count[:, None], (D, 4))
    pick = np.take_along_axis(hot, rng.integers(0, 4, (D, 3 * K)), axis=1)
    t_valid = rng.random((D, 2 * K)) < 0.6
    t_off = np.where(t_valid, rng.integers(1, 4, (D, 2 * K)), 0)
    t_slot = np.where(t_valid, pick[:, :2 * K], C + 1)
    e_valid = rng.random((D, K)) < 0.7
    e_slot = np.where(rng.random((D, K)) < 0.25, count[:, None],
                      pick[:, 2 * K:])
    e_off = np.where(rng.random((D, K)) < 0.5, 0,
                     rng.integers(1, 4, (D, K)))
    e_off = np.where(e_valid, e_off, 0)
    rank = rng.integers(0, K, (D, K))
    slot = np.concatenate([t_slot, np.where(e_valid, e_slot, C + 1)], 1)
    minor = np.concatenate([(2 * t_off + 1) * K, 2 * e_off * K + rank], 1)
    return slot.astype(np.int32), minor.astype(np.int32)


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("K,C", [(EG_K, 40), (EG_K, 160), (3, 40)])
def test_row_merge_equals_the_stable_sort(K, C, seed, ties):
    """``_merge_rows`` against ``lax.sort(..., is_stable=True,
    num_keys=2)`` + ``take_along_axis`` on random key tables with the
    walker's structure, every field (int32 and the uint32 removers)
    bit for bit. The walker's keys never tie a base row's (j, K) —
    asserted on the generated tables; with ``ties`` some extra rows are
    given a base row's key anyway, and the merge still keeps the stable
    sort's order (the base row first)."""
    rng = np.random.default_rng(1000 * K + 10 * C + seed)
    D, N = 6, 3 * K
    slot, minor = _walker_keys(rng, D, C, K)
    assert not np.any((slot < C) & (minor == K))
    if ties:
        at = rng.random((D, N)) < 0.2
        slot = np.where(at, rng.integers(0, C, (D, N)), slot)
        minor = np.where(at, K, minor).astype(np.int32)
    base = [jnp.asarray(rng.integers(-2**31, 2**31, (D, C)), jnp.int32),
            jnp.asarray(rng.integers(0, 50, (D, C)), jnp.int32),
            jnp.asarray(rng.integers(0, 2**32, (D, C)), jnp.uint32)]
    extra = [jnp.asarray(rng.integers(-2**31, 2**31, (D, N)), jnp.int32),
             jnp.asarray(rng.integers(50, 99, (D, N)), jnp.int32),
             jnp.asarray(rng.integers(0, 2**32, (D, N)), jnp.uint32)]
    slot, minor = jnp.asarray(slot), jnp.asarray(minor)
    got = _jit_merge(K)(base, extra, slot, minor)
    want = _sorted_rows(base, extra, slot, minor, K)
    for f, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype, f
        assert np.array_equal(np.asarray(g), np.asarray(w)), (
            f"field {f}\nmerge {np.asarray(g)}\nsort  {np.asarray(w)}")


def test_walker_module_has_no_row_gather_and_no_sort():
    """The per-element gathers stay gone from the walker: in its lowered
    module (StableHLO, the same on every backend) no gather yields a row
    axis, C or R = C + 3K wide, and nothing sorts. Only the small
    [D, <= 3K] and [D] gathers remain."""
    C = 64
    R = C + 3 * EG_K
    streams = [record_sequential_stream(seed=s, n_steps=30)[1]
               for s in range(4)]
    batch = build_batch([encode_stream(s) for s in streams])
    program = build_event_graph(_arrays(batch))
    fn, args = compiled_window(make_table(4, C), program["prefix"])
    text = fn.lower(*args).as_text()
    widths = [  # the minor dimension of each gather's result
        int(m.group(1).rstrip("x").split("x")[-1])
        for m in re.finditer(
            r'"stablehlo\.gather".*-> tensor<((?:\d+x)+)[a-z]+\d*>', text)
    ]
    assert widths, "no gather found: the pattern no longer reads the text"
    assert not [w for w in widths if w in (C, R)], widths
    assert max(widths) <= 3 * EG_K
    assert "stablehlo.sort" not in text


# ======================================================================
# route validation (the select_pool loud-on-typo discipline)


@pytest.mark.parametrize("backend,route", [
    ("tpu", "egwalker"), ("cpu", "scan"), ("gpu", "scan")])
def test_default_route_by_backend(monkeypatch, backend, route):
    """The route is the backend's rule alone: egwalker on a TPU, the
    scan everywhere else — no table, no environment knob."""
    from fluidframework_tpu.service.tpu_sidecar import default_executor

    monkeypatch.setenv("FFTPU_SIDECAR_EXECUTOR", "scan")  # no knob
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert default_executor() == route
    assert route in EXECUTOR_ROUTES


def test_executor_constructor_typo_is_loud():
    from fluidframework_tpu.service import TpuMergeSidecar
    from fluidframework_tpu.service.tpu_sidecar import select_pool

    with pytest.raises(ValueError, match="executor='egwalkr'"):
        TpuMergeSidecar(executor="egwalkr")
    # every route name the registry declares constructs
    for route in EXECUTOR_ROUTES:
        TpuMergeSidecar(max_docs=2, capacity=16, executor=route)
    import jax

    from fluidframework_tpu.parallel import make_seq_mesh

    mesh = make_seq_mesh(jax.devices()[:1])
    with pytest.raises(ValueError, match="executor='chunked'"):
        select_pool(mesh, 64, executor="chunked")


def test_egwalker_pool_routes_scan_on_degenerate_seq_mesh(monkeypatch):
    """The pool tier replays full histories where the critical-prefix
    fast path buys nothing: the pool under an egwalker sidecar on a
    single-shard seq mesh runs the plain scan and takes no route."""
    from fluidframework_tpu.ops import merge_kernel
    from fluidframework_tpu.parallel import make_seq_mesh
    from fluidframework_tpu.service import TpuMergeSidecar, tpu_sidecar

    scanned = []

    def spy(table, batch):
        scanned.append(table.capacity)
        return merge_kernel.apply_window(table, batch)

    monkeypatch.setattr(tpu_sidecar, "apply_window", spy)
    sc = TpuMergeSidecar(max_docs=2, capacity=16, max_capacity=16,
                         executor="egwalker", pool_capacity=64,
                         seq_mesh=make_seq_mesh(jax.devices()[:1]))
    assert sc.executor == "egwalker"
    assert not hasattr(sc._pool, "executor")
    sc._pool.prewarm()  # drives _apply through the plain scan
    assert scanned and set(scanned) == {64}


def test_eg_k_stays_within_the_cover_bitmask():
    assert 1 <= EG_K <= 31
