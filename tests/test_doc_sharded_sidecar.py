"""The sidecar's primary plane split by document over a mesh
(``TpuMergeSidecar(doc_mesh=...)``), on 4 of the CPU host devices.

A document mesh changes where each document's rows live, never what
they hold: on seeded sessions the sharded sidecar's tables are
bit-identical to the one-device sidecar's after every round, through
regrows, compaction, concurrent suffixes and overflow recovery, and
both serve the scalar replicas' text and signature. Each program runs
each device's own documents, so the compiled walker, suffix, scan,
compaction and pad hold no collective at all; the settle reads one
flag reduced over the devices.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from fluidframework_tpu.models.mergetree import MergeTreeClient
from fluidframework_tpu.ops import OpBatch, fetch, make_table
from fluidframework_tpu.ops.bucket_ladder import BucketLadder
from fluidframework_tpu.ops.event_graph import (
    EG_K,
    _get_jit,
    _get_jit_pingpong,
    _packed_scan,
    build_event_graph,
)
from fluidframework_tpu.ops.host_bridge import interned_signature, pack_rows
from fluidframework_tpu.ops.merge_kernel import (
    _pingpong_impl,
    apply_window_impl,
    compact,
    pad_capacity,
)
from fluidframework_tpu.ops.segment_table import KIND_INSERT, KIND_NOOP
from fluidframework_tpu.parallel.mesh import doc_sharding, make_mesh
from fluidframework_tpu.protocol.messages import MessageType
from fluidframework_tpu.service.tpu_sidecar import (
    TpuMergeSidecar,
    _overflow_any,
)
from fluidframework_tpu.testing.fuzz import (
    FuzzConfig,
    record_corpus_stream,
    record_op_stream,
)

DOCS = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:4])


def _sessions(seed: int) -> list:
    """(converged text, sequenced stream) per document: the bench
    corpus's fuzz mix, its webflow mix for documents 3 and 7, and one
    insert-heavy document (5) that outgrows the ladder."""
    return [record_op_stream(FuzzConfig(
        n_clients=3, n_steps=260, seed=seed, insert_weight=0.9,
        remove_weight=0.0, annotate_weight=0.1, process_weight=0.15,
        max_insert_len=4)) if d == 5
        else record_corpus_stream(d, seed, 3, 110) for d in range(DOCS)]


def _enveloped(msg):
    if msg.type != MessageType.OPERATION:
        return msg
    return dataclasses.replace(msg, contents={
        "kind": "op", "address": "d", "channel": "s",
        "contents": msg.contents})


def _pair(mesh, executor: str, **kw) -> tuple:
    kw = dict(max_docs=DOCS, capacity=32, max_capacity=128,
              compact_every=2, executor=executor,
              ladder=BucketLadder(window_floor=16, max_bucket=64), **kw)
    pair = (TpuMergeSidecar(doc_mesh=mesh, **kw), TpuMergeSidecar(**kw))
    for sc in pair:
        for d in range(DOCS):
            sc.track(f"doc-{d}", "d", "s")
    return pair


def _assert_sharded(table, mesh) -> None:
    want = doc_sharding(mesh)
    for f in table._fields:
        a = getattr(table, f)
        assert len(a.sharding.device_set) == 4, f
        assert a.sharding.is_equivalent_to(want, a.ndim), f


def _serve(sessions, pair, mesh, seed: int):
    """Deliver every document's stream in seeded slices, one round per
    slice: apply, settle, then the tables must agree bit for bit and
    every table the sharded sidecar holds must be split over the
    mesh."""
    rng = np.random.default_rng(seed)
    pos = [0] * DOCS
    rounds = 0
    while any(p < len(s) for p, (_t, s) in zip(pos, sessions)):
        rounds += 1
        for d, (_text, stream) in enumerate(sessions):
            n = int(rng.integers(0, 24))
            for msg in stream[pos[d]:pos[d] + n]:
                for sc in pair:
                    sc.ingest(f"doc-{d}", _enveloped(msg))
            pos[d] += n
        for sc in pair:
            sc.apply()
        # the dead table is the previous round's live one: split too
        if pair[0]._dead is not None:
            _assert_sharded(pair[0]._dead, mesh)
        for sc in pair:
            sc.sync()
        sharded, single = fetch(pair[0]._table), fetch(pair[1]._table)
        for f in sharded:
            assert np.array_equal(sharded[f], single[f]), (rounds, f)
        _assert_sharded(pair[0]._table, mesh)
    return rounds


@pytest.mark.parametrize("executor", ["egwalker", "scan"])
@pytest.mark.parametrize("seed", [7, 1234])
def test_sharded_plane_serves_the_one_device_tables(mesh, executor, seed):
    sessions = _sessions(seed)
    pair = _pair(mesh, executor)
    sharded, single = pair
    rounds = _serve(sessions, pair, mesh, seed)
    assert rounds >= 10
    # a regrow to each rung, then the documents past the top are
    # recovered to host replicas, on both planes alike
    for sc in pair:
        assert sc.capacity == 128 and sc.grow_count == 2
        assert sc.evict_count >= 1
        assert sc.host_mode_docs() == sc.evict_count == single.evict_count
        assert not sc.overflowed()
    if executor == "egwalker":
        assert sharded.stats["suffix_launches"] >= 5
    assert sharded.stats["compact_s"] > 0.0
    for d, (text, stream) in enumerate(sessions):
        doc = f"doc-{d}"
        assert sharded.text(doc, "d", "s") == text == \
            single.text(doc, "d", "s")
        sig = sharded.signature(doc, "d", "s")
        assert sig == single.signature(doc, "d", "s")
        replica = MergeTreeClient("replica")
        replica.start_collaboration("replica")
        for msg in stream:
            replica.apply_msg(msg)
        assert replica.get_text() == text
        assert interned_signature(replica, sharded._streams[d]) == sig


def test_round_counters_split_by_device(mesh):
    sessions = _sessions(3)
    pair = _pair(mesh, "egwalker")
    _serve(sessions, pair, mesh, 3)
    sharded, single = pair
    st = sharded.stats
    assert len(st["shard_real_ops"]) == len(st["shard_active_docs"]) == 4
    assert sum(st["shard_real_ops"]) == st["real_ops"] == \
        single.stats["real_ops"]
    assert single.stats["shard_real_ops"] == [st["real_ops"]]
    assert sum(st["shard_active_docs"]) == st["active_docs"]
    # slot s lives on device s // 2: documents 0-1 on the first, ...
    assert all(n > 0 for n in st["shard_real_ops"])
    # the sharded plane times its placement; one device copies inside
    # the kernels' own calls, as before a mesh existed
    assert st["place_s"] > 0.0 and single.stats["place_s"] == 0.0


def test_a_snapshot_of_the_stats_keeps_its_counts(mesh):
    sc = _pair(mesh, "scan")[0]
    before = dict(sc.stats)
    for msg in _sessions(5)[0][1][:20]:
        sc.ingest("doc-0", _enveloped(msg))
    sc.apply()
    assert before["shard_real_ops"] == [0, 0, 0, 0]
    assert sc.stats["shard_real_ops"][0] > 0


def test_max_docs_must_split_over_the_mesh(mesh):
    with pytest.raises(ValueError, match="multiple of the mesh size"):
        TpuMergeSidecar(max_docs=10, capacity=32, doc_mesh=mesh)
    from jax.sharding import Mesh

    other = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                 ("docs", "seq"))
    with pytest.raises(ValueError, match="one axis 'docs'"):
        TpuMergeSidecar(max_docs=8, capacity=32, doc_mesh=other)


def _window_rows(docs, concurrent: bool):
    """A packed window: three critical inserts on document 0, with
    ``concurrent`` two writers at one refSeq on document 1 (a suffix),
    and a noop on the last document."""
    rows = {0: [dict(kind=KIND_INSERT, pos1=0, pos2=0, seq=k + 1,
                     refseq=k, client=0, op_id=k, length=1, is_marker=0,
                     prop_key=0, prop_val=0, min_seq=0) for k in range(3)]}
    if concurrent:
        rows[1] = [dict(rows[0][0], seq=c + 1, refseq=0, client=c)
                   for c in (0, 1)]
    rows[docs - 1] = [dict(rows[0][0], kind=KIND_NOOP)]
    return pack_rows(docs, rows, bucket_floor=16)


def _collectives(hlo: str) -> set:
    return {op for op in ("all-gather", "all-to-all", "collective-permute",
                          "all-reduce", "reduce-scatter") if op in hlo}


def test_sharded_programs_hold_no_collective(mesh):
    """Each program runs each device's own documents: no all-gather or
    all-to-all of a table field, and no collective at all, not even
    the walker's loop test (each device runs its own trip count)."""
    sc = TpuMergeSidecar(max_docs=DOCS, capacity=64, doc_mesh=mesh)
    table = sc._new_table(64)
    program = build_event_graph(_window_rows(DOCS, True), k_max=EG_K)
    place = doc_sharding(mesh, axis=1)
    prefix = jax.device_put(program["prefix"], place)
    suffix = jax.device_put(program["suffix"], place)
    batch = jax.device_put(
        OpBatch(**{f: _window_rows(DOCS, True)[f] for f in OpBatch._fields}),
        doc_sharding(mesh))
    for jitted, args in (
            (sc._on_mesh(_get_jit(EG_K), (0, 1)), (table, prefix)),
            (sc._on_mesh(_get_jit_pingpong(EG_K), (0, 0, 1), (0,)),
             (table, table, prefix)),
            (sc._on_mesh(_packed_scan, (0, 1)), (table, suffix)),
            (sc._on_mesh(apply_window_impl, (0, 0)), (table, batch)),
            (sc._on_mesh(_pingpong_impl, (0, 0, 0), (0,)),
             (table, table, batch)),
            (sc._on_mesh(compact, (0,)), (table,)),
            (sc._on_mesh(pad_capacity, (0,), static=(128,)), (table,))):
        hlo = jitted.lower(*args).compile().as_text()
        assert _collectives(hlo) == set(), jitted
    # the settle's flag: one scalar, reduced across the devices
    hlo = _overflow_any.lower(table.overflow).compile().as_text()
    assert _collectives(hlo) <= {"all-reduce"}


def test_sharded_programs_keep_their_names(mesh):
    """A trace files a sharded program under its one-device name."""
    sc = TpuMergeSidecar(max_docs=DOCS, capacity=64, doc_mesh=mesh)
    table = sc._new_table(64)
    prefix = jax.device_put(
        build_event_graph(_window_rows(DOCS, False), k_max=EG_K)["prefix"],
        doc_sharding(mesh, axis=1))

    def module(jitted, *args) -> str:
        return jitted.lower(*args).compiler_ir("hlo").as_hlo_module().name

    assert module(sc._on_mesh(_get_jit(EG_K), (0, 1)), table,
                  prefix) == "jit__lambda"
    assert module(sc._on_mesh(_get_jit_pingpong(EG_K), (0, 0, 1), (0,)),
                  table, table, prefix) == "jit_run"
    assert module(sc._on_mesh(compact, (0,)), table) == "jit_compact"
    assert module(sc._on_mesh(pad_capacity, (0,), static=(128,)),
                  table) == "jit_pad_capacity"


@pytest.mark.parametrize("executor", ["egwalker", "scan"])
def test_prewarm_compiles_every_serving_program(mesh, executor):
    """After ``prewarm`` a sharded round, its regrow and its settle
    compile nothing."""
    from jax import monitoring

    sessions = _sessions(11)
    sc = _pair(mesh, executor)[0]
    sc.prewarm()
    compiled = []

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    monitoring.register_event_duration_secs_listener(on_compile)
    try:
        for d, (_text, stream) in enumerate(sessions):
            for msg in stream[:36]:      # windows inside the ladder
                sc.ingest(f"doc-{d}", _enveloped(msg))
        sc.apply()
        sc.sync()
    finally:
        monitoring.unregister_event_duration_listener(on_compile)
    assert sc.grow_count >= 1
    assert compiled == []


def test_pingpong_donates_a_sharded_dead_table(mesh, monkeypatch):
    """On the chip's path the dead table is donated into the sharded
    walker: the output keeps the live table's sharding."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sc = TpuMergeSidecar(max_docs=DOCS, capacity=32, executor="egwalker",
                         donate=True, doc_mesh=mesh)
    program = build_event_graph(_window_rows(DOCS, False), k_max=EG_K)
    table, dead = sc._new_table(32), sc._new_table(32)
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", "Some donated buffers")
        out = sc._apply_program(table, program, dead)
    _assert_sharded(out, mesh)
    assert int(np.asarray(out.count)[0]) == 3


def test_place_span_nests_in_the_dispatch_span(mesh, tmp_path, monkeypatch):
    """Behind the device-trace switch each round's sharded device_put
    is ``sidecar:place:r<n>``, inside that round's ``sidecar:dispatch``."""
    import glob

    from jax.profiler import ProfileData

    from fluidframework_tpu.obs import profiler

    monkeypatch.delenv(profiler.DEVICE_TRACE_ENV, raising=False)
    sessions = _sessions(2)
    sc = _pair(mesh, "egwalker")[0]
    profiler.set_device_trace(True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        for rnd in range(2):
            for d, (_text, stream) in enumerate(sessions):
                for msg in stream[rnd * 12:(rnd + 1) * 12]:
                    sc.ingest(f"doc-{d}", _enveloped(msg))
            sc.apply()
        sc.sync()
        jax.profiler.stop_trace()
    finally:
        profiler.set_device_trace(False)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("sidecar:")}
    for rnd in (1, 2):
        lo, hi = spans[f"sidecar:dispatch:r{rnd}"]
        p_lo, p_hi = spans[f"sidecar:place:r{rnd}"]
        assert lo <= p_lo <= p_hi <= hi
