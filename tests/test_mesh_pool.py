"""Mesh-sharded document pool (ROADMAP item 1): pooled documents
spread across the mesh's DOC shards (parallel/mesh_pool.py), with
live hot-document migration at the settle boundary.

THE correctness pin is the route-parity differential: a scripted
hot-spot run on a multi-shard mesh — with migrations actually firing
— must serve text() and signature() bit-identical to the
never-migrated single-shard pool AND the per-client container oracle,
through grow/evict/overflow/migration interleavings, including a
migration racing an overflow-recovery rebuild (the PR2 double-apply
shape, re-pinned for cross-shard moves).
"""
import jax
import numpy as np
import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.parallel import (
    MeshShardedPool,
    make_mesh,
    make_seq_mesh,
)
from fluidframework_tpu.service import LocalServer, TpuMergeSidecar
from fluidframework_tpu.service.tpu_sidecar import (
    SeqShardedPool,
    select_pool,
)


def _open_doc(server, sidecars, doc):
    factory = LocalDocumentServiceFactory(server)
    for sc in sidecars:
        sc.subscribe(server, doc, "d", "s")
    c = Container.load(factory.create_document_service(doc),
                       client_id=f"{doc}-w")
    s = c.runtime.create_datastore("d").create_channel(
        "sharedstring", "s")
    return c, s


def _grow_into_pool(c, s, n_chunks=20):
    for i in range(n_chunks):
        s.insert_text(0, "abcdefgh")
        c.flush()
        if i % 3 == 2 and s.get_length() > 6:
            s.remove_text(2, 5)
            c.flush()


def _assert_parity(sidecars, docs, strings):
    ref = sidecars[0]
    for doc in docs:
        want = strings[doc].get_text()
        for sc in sidecars:
            assert sc.text(doc, "d", "s") == want, (
                f"text divergence on {doc}")
            assert sc.signature(doc, "d", "s") == \
                ref.signature(doc, "d", "s"), (
                    f"signature divergence on {doc}")


# ======================================================================
# route selection (ONE place: select_pool)


def test_select_pool_routes_by_mesh_axes():
    docs4 = make_mesh(jax.devices()[:4])
    assert isinstance(select_pool(docs4, 128), MeshShardedPool)
    seq = make_seq_mesh(jax.devices()[:4])  # 1 doc lane x 4 seq
    assert isinstance(select_pool(seq, 128), SeqShardedPool)
    # single-shard: a degenerate seq mesh keeps the existing seq-pool
    # path, a docs mesh gets a 1-shard mesh pool
    seq1 = make_seq_mesh(jax.devices()[:1])
    assert isinstance(select_pool(seq1, 128), SeqShardedPool)
    docs1 = make_mesh(jax.devices()[:1])
    assert isinstance(select_pool(docs1, 128), MeshShardedPool)


def test_select_pool_env_and_arg_override(monkeypatch):
    docs1 = make_mesh(jax.devices()[:1])
    seq1 = make_seq_mesh(jax.devices()[:1])
    # constructor arg wins outright
    assert isinstance(
        select_pool(seq1, 128, route="seq"), SeqShardedPool)
    # env override routes — and an override that cannot fit the mesh
    # fails in the chosen pool's own validation, never silently
    monkeypatch.setenv("FFTPU_SIDECAR_POOL", "mesh")
    assert isinstance(select_pool(docs1, 128), MeshShardedPool)
    monkeypatch.setenv("FFTPU_SIDECAR_POOL", "seq")
    with pytest.raises(ValueError, match="seq pool needs"):
        select_pool(docs1, 128)
    monkeypatch.setenv("FFTPU_SIDECAR_POOL", "warp")
    with pytest.raises(ValueError, match="FFTPU_SIDECAR_POOL"):
        select_pool(docs1, 128)
    # the CONSTRUCTOR-ARG spelling of a typo must be just as loud —
    # a route='msh' silently building the other pool is exactly the
    # silent-route-change failure select_pool exists to close
    monkeypatch.delenv("FFTPU_SIDECAR_POOL")
    with pytest.raises(ValueError, match="pool_route='msh'"):
        select_pool(seq1, 128, route="msh")


def test_select_pool_resolves_backend_default_executor():
    """String pools resolve no executor route at all: select_pool
    builds them without one on any mesh, and a route passed for the
    merge plane fails loudly instead of being ignored."""
    for mesh in (make_mesh(jax.devices()[:1]),
                 make_seq_mesh(jax.devices()[:1])):
        pool = select_pool(mesh, 128)
        assert not hasattr(pool, "executor")
        with pytest.raises(ValueError, match="take no route"):
            select_pool(mesh, 128, executor="egwalker")


@pytest.mark.parametrize("route", ["scan", "egwalker"])
@pytest.mark.parametrize("tier", ["seq", "mesh"])
def test_pools_run_the_scan(monkeypatch, tier, route):
    """A document that outgrows the ladder top is admitted to the
    pool and served by the pool's scan — the plain ``apply_window``
    on a one-device mesh, whatever the sidecar's route — equal to
    the client's text."""
    from fluidframework_tpu.ops import merge_kernel
    from fluidframework_tpu.parallel import mesh_pool
    from fluidframework_tpu.service import tpu_sidecar

    module = tpu_sidecar if tier == "seq" else mesh_pool
    scanned = []

    def spy(table, batch):
        scanned.append(table.capacity)
        return merge_kernel.apply_window(table, batch)

    monkeypatch.setattr(module, "apply_window", spy)
    mesh = (make_seq_mesh(jax.devices()[:1]) if tier == "seq"
            else make_mesh(jax.devices()[:1]))
    server = LocalServer()
    sc = TpuMergeSidecar(max_docs=2, capacity=16, max_capacity=16,
                         executor=route, seq_mesh=mesh,
                         pool_capacity=256)
    c, s = _open_doc(server, [sc], "doc")
    _grow_into_pool(c, s)
    sc.apply()
    sc.sync()
    assert sc.pooled_docs() == 1
    assert isinstance(sc._pool, SeqShardedPool if tier == "seq"
                      else MeshShardedPool)
    assert 256 in scanned, "the pool never ran the scan"
    s.insert_text(0, "tail")
    c.flush()
    sc.apply()
    sc.sync()
    assert sc.text("doc", "d", "s") == s.get_text()


def test_mesh_pool_rejects_bad_meshes():
    with pytest.raises(ValueError, match="mesh axis"):
        MeshShardedPool(make_seq_mesh(jax.devices()[:2]), 128,
                        doc_axis="absent")
    # a real seq axis is the seq pool's job
    mesh2d = make_seq_mesh(jax.devices()[:4], doc_shards=2)
    with pytest.raises(ValueError, match="documents only"):
        MeshShardedPool(mesh2d, 128)
    with pytest.raises(ValueError, match="capacity"):
        MeshShardedPool(make_mesh(jax.devices()[:2]), 8)


# ======================================================================
# the pool tier end to end (sidecar-driven, multi-shard)


def test_overgrown_docs_spread_across_shards():
    mesh = make_mesh(jax.devices()[:4])
    server = LocalServer()
    sidecar = TpuMergeSidecar(max_docs=8, capacity=16, max_capacity=32,
                              seq_mesh=mesh, pool_capacity=256)
    assert isinstance(sidecar._pool, MeshShardedPool)
    docs, strings = [], {}
    for i in range(4):
        doc = f"doc-{i}"
        c, s = _open_doc(server, [sidecar], doc)
        _grow_into_pool(c, s, n_chunks=60)
        docs.append(doc)
        strings[doc] = s
    sidecar.apply()
    sidecar.sync()
    assert sidecar.pooled_docs() == 4
    assert sidecar.host_mode_docs() == 0
    # placement spread: no shard hoards the pool
    assert [len(m) for m in sidecar._pool.shard_members] == [1, 1, 1, 1]
    for doc in docs:
        assert sidecar.text(doc, "d", "s") == strings[doc].get_text()


def test_mesh_pool_eviction_keeps_survivors_correct():
    """Beyond pooled capacity -> host eviction; the mesh pool's
    remaining members must keep reading/applying correctly (the
    mesh-pool variant of the seq pool's eviction regression)."""
    mesh = make_mesh(jax.devices()[:2])
    server = LocalServer()
    sidecar = TpuMergeSidecar(max_docs=4, capacity=16, max_capacity=32,
                              seq_mesh=mesh, pool_capacity=128)
    a_c, a_s = _open_doc(server, [sidecar], "doc-a")
    b_c, b_s = _open_doc(server, [sidecar], "doc-b")
    _grow_into_pool(a_c, a_s, n_chunks=60)
    _grow_into_pool(b_c, b_s, n_chunks=60)
    sidecar.apply()
    sidecar.sync()
    assert sidecar.pooled_docs() == 2
    for _ in range(120):
        a_s.insert_text(0, "zzzzzzzz")
        a_c.flush()
    sidecar.apply()
    sidecar.sync()
    assert sidecar.host_mode_docs() == 1       # doc-a evicted
    assert sidecar.pooled_docs() == 1          # doc-b survives
    assert sidecar.text("doc-b", "d", "s") == b_s.get_text()
    b_s.insert_text(0, "still-alive-")
    b_c.flush()
    sidecar.apply()
    sidecar.sync()
    assert sidecar.pooled_docs() == 1, "no spurious eviction"
    assert sidecar.text("doc-b", "d", "s") == b_s.get_text()
    assert sidecar.text("doc-a", "d", "s") == a_s.get_text()


# ======================================================================
# THE migration route-parity differential


def _hotspot_pair(server, n_docs=3):
    """One sidecar on a 2-shard docs mesh (migrations expected), one
    on the degenerate single-shard seq mesh (the never-migrated
    oracle), identical otherwise, same sequenced streams.
    max_capacity == capacity: every overgrown doc pools at its first
    overflow (the ladder cannot grow), like the PR2 deferred tests."""
    mesh_sc = TpuMergeSidecar(
        max_docs=6, capacity=16, max_capacity=16,
        seq_mesh=make_mesh(jax.devices()[:2]), pool_capacity=256,
    )
    seq_sc = TpuMergeSidecar(
        max_docs=6, capacity=16, max_capacity=16,
        seq_mesh=make_seq_mesh(jax.devices()[:1]), pool_capacity=256,
    )
    assert isinstance(mesh_sc._pool, MeshShardedPool)
    assert isinstance(seq_sc._pool, SeqShardedPool)
    sidecars = [mesh_sc, seq_sc]
    docs, containers, strings = [], {}, {}
    for i in range(n_docs):
        doc = f"doc-{i}"
        c, s = _open_doc(server, sidecars, doc)
        docs.append(doc)
        containers[doc], strings[doc] = c, s
    return mesh_sc, seq_sc, docs, containers, strings


def test_hotspot_migration_is_bit_exact_vs_single_shard_pool():
    """The acceptance differential: a hot-spot run that MIGRATES
    (migrations_total > 0) serves bit-identical text/signature to the
    never-migrated single-shard pool and the container oracle."""
    server = LocalServer()
    mesh_sc, seq_sc, docs, containers, strings = _hotspot_pair(server)
    # fleet observability (PR13): attach a timeline so each migration
    # lands as a causal event next to its pool:migrate hop stamp
    from fluidframework_tpu.obs.metrics import MetricsRegistry
    from fluidframework_tpu.obs.timeline import FleetTimeline

    timeline = FleetTimeline(registry=MetricsRegistry(node="pool"))
    mesh_sc._pool.timeline = timeline
    # all three docs overflow into the pool in one settle: placement
    # [doc-0, doc-2] / [doc-1] on the 2-shard mesh
    for doc in docs:
        _grow_into_pool(containers[doc], strings[doc], n_chunks=20)
    for sc in (mesh_sc, seq_sc):
        sc.apply()
        sc.sync()
    assert mesh_sc.pooled_docs() == 3
    assert seq_sc.pooled_docs() == 3
    _assert_parity([mesh_sc, seq_sc], docs, strings)

    # hot-spot doc-0; its co-resident doc-2 should migrate off the
    # hot shard within a few settles
    for _ in range(6):
        for doc in docs:
            n = 12 if doc == "doc-0" else 1
            for _ in range(n):
                strings[doc].insert_text(0, "XY")
            containers[doc].flush()
        for sc in (mesh_sc, seq_sc):
            sc.apply()
            sc.sync()
    assert mesh_sc._pool.migration_count > 0, (
        "the hot-spot run must actually migrate")
    # every migration stamped the canonical pool:migrate hop and
    # recorded a timeline event carrying the move's src/dst shards
    pool = mesh_sc._pool
    assert len(pool.migration_traces) == pool.migration_count
    assert all(t.service == "pool" and t.action == "migrate"
               for t in pool.migration_traces)
    moves = timeline.events("migration")
    assert len(moves) == pool.migration_count
    assert all(e.fields["src"] != e.fields["dst"] for e in moves)
    assert seq_sc._pool.dispatch_count > 0
    assert mesh_sc.host_mode_docs() == 0
    assert seq_sc.host_mode_docs() == 0
    _assert_parity([mesh_sc, seq_sc], docs, strings)


def test_migration_racing_overflow_recovery_rebuild():
    """The PR2 double-apply shape re-pinned for cross-shard moves:
    after a migration has moved a doc, ONE apply carries (a) deferred
    window ops for the migrated doc and (b) a fourth doc overflowing
    into the pool — the recovery rebuild replays full canonical
    streams (which already contain the deferred ops) and must subsume
    them exactly once, with the migrated placement intact."""
    server = LocalServer()
    mesh_sc, seq_sc, docs, containers, strings = _hotspot_pair(server)
    for doc in docs:
        _grow_into_pool(containers[doc], strings[doc], n_chunks=20)
    for sc in (mesh_sc, seq_sc):
        sc.apply()
        sc.sync()
    for _ in range(4):
        for doc in docs:
            n = 12 if doc == "doc-0" else 1
            for _ in range(n):
                strings[doc].insert_text(0, "XY")
            containers[doc].flush()
        for sc in (mesh_sc, seq_sc):
            sc.apply()
            sc.sync()
    assert mesh_sc._pool.migration_count > 0
    members_after_migration = [
        list(m) for m in mesh_sc._pool.shard_members
    ]

    # ONE apply: deferred traffic for the MIGRATED pool members plus
    # a new doc overflowing into the pool (admission rebuild) in the
    # same settle
    late_c, late_s = _open_doc(server, [mesh_sc, seq_sc], "doc-late")
    docs.append("doc-late")
    containers["doc-late"], strings["doc-late"] = late_c, late_s
    for doc in docs[:3]:
        for _ in range(3):
            strings[doc].insert_text(0, "AB")
        containers[doc].flush()
    for _ in range(20):
        late_s.insert_text(0, "qrstuvwx")
    late_c.flush()
    for sc in (mesh_sc, seq_sc):
        sc.apply()
        sc.sync()
    assert mesh_sc.pooled_docs() == 4
    assert seq_sc.pooled_docs() == 4
    # the rebuild must respect the migrated placement, not undo it
    for shard, before in enumerate(members_after_migration):
        now = mesh_sc._pool.shard_members[shard]
        assert now[:len(before)] == before
    _assert_parity([mesh_sc, seq_sc], docs, strings)

    # second interleaving: round N overflows a FRESH primary doc with
    # the flag unsettled (pipelined default is on); round N+1 packs
    # fresh ops for a migrated pool member, and its LEADING settle
    # runs round N's recovery rebuild mid-flight — pre-watermark code
    # would apply those ops twice
    x_c, x_s = _open_doc(server, [mesh_sc, seq_sc], "doc-x")
    docs.append("doc-x")
    containers["doc-x"], strings["doc-x"] = x_c, x_s
    for _ in range(20):
        x_s.insert_text(0, "qrstuvwx")
    x_c.flush()
    for sc in (mesh_sc, seq_sc):
        sc.apply()          # NO sync: recovery defers to next settle
    for _ in range(3):
        strings["doc-0"].insert_text(0, "Z")
    containers["doc-0"].flush()
    for sc in (mesh_sc, seq_sc):
        sc.apply()
        sc.sync()
    assert mesh_sc.pooled_docs() == 5
    _assert_parity([mesh_sc, seq_sc], docs, strings)


# ======================================================================
# metrics + multi-shard CI subprocess (the tier-1 fixture satellite)


def test_mesh_pool_metrics_registered():
    from fluidframework_tpu.obs import metrics as obs_metrics

    server = LocalServer()
    mesh_sc, _seq, docs, containers, strings = _hotspot_pair(server)
    for doc in docs:
        _grow_into_pool(containers[doc], strings[doc], n_chunks=20)
    mesh_sc.apply()
    mesh_sc.sync()
    for _ in range(4):
        for doc in docs:
            n = 12 if doc == "doc-0" else 1
            for _ in range(n):
                strings[doc].insert_text(0, "XY")
            containers[doc].flush()
        mesh_sc.apply()
        mesh_sc.sync()
    flat = obs_metrics.REGISTRY.flat()
    assert flat.get('mesh_pool_members{shard="0"}', 0) >= 1
    assert flat.get("mesh_pool_dispatches_total", 0) >= 1
    assert flat.get("mesh_pool_watermark_ops", 0) > 0
    assert flat.get("mesh_pool_migrations_total", 0) >= 1
    assert "mesh_pool_shard_imbalance" in flat


def test_mesh_pool_on_4_device_cpu_subprocess(mesh_cpu_subprocess):
    """Multi-shard paths must run on CPU-only CI regardless of the
    parent session's device flags: the conftest fixture spawns a
    subprocess pinned to XLA_FLAGS=--xla_force_host_platform_
    device_count=4 and the mini hot-spot parity script must pass
    there on a real 4-shard mesh."""
    out = mesh_cpu_subprocess(
        """
import jax
jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 4, jax.devices()
from fluidframework_tpu.parallel import make_mesh
from fluidframework_tpu.service.tpu_sidecar import select_pool
from fluidframework_tpu.testing import FuzzConfig, record_op_stream
from fluidframework_tpu.ops import encode_stream, extract_text
from fluidframework_tpu.protocol.messages import MessageType

pool = select_pool(make_mesh(jax.devices()), 128)
oracle = select_pool(make_mesh(jax.devices()[:1]), 128, route="mesh")
texts, streams, o_streams = [], [], []
for i in range(6):
    text, msgs = record_op_stream(
        FuzzConfig(n_clients=2, n_steps=12, seed=300 + i))
    ops = [m for m in msgs if m.type == MessageType.OPERATION]
    streams.append(encode_stream(ops))
    o_streams.append(encode_stream(ops))
    texts.append(text)
assert pool.admit(list(range(6)), streams) == []
assert oracle.admit(list(range(6)), o_streams) == []
assert pool.n_shards == 4
for src in (streams, o_streams):
    fetched = (pool if src is streams else oracle).fetch()
    row_of = (pool if src is streams else oracle).row_of
    for slot in range(6):
        assert extract_text(fetched, src[slot], row_of[slot]) == \\
            texts[slot], slot
print("MESH4-OK")
""")
    assert "MESH4-OK" in out
