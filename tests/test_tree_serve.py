"""Tree serving plane: service-level differential parity across BOTH
tree executor routes (atom / macro), the test_sidecar_routes pattern
instantiated for the second kernelized DDS.

Two sidecars on the same sequenced stream — one per route — must
serve identical ``signature()`` through every policy transition:
steady windows, the 2x regrow ladder, overflow PARKING within one
window (both routes park conservatively at the shared predicate; the
snapshot re-apply at doubled capacity must erase any difference),
host eviction (capacity, ring-straggler, device-inexpressible), the
pooled tier, and the ChannelKindRouter ingress boundary.

The centerpiece is the THREE-WRITER concurrent fuzz: moves racing
removes (and annotates racing both) across three blind writers,
flushed in shuffled order, must converge bit-identical across both
device routes AND against the scalar SharedTree/EditManager oracle —
through the real LocalServer -> Container -> sidecar dispatch loop,
not a synthetic commit feed.
"""
import json
import random

import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.models.tree import changeset as cs
from fluidframework_tpu.models.tree import node
from fluidframework_tpu.protocol.messages import (
    MessageType,
    SequencedMessage,
)
from fluidframework_tpu.service import (
    LocalServer,
    TpuMergeSidecar,
    TreeSidecar,
)
from fluidframework_tpu.service.tree_sidecar import ChannelKindRouter
from fluidframework_tpu.testing.tree_fuzz import random_change_with_moves
from test_walker_differential import smoke_seeds

ROUTES = ("atom", "macro")


def _pair(**kw):
    """One tree sidecar per route, identical otherwise."""
    return {r: TreeSidecar(executor=r, **kw) for r in ROUTES}


def _open_doc(server, sidecars, doc, client_id=None):
    factory = LocalDocumentServiceFactory(server)
    for sc in sidecars.values():
        sc.subscribe(server, doc, "d", "t")
    c = Container.load(factory.create_document_service(doc),
                       client_id=client_id or f"{doc}-w")
    t = c.runtime.create_datastore("d").create_channel(
        "sharedtree", "t")
    return c, t


def _join(server, doc, client_id):
    """Second/third writer on an already-created document."""
    factory = LocalDocumentServiceFactory(server)
    c = Container.load(factory.create_document_service(doc),
                       client_id=client_id)
    t = c.runtime.get_datastore("d").get_channel("t")
    return c, t


def _sig_of(tree):
    """The scalar oracle in the sidecar's signature convention."""
    return json.dumps({"root": tree.root().get("root", [])},
                      sort_keys=True, default=str)


def _assert_parity(sidecars, docs, oracle=None):
    atom = sidecars["atom"]
    for doc in docs:
        sig = atom.signature(doc, "d", "t")
        for route in ROUTES[1:]:
            assert sig == sidecars[route].signature(doc, "d", "t"), (
                f"signature route divergence ({route}) on {doc}")
        if oracle is not None and doc in oracle:
            assert sig == _sig_of(oracle[doc]), (
                f"both routes diverged from the oracle on {doc}")


def mk_nodes(n, base=0):
    return [node("n", value=base + i) for i in range(n)]


# ======================================================================
# the tentpole differential: three blind writers, moves racing removes


@pytest.mark.parametrize("seed", smoke_seeds(10, {0, 4, 7}))
def test_three_writer_concurrent_move_fuzz(seed):
    """Three writers author concurrently (moves, removes, inserts and
    annotates all racing), flush in shuffled order, for several
    rounds. All scalar replicas converge (EditManager), and both
    device routes serve that exact state through the real dispatch
    loop."""
    rng = random.Random(seed * 101 + 13)
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=64, max_capacity=1024)
    c1, t1 = _open_doc(server, sidecars, "doc", client_id="alice")
    t1.insert_nodes(("root",), 0, mk_nodes(6))
    c1.flush()
    c2, t2 = _join(server, "doc", "bob")
    c3, t3 = _join(server, "doc", "carol")
    writers = [(c1, t1, "A"), (c2, t2, "B"), (c3, t3, "C")]

    for rnd in range(5):
        # author concurrently: every writer edits its CURRENT view
        # before anyone flushes
        for _c, t, uid in writers:
            base_nodes = t.get_field(("root",))
            t.apply_changeset(random_change_with_moves(
                rng, base_nodes, f"{uid}{rnd}"))
        order = list(writers)
        rng.shuffle(order)
        for c, _t, _uid in order:
            c.flush()
        if rng.random() < 0.5:
            for sc in sidecars.values():
                sc.apply()

    # scalar convergence first (the oracle is meaningful) ...
    sig1 = _sig_of(t1)
    assert sig1 == _sig_of(t2) == _sig_of(t3), "scalar replicas split"
    # ... then both device routes serve exactly that state
    for sc in sidecars.values():
        sc.apply()
        sc.sync()
    _assert_parity(sidecars, ["doc"], {"doc": t1})
    for route in ROUTES:
        assert not sidecars[route].overflowed(), route


# ======================================================================
# policy transitions, the test_sidecar_routes ladder


@pytest.mark.slow
def test_routes_agree_on_steady_multidoc_traffic():
    rng = random.Random(11)
    server = LocalServer()
    sidecars = _pair(max_docs=8, capacity=256)
    docs = [f"doc-{i}" for i in range(4)]
    trees, containers = {}, {}
    for doc in docs:
        c, t = _open_doc(server, sidecars, doc)
        t.insert_nodes(("root",), 0, mk_nodes(4))
        c.flush()
        containers[doc], trees[doc] = c, t
    for i in range(40):
        doc = rng.choice(docs)
        t = trees[doc]
        n = len(t.get_field(("root",)))
        roll = rng.random()
        if n > 2 and roll < 0.25:
            start = rng.randint(0, n - 2)
            t.delete_nodes(("root",), start,
                           rng.randint(1, n - start))
        elif n >= 2 and roll < 0.5:
            src = rng.randint(0, n - 2)
            t.move_nodes(("root",), src, 1,
                         rng.choice([0, n]))
        elif n > 0 and roll < 0.7:
            t.set_value(("root",), rng.randint(0, n - 1),
                        rng.randint(100, 199))
        else:
            t.insert_nodes(("root",), rng.randint(0, n),
                           mk_nodes(rng.randint(1, 2), 500))
        containers[doc].flush()
        if rng.random() < 0.3:
            for sc in sidecars.values():
                sc.apply()
    for sc in sidecars.values():
        sc.apply()
    _assert_parity(sidecars, docs, trees)
    for route in ROUTES:
        assert not sidecars[route].overflowed(), route


@pytest.mark.slow
def test_routes_agree_through_grow_ladder():
    """Windows big enough to overflow a 16-slot slab force the regrow
    path: both routes PARK the doc at the shared predicate and the
    snapshot re-apply at doubled capacity must reconverge them."""
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=16, max_capacity=512)
    c, t = _open_doc(server, sidecars, "doc")
    for i in range(30):
        t.insert_nodes(("root",), 0, mk_nodes(4, i * 10))
        c.flush()
        if i % 4 == 3 and len(t.get_field(("root",))) > 6:
            t.delete_nodes(("root",), 2, 5)
            c.flush()
    for sc in sidecars.values():
        sc.apply()
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].grow_count >= 1, route
        assert sidecars[route].host_mode_docs() == 0, route
    _assert_parity(sidecars, ["doc"], {"doc": t})


def test_routes_agree_on_overflow_parking_within_one_window():
    """ONE window whose attaches keep coming past the capacity point:
    the kernel parks the doc (state, ring and overflow all predate
    the window — the park contract) and the sidecar re-applies the
    whole window from the snapshot at the doubled capacity. The blind
    burst stays UNDER the trunk ring depth (a deeper one is a ring
    eviction by design — see the straggler test)."""
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=16, max_capacity=256)
    c, t = _open_doc(server, sidecars, "doc")
    for i in range(7):
        t.insert_nodes(("root",), 0, mk_nodes(4, i * 10))
    c.flush()
    for sc in sidecars.values():
        sc.apply()   # one dispatch: overflow mid-window on both
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].grow_count >= 1, route
        assert not sidecars[route].overflowed(), route
    _assert_parity(sidecars, ["doc"], {"doc": t})


def test_routes_agree_through_eviction_and_recovery():
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=16, max_capacity=16)
    c, t = _open_doc(server, sidecars, "big")
    c2, t2 = _open_doc(server, sidecars, "small")
    for i in range(20):
        t.insert_nodes(("root",), 0, mk_nodes(2, i * 10))
        c.flush()
    t2.insert_nodes(("root",), 0, mk_nodes(3))
    c2.flush()
    for sc in sidecars.values():
        sc.apply()
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].host_mode_docs() == 1, route
    # post-eviction traffic keeps flowing on both routes (host
    # replica ingest path), small doc stays on device
    t.move_nodes(("root",), 0, 1, 4)
    t2.set_value(("root",), 0, 42)
    c.flush()
    c2.flush()
    for sc in sidecars.values():
        sc.apply()
    _assert_parity(sidecars, ["big", "small"],
                   {"big": t, "small": t2})


def test_ring_straggler_evicts_to_host():
    """A commit whose ref predates the device trunk ring takes the
    host path by design: the ring holds the last TRUNK_RING rebased
    trunk commits, so a straggler needing more is evicted BEFORE its
    encode (both routes, same trigger, same served state).
    Local containers capture refs at flush, so the straggler arrives
    as a synthetic sequenced message through the real ingest path."""
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=256)
    c, t = _open_doc(server, sidecars, "doc", client_id="w")
    t.insert_nodes(("root",), 0, mk_nodes(4))
    c.flush()
    for i in range(20):
        t.set_value(("root",), 0, i)
        c.flush()
    last = max(sc._last_ingested["doc"] for sc in sidecars.values())
    change = cs.stamp({"root": [cs.skip(1), cs.mod(value={
        "new": 999, "old": None})]}, "straggler")
    for sc in sidecars.values():
        sc.ingest("doc", SequencedMessage(
            client_id="straggler", sequence_number=last + 1,
            minimum_sequence_number=0, client_sequence_number=1,
            reference_sequence_number=1,
            type=MessageType.OPERATION,
            contents={"kind": "op", "address": "d", "channel": "t",
                      "contents": {"type": "tree",
                                   "changes": change}},
        ))
        sc.apply()
        sc.sync()
    sig = sidecars["atom"].signature("doc", "d", "t")
    for route in ROUTES:
        assert sidecars[route].ring_evict_count == 1, route
        assert sidecars[route].host_mode_docs() == 1, route
        assert sidecars[route].signature("doc", "d", "t") == sig, route
    assert '"value": 999' in sig  # the straggler's edit was served


def test_inexpressible_changeset_evicts_to_host():
    """A changeset touching a non-root field is device-inexpressible
    (the slab holds the root sequence only): the full-fidelity host
    replica takes over, and reads keep serving the ROOT field
    identically on both routes and vs the scalar oracle."""
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=64)
    c, t = _open_doc(server, sidecars, "doc")
    t.insert_nodes(("root",), 0, mk_nodes(3))
    c.flush()
    t.apply_changeset(cs.stamp(
        {"side": [cs.ins(mk_nodes(2, 900))]}, "u-side"))
    c.flush()
    t.set_value(("root",), 0, 42)  # post-eviction traffic
    c.flush()
    for sc in sidecars.values():
        sc.apply()
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].evict_count == 1, route
        assert sidecars[route].host_mode_docs() == 1, route
    _assert_parity(sidecars, ["doc"], {"doc": t})


def test_routes_agree_with_pool_tier():
    """Grow ladder -> pooled-tier admission -> continued pooled
    collaboration on both routes (the pool's capacity unlock is a
    bigger chip-local slab; single-device mesh for select_pool API
    parity)."""
    import jax

    from fluidframework_tpu.parallel import make_seq_mesh

    mesh = make_seq_mesh(jax.devices()[:1])
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=16, max_capacity=32,
                     pool_mesh=mesh, pool_capacity=256)
    c, t = _open_doc(server, sidecars, "big")
    for i in range(25):
        t.insert_nodes(("root",), 0, mk_nodes(2, i * 10))
        c.flush()
    for sc in sidecars.values():
        sc.apply()
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].pooled_docs() == 1, route
        assert sidecars[route].host_mode_docs() == 0, route
    # pooled docs keep collaborating through the pool dispatch path
    for i in range(3):
        t.move_nodes(("root",), 0, 1, 5)
        c.flush()
    for sc in sidecars.values():
        sc.apply()
    _assert_parity(sidecars, ["big"], {"big": t})
    for route in ROUTES:
        assert sidecars[route]._pool.dispatch_count >= 1, route


def test_duplicate_delivery_dropped():
    """At-least-once upstream: re-ingesting an already-sequenced
    message must not extend the canonical histories (the merge
    sidecar's dedupe discipline)."""
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=64)
    c, t = _open_doc(server, sidecars, "doc")
    t.insert_nodes(("root",), 0, mk_nodes(3))
    c.flush()
    replay = SequencedMessage(
        client_id="doc-w", sequence_number=1,
        minimum_sequence_number=0, client_sequence_number=1,
        reference_sequence_number=0, type=MessageType.OPERATION,
        contents={"kind": "op", "address": "d", "channel": "t",
                  "contents": {"type": "tree", "changes": cs.stamp(
                      {"root": [cs.ins(mk_nodes(1))]}, "dup")}},
    )
    for sc in sidecars.values():
        slot = sc._slot("doc", "d", "t")
        depth = len(sc._raw[slot])
        assert depth >= 1
        sc.ingest("doc", replay)
        assert len(sc._raw[slot]) == depth, (
            "duplicate extended history")
        sc.apply()
    _assert_parity(sidecars, ["doc"], {"doc": t})


# ======================================================================
# ingress routing + pool selection


def test_channel_kind_router_routes_by_channel_type():
    """One document carrying BOTH channel kinds: the router feeds the
    string channel to the merge sidecar and the tree channel to the
    tree sidecar off the attach op's channelType — neither plane's
    state traverses the other's code."""
    server = LocalServer()
    merge_sc = TpuMergeSidecar(max_docs=4, capacity=64)
    tree_sc = TreeSidecar(max_docs=4, capacity=64)
    router = ChannelKindRouter(merge=merge_sc, tree=tree_sc)
    router.subscribe(server, "doc")
    factory = LocalDocumentServiceFactory(server)
    c = Container.load(factory.create_document_service("doc"),
                       client_id="w")
    ds = c.runtime.create_datastore("d")
    s = ds.create_channel("sharedstring", "s")
    t = ds.create_channel("sharedtree", "t")
    s.insert_text(0, "hello")
    t.insert_nodes(("root",), 0, mk_nodes(2))
    c.flush()
    s.insert_text(5, "!")
    t.move_nodes(("root",), 0, 1, 2)
    c.flush()
    merge_sc.apply()
    merge_sc.sync()
    tree_sc.apply()
    tree_sc.sync()
    assert merge_sc.text("doc", "d", "s") == s.get_text()
    assert tree_sc.signature("doc", "d", "t") == _sig_of(t)
    # cross-plane isolation: the tree sidecar never tracked the
    # string channel, the merge sidecar never tracked the tree one
    assert ("doc", "d", "s") not in tree_sc._slots
    assert ("doc", "d", "t") not in merge_sc._slots


def test_select_pool_tree_plane():
    import jax

    from fluidframework_tpu.parallel import make_seq_mesh
    from fluidframework_tpu.service.tpu_sidecar import select_pool
    from fluidframework_tpu.service.tree_sidecar import TreeSeqPool

    mesh = make_seq_mesh(jax.devices()[:1])
    pool = select_pool(mesh, None, executor="atom",
                       max_capacity=64, plane="tree")
    assert isinstance(pool, TreeSeqPool)
    assert pool.capacity == 256  # min(max_capacity * 4, 16384)
    with pytest.raises(ValueError, match="plane"):
        select_pool(mesh, None, plane="bogus")
    with pytest.raises(ValueError, match="executor"):
        select_pool(mesh, None, executor="scan", plane="tree")
