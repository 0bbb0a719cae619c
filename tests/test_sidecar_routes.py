"""Differential sidecar-level parity across the two executor routes.

The egwalker executor's bit-identical contract is pinned at the kernel
level (tests/test_walker_differential.py, tests/test_event_graph.py);
this suite pins it at the SERVICE level — two sidecars on the same
sequenced stream, one per route (scan / egwalker), must serve
identical ``text()`` and ``signature()`` through every policy
transition: steady windows, the 2x regrow ladder, host eviction at the
ladder top, the seq-sharded pool, and the one semantic divergence the
walker has — post-overflow PARKING (the walker stops applying a doc's
window at the failing span while the scan keeps going, and scans its
concurrent SUFFIX onto the parked prefix; the sidecar's recovery
re-applies the whole window from the pre-dispatch snapshot, which must
erase the difference).
"""
import random

import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.service import LocalServer, TpuMergeSidecar


ROUTES = ("scan", "egwalker")


def _pair(**kw):
    """One sidecar per route, identical otherwise."""
    return {r: TpuMergeSidecar(executor=r, **kw) for r in ROUTES}


def _open_doc(server, sidecars, doc, client_id=None):
    factory = LocalDocumentServiceFactory(server)
    for sc in sidecars.values():
        sc.subscribe(server, doc, "d", "s")
    c = Container.load(factory.create_document_service(doc),
                       client_id=client_id or f"{doc}-w")
    s = c.runtime.create_datastore("d").create_channel(
        "sharedstring", "s")
    return c, s


def _assert_parity(sidecars, docs, oracle=None):
    scan = sidecars["scan"]
    for doc in docs:
        t_scan = scan.text(doc, "d", "s")
        sig_scan = scan.signature(doc, "d", "s")
        for route in ROUTES[1:]:
            assert t_scan == sidecars[route].text(doc, "d", "s"), (
                f"text route divergence ({route}) on {doc}")
            assert sig_scan == sidecars[route].signature(
                doc, "d", "s"), (
                f"signature route divergence ({route}) on {doc}")
        if oracle is not None and doc in oracle:
            assert t_scan == oracle[doc].get_text(), (
                f"all routes diverged from the oracle on {doc}")


@pytest.mark.slow
def test_routes_agree_on_steady_multidoc_traffic():
    rng = random.Random(7)
    server = LocalServer()
    sidecars = _pair(max_docs=8, capacity=256)
    docs = [f"doc-{i}" for i in range(4)]
    strings = {}
    containers = {}
    for doc in docs:
        c, s = _open_doc(server, sidecars, doc)
        containers[doc], strings[doc] = c, s
    for i in range(50):
        doc = rng.choice(docs)
        s = strings[doc]
        length = s.get_length()
        roll = rng.random()
        if length > 4 and roll < 0.3:
            start = rng.randint(0, length - 2)
            s.remove_text(start, rng.randint(start + 1, length))
        elif length > 2 and roll < 0.45:
            s.annotate_range(0, rng.randint(1, length),
                             {"k": rng.randint(1, 3)})
        else:
            s.insert_text(rng.randint(0, length),
                          rng.choice(["ab", "xyz", "q"]))
        containers[doc].flush()
        if rng.random() < 0.3:
            for sc in sidecars.values():
                sc.apply()
    for sc in sidecars.values():
        sc.apply()
    _assert_parity(sidecars, docs, strings)
    for route in ROUTES:
        assert not sidecars[route].overflowed(), route


@pytest.mark.slow
def test_routes_agree_through_grow_ladder():
    """Windows big enough to overflow a 16-slot slab force the regrow
    path — where the walker's overflow PARKING differs from the
    scan mid-window, and recovery must reconverge them."""
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=16, max_capacity=512)
    c, s = _open_doc(server, sidecars, "doc")
    for i in range(40):
        s.insert_text(0, "abcdefgh")
        c.flush()
        if i % 3 == 2 and s.get_length() > 6:
            s.remove_text(2, 5)
            c.flush()
    for sc in sidecars.values():
        sc.apply()
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].grow_count >= 1, route
        assert sidecars[route].host_mode_docs() == 0, route
    _assert_parity(sidecars, ["doc"], {"doc": s})


def test_routes_agree_on_overflow_parking_within_one_window():
    """The overflow-parking case proper: ONE window whose ops keep
    coming after the capacity overflow point. The scan executor keeps
    applying post-overflow ops (garbage-tolerant: the doc is flagged),
    the walker parks the doc at its pre-span state — the
    sidecar policy layer re-applies the window from the snapshot at
    the doubled capacity, so the served state must be identical."""
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=16, max_capacity=256)
    c, s = _open_doc(server, sidecars, "doc")
    # a single flush cycle delivering far more segments than capacity:
    # everything lands in ONE apply window on both routes
    for i in range(30):
        s.insert_text(0, "wxyz")
    c.flush()
    for sc in sidecars.values():
        sc.apply()   # one dispatch: overflow mid-window on both
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].grow_count >= 1, route
    _assert_parity(sidecars, ["doc"], {"doc": s})
    for route in ROUTES:
        assert not sidecars[route].overflowed(), route


def test_routes_agree_through_eviction_and_recovery():
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=16, max_capacity=16)
    c, s = _open_doc(server, sidecars, "big")
    c2, s2 = _open_doc(server, sidecars, "small")
    for i in range(40):
        s.insert_text(0, "abcdefgh")
        c.flush()
    s2.insert_text(0, "tiny")
    c2.flush()
    for sc in sidecars.values():
        sc.apply()
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].host_mode_docs() == 1, route
    # post-eviction traffic keeps flowing on both routes
    s.insert_text(0, "MORE")
    s2.insert_text(4, "!")
    c.flush()
    c2.flush()
    for sc in sidecars.values():
        sc.apply()
    _assert_parity(sidecars, ["big", "small"],
                   {"big": s, "small": s2})


def test_routes_agree_with_pool_tier():
    """Grow ladder -> seq-sharded pool admission -> continued pooled
    collaboration, on both routes (single-shard mesh: the pool
    applies the plain scan to its table there, whatever the route)."""
    import jax

    from fluidframework_tpu.parallel import make_seq_mesh

    mesh = make_seq_mesh(jax.devices()[:1])
    server = LocalServer()
    sidecars = _pair(max_docs=2, capacity=16, max_capacity=32,
                     seq_mesh=mesh, pool_capacity=256)
    c, s = _open_doc(server, sidecars, "big")
    for i in range(40):
        s.insert_text(0, "abcdefgh")
        c.flush()
    for sc in sidecars.values():
        sc.apply()
        sc.sync()
    for route in ROUTES:
        assert sidecars[route].pooled_docs() == 1, route
    # pooled docs keep collaborating through the pool dispatch path
    for i in range(4):
        s.insert_text(0, "Q")
        c.flush()
    for sc in sidecars.values():
        sc.apply()
    _assert_parity(sidecars, ["big"], {"big": s})
