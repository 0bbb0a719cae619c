"""Sidecar overflow recovery + insert-props kernel fidelity.

A document that outgrows its device slab or exceeds the interned
property channels must never be silently wrong: the sidecar regrows
the slab (capacity ladder) or evicts the doc to a full-fidelity host
replica.
"""
import random

import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.models.mergetree import MergeTreeClient
from fluidframework_tpu.ops import (
    apply_window,
    build_batch,
    encode_stream,
    extract_signature,
    extract_text,
    fetch,
    make_table,
)
from fluidframework_tpu.ops.host_replay import replay_encoded
from fluidframework_tpu.service import LocalServer, TpuMergeSidecar
from fluidframework_tpu.testing import FuzzConfig, record_op_stream


def _session(server, sidecar, doc, n_chunks=40, chunk="abcdefgh",
             props=None):
    factory = LocalDocumentServiceFactory(server)
    sidecar.subscribe(server, doc, "d", "s")
    c = Container.load(factory.create_document_service(doc),
                       client_id=f"{doc}-writer")
    s = c.runtime.create_datastore("d").create_channel("sharedstring", "s")
    for i in range(n_chunks):
        if props is not None:
            s.insert_text(0, chunk, dict(props))
        else:
            s.insert_text(0, chunk)
        c.flush()
        # segment churn: removes create splits/tombstones
        if i % 3 == 2 and s.get_length() > 6:
            s.remove_text(2, 5)
            c.flush()
    return c, s


def test_overflow_grows_capacity_ladder():
    server = LocalServer()
    sidecar = TpuMergeSidecar(max_docs=2, capacity=16, max_capacity=512)
    c, s = _session(server, sidecar, "doc")
    sidecar.apply()
    sidecar.sync()  # pipelined dispatch: recovery runs at settle
    assert sidecar.grow_count >= 1, "expected slab growth"
    assert sidecar.host_mode_docs() == 0
    assert not sidecar.overflowed()
    assert sidecar.text("doc", "d", "s") == s.get_text()


def test_overflow_evicts_to_host_at_max_capacity():
    server = LocalServer()
    sidecar = TpuMergeSidecar(max_docs=2, capacity=16, max_capacity=16)
    c, s = _session(server, sidecar, "doc")
    sidecar.apply()
    sidecar.sync()  # pipelined dispatch: recovery runs at settle
    assert sidecar.evict_count >= 1
    assert sidecar.host_mode_docs() == 1
    assert not sidecar.overflowed()
    assert sidecar.text("doc", "d", "s") == s.get_text()
    # later traffic keeps flowing to the host replica
    s.insert_text(0, "MORE")
    c.flush()
    sidecar.apply()
    assert sidecar.text("doc", "d", "s") == s.get_text()


def test_excess_prop_channels_evicts_to_host():
    server = LocalServer()
    sidecar = TpuMergeSidecar(max_docs=2, capacity=256)
    factory = LocalDocumentServiceFactory(server)
    sidecar.subscribe(server, "doc", "d", "s")
    c = Container.load(factory.create_document_service("doc"),
                       client_id="w")
    s = c.runtime.create_datastore("d").create_channel("sharedstring", "s")
    s.insert_text(0, "hello world")
    c.flush()
    for i, key in enumerate(["k1", "k2", "k3", "k4", "k5", "k6"]):
        s.annotate_range(0, 5, {key: i + 1})
        c.flush()
    sidecar.apply()
    assert sidecar.host_mode_docs() == 1
    assert sidecar.text("doc", "d", "s") == s.get_text()
    assert s.client.mergetree.segments[0].props == {
        "k1": 1, "k2": 2, "k3": 3, "k4": 4, "k5": 5, "k6": 6,
    }


def test_healthy_docs_unaffected_by_neighbor_eviction():
    server = LocalServer()
    sidecar = TpuMergeSidecar(max_docs=2, capacity=16, max_capacity=16)
    c1, s1 = _session(server, sidecar, "big")        # overflows
    factory = LocalDocumentServiceFactory(server)
    sidecar.subscribe(server, "small", "d", "s")
    c2 = Container.load(factory.create_document_service("small"),
                        client_id="w2")
    s2 = c2.runtime.create_datastore("d").create_channel(
        "sharedstring", "s")
    s2.insert_text(0, "tiny")
    c2.flush()
    sidecar.apply()
    sidecar.sync()  # pipelined dispatch: recovery runs at settle
    assert sidecar.host_mode_docs() == 1
    assert sidecar.text("big", "d", "s") == s1.get_text()
    assert sidecar.text("small", "d", "s") == s2.get_text()


# ----------------------------------------------------------------------
# insert-with-props kernel fidelity

@pytest.mark.parametrize("seed", range(8))
def test_kernel_insert_props_differential(seed):
    text, stream = record_op_stream(FuzzConfig(
        n_clients=3, n_steps=100, seed=seed * 13 + 5,
        remove_weight=0.25, annotate_weight=0.1,
        insert_props_weight=0.5,
    ))
    enc = encode_stream(stream)
    batch = build_batch([enc])
    table = apply_window(make_table(1, 1024), batch)
    np_table = fetch(table)
    assert not np_table["overflow"].any()
    assert extract_text(np_table, enc, 0) == text

    from fluidframework_tpu.ops.host_bridge import interned_signature

    obs = MergeTreeClient("observer")
    obs.start_collaboration("observer")
    for msg in stream:
        obs.apply_msg(msg)
    assert extract_signature(np_table, enc, 0) == interned_signature(
        obs, enc)


# ----------------------------------------------------------------------
# host replay twin: python-encoded vs kernel (and implicitly vs C++,
# which test_native_replay pins to the kernel)

@pytest.mark.parametrize("seed", range(8))
def test_host_replay_matches_kernel(seed):
    text, stream = record_op_stream(FuzzConfig(
        n_clients=3, n_steps=90, seed=seed * 7 + 1,
        remove_weight=0.3, annotate_weight=0.15,
        insert_props_weight=0.3,
    ))
    enc = encode_stream(stream)
    batch = build_batch([enc])
    table = apply_window(make_table(1, 1024), batch)
    np_table = fetch(table)
    assert not np_table["overflow"].any()
    host = replay_encoded(enc.ops).as_table()
    assert extract_text(host, enc, 0) == extract_text(np_table, enc, 0)
    assert extract_signature(host, enc, 0) == extract_signature(
        np_table, enc, 0)


def test_post_eviction_new_prop_value_signature():
    """code-review r2: ops after eviction bypass the encoder, so the
    signature path must intern unseen values at read time instead of
    crashing."""
    server = LocalServer()
    sidecar = TpuMergeSidecar(max_docs=2, capacity=16, max_capacity=16)
    c, s = _session(server, sidecar, "doc")
    sidecar.apply()
    sidecar.sync()  # pipelined dispatch: recovery runs at settle
    assert sidecar.host_mode_docs() == 1
    s.annotate_range(0, 4, {"bold": 777})  # value the encoder never saw
    c.flush()
    sidecar.apply()
    sig = sidecar.signature("doc", "d", "s")  # must not raise
    assert len(sig) == s.get_length()
    assert sidecar.text("doc", "d", "s") == s.get_text()
