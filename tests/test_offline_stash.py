"""Container-level offline stash: closeAndGetPendingLocalState +
rehydrate (container.ts getPendingLocalState; sharedObject.ts:510
applyStashedOp) — edits made offline survive a full process-style
close/reload cycle and resubmit rebased.
"""
import json

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.service.local_server import LocalServer


def _setup():
    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    a = Container.load(factory.create_document_service("doc"),
                       client_id="alice")
    ds = a.runtime.create_datastore("d")
    text = ds.create_channel("sharedstring", "t")
    kv = ds.create_channel("sharedmap", "m")
    a.flush()
    text.insert_text(0, "base")
    kv.set("k", 1)
    a.flush()
    return server, factory, a


def test_stash_rehydrate_resubmits_offline_edits():
    server, factory, a = _setup()
    # go offline, keep editing
    a.disconnect()
    text = a.runtime.get_datastore("d").get_channel("t")
    kv = a.runtime.get_datastore("d").get_channel("m")
    text.insert_text(4, " + offline edit")
    kv.set("k", 2)
    kv.set("offline", True)
    a.flush()
    stash = a.close_and_get_pending_state()
    # the stash is JSON-safe (it would be written to disk)
    stash = json.loads(json.dumps(stash))
    assert len(stash["pending"]) >= 3

    # meanwhile another client edits the same document
    b = Container.load(factory.create_document_service("doc"),
                       client_id="bob")
    tb = b.runtime.get_datastore("d").get_channel("t")
    tb.insert_text(0, ">> ")
    b.flush()

    # rehydrate: stashed edits apply as pending, then resubmit on
    # connect, rebased over bob's interleaved edit
    a2 = Container.load(factory.create_document_service("doc"),
                        client_id="alice-2", pending_state=stash)
    t2 = a2.runtime.get_datastore("d").get_channel("t")
    k2 = a2.runtime.get_datastore("d").get_channel("m")
    a2.flush()
    b.flush()
    assert t2.get_text() == ">> base + offline edit"
    assert tb.get_text() == t2.get_text()
    assert k2.get("k") == 2
    assert k2.get("offline") is True
    assert b.runtime.get_datastore("d").get_channel("m").get("k") == 2


def test_stash_includes_unattached_channels():
    """A channel created offline rides the stash as a pending attach
    and materializes on rehydrate."""
    server, factory, a = _setup()
    a.disconnect()
    ds = a.runtime.get_datastore("d")
    fresh = ds.create_channel("sharedmap", "made-offline")
    fresh.set("born", "offline")
    a.flush()
    stash = json.loads(json.dumps(a.close_and_get_pending_state()))

    a2 = Container.load(factory.create_document_service("doc"),
                        client_id="alice-2", pending_state=stash)
    got = a2.runtime.get_datastore("d").get_channel("made-offline")
    assert got.get("born") == "offline"
    a2.flush()

    b = Container.load(factory.create_document_service("doc"),
                       client_id="bob")
    assert (b.runtime.get_datastore("d")
            .get_channel("made-offline").get("born")) == "offline"

def test_stash_refuses_with_inflight_ops():
    """Stashing with sent-but-unacked ops would double-apply them
    (they sequence AND resubmit); the container refuses unless forced
    (code-review r3)."""
    import pytest

    server, factory, a = _setup()
    a.pause_inbound()  # acks stop arriving
    text = a.runtime.get_datastore("d").get_channel("t")
    text.insert_text(4, "X")
    a.flush()  # sent while connected; ack is queued but unprocessed
    with pytest.raises(ValueError, match="in flight"):
        a.close_and_get_pending_state()


def test_stash_against_newer_summary_fails_clearly():
    """A service summary newer than the stash truncates the op log
    (scribe ack -> truncate_below), so the stash positions can no
    longer be rebased exactly; rehydrate must fail with a CLEAR error,
    not corrupt or KeyError (code-review r3)."""
    import pytest

    server, factory, a = _setup()
    a.disconnect()
    text = a.runtime.get_datastore("d").get_channel("t")
    text.insert_text(4, "!")
    a.flush()
    stash = json.loads(json.dumps(a.close_and_get_pending_state()))

    b = Container.load(factory.create_document_service("doc"),
                       client_id="bob")
    tb = b.runtime.get_datastore("d").get_channel("t")
    tb.insert_text(0, "# ")
    b.flush()
    b.summarize()  # service summary PAST the stash point

    with pytest.raises(ValueError, match="op retention"):
        Container.load(factory.create_document_service("doc"),
                       client_id="alice-2", pending_state=stash)


def test_every_channel_type_survives_stash_cycle():
    """Every shipped channel must rehydrate from
    an offline stash (apply_stashed_op), or offline sessions die on
    that channel. Drives each type through edit-offline -> stash ->
    rehydrate -> resubmit -> converge with a second client.

    (sharedsummaryblock is excluded: it is write-once pre-attach and
    receives no ops by contract.)"""
    from fluidframework_tpu.models.tree.forest import node

    edits = {
        "sharedstring": lambda ch: ch.insert_text(0, "x"),
        "sharedmap": lambda ch: ch.set("k", 2),
        "shareddirectory": lambda ch: (
            ch.create_sub_directory("sub"),
            ch.set("dk", 1, path="/sub"),
        ),
        "sharedcell": lambda ch: ch.set("v2"),
        "sharedcounter": lambda ch: ch.increment(5),
        "sharedmatrix": lambda ch: (
            ch.insert_rows(0, 1), ch.insert_cols(0, 1),
            ch.set_cell(0, 0, 7),
        ),
        "sharedtree": lambda ch: ch.insert_nodes(
            ("items",), 0, [node("item", value=1)]),
        "legacysharedtree": lambda ch: ch.apply(
            __import__(
                "fluidframework_tpu.models.legacy_tree",
                fromlist=["insert_tree"],
            ).insert_tree(
                [{"definition": "n", "identifier": "s1",
                  "payload": None}],
                __import__(
                    "fluidframework_tpu.models.legacy_tree",
                    fromlist=["place_at_start"],
                ).place_at_start("root", "items"),
            )),
        "sharedjson": lambda ch: ch.set(["k"], 1),
        "sharedpropertytree": lambda ch: (
            ch.insert_property("p", "Int32", 1), ch.commit()),
        "ink": lambda ch: ch.create_stroke(),
        "sharedquorum": lambda ch: ch.set("q", "v"),
        "taskmanager": lambda ch: ch.volunteer("job"),
        "consensusregistercollection": lambda ch: ch.write("r", 1),
        "consensusorderedcollection": lambda ch: ch.add("item"),
    }
    for type_name, edit in edits.items():
        server = LocalServer()
        factory = LocalDocumentServiceFactory(server)
        a = Container.load(factory.create_document_service("doc"),
                           client_id="alice")
        ch = a.runtime.create_datastore("d").create_channel(
            type_name, "c")
        a.flush()
        a.disconnect()
        edit(ch)
        a.flush()
        stash = json.loads(json.dumps(a.close_and_get_pending_state()))
        assert stash["pending"], type_name

        b = Container.load(factory.create_document_service("doc"),
                           client_id="bob")
        a2 = Container.load(factory.create_document_service("doc"),
                            client_id="alice-2", pending_state=stash)
        a2.flush()
        b.flush()
        a2.flush()
        cb = b.runtime.get_datastore("d").get_channel("c")
        c2 = a2.runtime.get_datastore("d").get_channel("c")
        if hasattr(c2, "signature"):
            assert c2.signature() == cb.signature(), type_name
