"""FlowDocument binding (the webflow-class example layer):
nested tag-pair markers, pair-consistent removal, css
token-list annotates, line breaks, comments — and the heavy
marker/annotate workload that doubles as a kernel stress source.

Mirrors examples/data-objects/webflow/src/document (index.ts:248
remove walk, :309 insertTags) and test/document.spec.ts.
"""
import random

import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.framework.flowdoc import (
    MARKER_TAG_BEGIN,
    MARKER_TAG_END,
    FlowDocument,
    flow_workload,
)
from fluidframework_tpu.loader import Container
from fluidframework_tpu.service.local_server import LocalServer


def make_pair(doc="fw"):
    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    a = Container.load(factory.create_document_service(doc),
                       client_id="alice")
    sa = a.runtime.create_datastore("app").create_channel(
        "sharedstring", "body")
    a.flush()
    b = Container.load(factory.create_document_service(doc),
                       client_id="bob")
    sb = b.runtime.get_datastore("app").get_channel("body")
    return server, (a, FlowDocument(sa, "alice")), \
        (b, FlowDocument(sb, "bob"))


def _pair_balance(doc):
    """begin/end marker multisets by pairId."""
    begins, ends = [], []
    for item in doc._items():
        if item[0] != "marker":
            continue
        _, rt, props = item
        if rt == MARKER_TAG_BEGIN:
            begins.append((props or {}).get("pairId"))
        elif rt == MARKER_TAG_END:
            ends.append((props or {}).get("pairId"))
    return sorted(begins), sorted(ends)


def test_tags_render_nested():
    _, (ca, da), (cb, db) = make_pair()
    da.insert_text(0, "alpha beta gamma")
    da.insert_tags(6, 10, "strong")   # 'beta'
    da.insert_tags(0, 18, "em")       # everything (incl. markers)
    ca.flush()
    runs = [(t, tags) for t, tags, _ in
            (r for b in db.render() for r in b.runs)]
    assert ("beta", ("em", "strong")) in runs
    assert ("alpha ", ("em",)) in runs


def test_remove_crossing_pair_removes_partner():
    _, (ca, da), (cb, db) = make_pair()
    da.insert_text(0, "abcdefgh")
    da.insert_tags(2, 6, "em")        # begin@2, end@7 (begin shifted)
    ca.flush()
    assert _pair_balance(db)[0] == _pair_balance(db)[1] != []
    # remove a range containing ONLY the begin marker
    da.remove(1, 4)
    ca.flush()
    b, e = _pair_balance(da)
    assert b == e == [], (b, e)       # orphan end removed too
    assert da.plain_text() == db.plain_text()


def test_remove_crossing_end_removes_begin():
    _, (ca, da), (cb, db) = make_pair()
    da.insert_text(0, "abcdefgh")
    da.insert_tags(1, 5, "code")
    ca.flush()
    # remove a range containing only the END marker
    da.remove(5, 8)
    ca.flush()
    b, e = _pair_balance(db)
    assert b == e == [], (b, e)


def test_line_breaks_and_headings_make_blocks():
    _, (ca, da), (cb, db) = make_pair()
    da.insert_text(0, "onetwo")
    da.insert_line_break(3)
    da.insert_paragraph(0, heading=2)
    ca.flush()
    blocks = db.render()
    kinds = [(b.kind, b.heading) for b in blocks]
    assert ("p", 2) in kinds and ("br", None) in kinds
    assert db.plain_text() == "onetwo"


def test_css_classes_split_runs_and_remove():
    _, (ca, da), (cb, db) = make_pair()
    da.insert_text(0, "styled text here")
    da.add_css_class(0, 6, "hot")
    da.add_css_class(3, 10, "cold")
    ca.flush()
    runs = [r for b in db.render() for r in b.runs]
    assert ("sty", (), frozenset({"hot"})) in runs
    assert ("led", (), frozenset({"hot", "cold"})) in runs
    da.remove_css_class(0, 16, "hot")
    ca.flush()
    assert all("hot" not in cls for _, _, cls in
               (r for b in db.render() for r in b.runs))


def test_comments_slide_with_edits():
    _, (ca, da), (cb, db) = make_pair()
    da.insert_text(0, "comment target")
    da.add_comment(8, 14, "look")
    ca.flush()
    db.insert_text(0, "XXX ")
    cb.flush()
    c = da.comments()[0]
    # endpoints anchor characters (end inclusive): still 'target'
    # after the remote prefix insert shifted everything right
    assert da.plain_text()[c["start"]:c["end"] + 1] == "target"
    assert c["author"] == "alice" and c["text"] == "look"


def test_concurrent_tag_inserts_converge():
    _, (ca, da), (cb, db) = make_pair()
    da.insert_text(0, "shared flowing text")
    ca.flush()
    da.insert_tags(0, 6, "em")
    db.insert_tags(7, 14, "strong")
    ca.flush()
    cb.flush()
    ca.flush()
    assert da.signature() == db.signature()
    assert [(b.kind, b.runs) for b in da.render()] == \
        [(b.kind, b.runs) for b in db.render()]


@pytest.mark.parametrize("seed", range(6))
def test_flow_workload_fuzz_converges(seed):
    """Two users hammer the flowed doc with the marker/annotate-heavy
    mix; content, tags, classes and comments all converge."""
    _, (ca, da), (cb, db) = make_pair()
    rng = random.Random(seed)
    for _ in range(8):
        flow_workload(da, rng, 5)
        flow_workload(db, rng, 5)
        if rng.random() < 0.7:
            ca.flush()
        if rng.random() < 0.7:
            cb.flush()
    ca.flush()
    cb.flush()
    ca.flush()
    assert da.plain_text() == db.plain_text(), seed
    assert da.signature() == db.signature(), seed
    assert [(b.kind, b.heading, b.runs) for b in da.render()] == \
        [(b.kind, b.heading, b.runs) for b in db.render()], seed
    assert da.comments() == db.comments(), seed


def test_recorded_flow_stream_is_kernel_exact():
    """The webflow-mix recorded stream (a corpus member) is
    kernel-encodable within the 4 device property channels and BOTH
    executors reproduce the scalar oracle on it."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from fluidframework_tpu.models.mergetree import MergeTreeClient
    from fluidframework_tpu.ops import (
        build_batch,
        encode_stream,
        make_table,
    )
    from fluidframework_tpu.ops.host_bridge import (
        extract_signature,
        fetch,
        interned_signature,
    )
    from fluidframework_tpu.ops.event_graph import apply_batch_egwalker
    from fluidframework_tpu.ops.merge_kernel import apply_window_impl
    from fluidframework_tpu.protocol.messages import MessageType
    from fluidframework_tpu.testing import record_flow_stream

    _, stream = record_flow_stream(seed=3, n_clients=3, n_steps=110)
    enc = encode_stream(stream)
    assert len(enc.prop_keys) <= 4
    batch = build_batch([enc])
    seq = fetch(apply_window_impl(make_table(1, 1024), batch))
    eg = fetch(apply_batch_egwalker(make_table(1, 1024), batch))
    obs = MergeTreeClient("o")
    obs.start_collaboration("o")
    for m in stream:
        if m.type == MessageType.OPERATION:
            obs.apply_msg(m)
    want = interned_signature(obs, enc)
    assert extract_signature(seq, enc, 0) == want
    assert extract_signature(eg, enc, 0) == want
