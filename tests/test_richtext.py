"""RichTextEditor binding (the prosemirror-class example
layer): paragraphs/marks/comments/cursors over
SharedString, concurrent editing convergence, cursor stability through
remote edits, and the fuzz workload."""
import random

import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.framework.richtext import (
    MARK_KEYS,
    RichTextEditor,
    editor_workload,
)
from fluidframework_tpu.loader import Container
from fluidframework_tpu.service.local_server import LocalServer


def make_pair(doc="rt"):
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
    return server, (a, RichTextEditor(sa, "alice")), \
        (b, RichTextEditor(sb, "bob"))


def test_typing_and_rendering():
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("Hello world")
    ea.split_paragraph(heading=2)
    ea.type_text("Section body")
    ca.flush()
    paras = eb.render()
    assert [p.text for p in paras] == ["Hello world", "Section body"]
    assert paras[1].style == {"heading": 2}
    assert eb.plain_text() == ea.plain_text()


def test_marks_apply_and_toggle_off():
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("make this bold")
    ea.set_cursor(5)
    ea.set_cursor(9, extend=True)
    ea.toggle_mark("bold")
    ca.flush()
    runs = eb.render()[0].runs
    assert ("this", frozenset({"bold"})) in runs
    # toggling again over the same span clears it
    ea.set_cursor(5)
    ea.set_cursor(9, extend=True)
    ea.toggle_mark("bold")
    ca.flush()
    assert all("bold" not in m for _, m in eb.render()[0].runs)


def test_stored_marks_caret_typing():
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("ab")
    ea.toggle_mark("italic")  # caret: stored mark
    ea.type_text("cd")
    ca.flush()
    runs = eb.render()[0].runs
    assert runs == [("ab", frozenset()),
                    ("cd", frozenset({"italic"}))]


def test_cursor_survives_remote_edits():
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("abcdef")
    ca.flush()
    eb.set_cursor(3)  # bob's caret between c and d
    # alice inserts at the front; bob's caret must slide right
    ea.set_cursor(0)
    ea.type_text("XY")
    ca.flush()
    cb.flush()
    assert eb.plain_text() == "XYabcdef"
    assert eb.cursor == 5  # still between c and d
    # alice deletes the region containing the caret: slides
    ea.set_cursor(0)
    ea.string.remove_text(2, 6)  # removes abcd
    ca.flush()
    assert eb.plain_text() == "XYef"
    assert 0 <= eb.cursor <= eb.length


def test_comment_slides_with_edits():
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("review this passage carefully")
    ca.flush()
    ea.add_comment(7, 19, "check wording")  # "this passage"
    ca.flush()
    # bob types at the front concurrently
    eb.set_cursor(0)
    eb.type_text(">> ")
    cb.flush()
    ca.flush()
    got = ea.comments()
    assert len(got) == 1
    c = got[0]
    assert ea.plain_text()[c["start"]:c["end"]] == "this passage"
    assert c["author"] == "alice" and c["text"] == "check wording"
    assert eb.comments() == got


def test_concurrent_editing_converges():
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("shared document")
    ca.flush()
    cb.flush()
    # concurrent: alice bolds while bob types in the middle
    ea.set_cursor(0)
    ea.set_cursor(6, extend=True)
    ea.toggle_mark("bold")
    eb.set_cursor(7)
    eb.type_text("rich ")
    ca.flush()
    cb.flush()
    assert ea.plain_text() == eb.plain_text()
    assert [p.runs for p in ea.render()] == \
        [p.runs for p in eb.render()]


@pytest.mark.parametrize("seed", range(6))
def test_workload_fuzz_converges(seed):
    """The editor workload generator: two users hammer the same doc
    with bursty typing/formatting/comments; everything converges at
    the binding level (render + comments identical)."""
    _, (ca, ea), (cb, eb) = make_pair()
    rng = random.Random(seed)
    for round_ in range(8):
        editor_workload(ea, rng, 6)
        editor_workload(eb, rng, 6)
        if rng.random() < 0.7:
            ca.flush()
        if rng.random() < 0.7:
            cb.flush()
    ca.flush()
    cb.flush()
    ca.flush()
    assert ea.plain_text() == eb.plain_text(), seed
    assert [(p.style, p.runs) for p in ea.render()] == \
        [(p.style, p.runs) for p in eb.render()], seed
    assert ea.comments() == eb.comments(), seed


def test_reconnect_offline_edits_replay():
    """Offline typing + formatting replays on reconnect — the editor
    session survives a connection blip (faultInjection-style)."""
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("stable base. ")
    ca.flush()
    cb.flush()
    ca.disconnect()
    ea.set_cursor(ea.length)
    ea.type_text("offline words")
    ea.set_cursor(0)
    ea.set_cursor(6, extend=True)
    ea.toggle_mark("code")
    # bob keeps editing while alice is away
    eb.set_cursor(eb.length)
    eb.type_text("(bob was here) ")
    cb.flush()
    ca.connect()
    ca.flush()
    cb.flush()
    ca.flush()
    assert ea.plain_text() == eb.plain_text()
    assert "offline words" in ea.plain_text()
    assert "(bob was here)" in ea.plain_text()
    assert [p.runs for p in ea.render()] == \
        [p.runs for p in eb.render()]


def test_workload_feeds_merge_kernel():
    """The binding's sequenced stream replays bit-faithfully through
    the batched device executors — the editor doubles as the kernel
    workload generator it was asked to be."""
    import dataclasses

    import numpy as np

    from fluidframework_tpu.ops import (
        build_batch, encode_stream, extract_text, fetch, make_table,
    )
    from fluidframework_tpu.ops.event_graph import apply_batch_egwalker
    from fluidframework_tpu.ops.merge_kernel import apply_window_impl
    from fluidframework_tpu.protocol.messages import MessageType

    server, (ca, ea), (cb, eb) = make_pair()
    rng = random.Random(42)
    for _ in range(5):
        editor_workload(ea, rng, 5)
        editor_workload(eb, rng, 5)
        ca.flush()
        cb.flush()
    ca.flush()
    msgs = []
    for msg in server.read_ops("rt", 0):
        env = msg.contents if isinstance(msg.contents, dict) else {}
        if (msg.type == MessageType.OPERATION
                and env.get("kind", "op") == "op"
                and env.get("address") == "app"
                and env.get("channel") == "body"):
            inner = env["contents"]
            if not hasattr(inner, "type"):
                # interval-collection op: rides the channel stream but
                # isn't a merge-tree op — the device path sees a noop
                msgs.append(dataclasses.replace(
                    msg, type=MessageType.NO_OP, contents=None,
                    client_id=None))
                continue
            msgs.append(dataclasses.replace(msg, contents=inner))
        else:
            msgs.append(dataclasses.replace(
                msg, type=MessageType.NO_OP, contents=None,
                client_id=None))
    enc = encode_stream(msgs)
    batch = build_batch([enc])
    seq_tab = fetch(apply_window_impl(make_table(1, 1024), batch))
    eg_tab = fetch(apply_batch_egwalker(make_table(1, 1024), batch))
    want = ea.plain_text()
    assert extract_text(seq_tab, enc, 0) == want
    assert extract_text(eg_tab, enc, 0) == want
    n = int(seq_tab["count"][0])
    for f in ("length", "seq", "client", "removed_seq"):
        assert np.array_equal(seq_tab[f][0, :n], eg_tab[f][0, :n])


def test_toggle_mark_across_paragraph_boundary_clears():
    """A fully-marked selection spanning a paragraph marker must
    CLEAR on toggle (the marker itself never carries the mark —
    code-review r4 reproduced the double-toggle bug)."""
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("aaa")
    ea.split_paragraph()
    ea.type_text("bbb")
    # bold both paragraphs' text separately
    ea.set_cursor(0)
    ea.set_cursor(3, extend=True)
    ea.toggle_mark("bold")
    ea.set_cursor(4)
    ea.set_cursor(7, extend=True)
    ea.toggle_mark("bold")
    ca.flush()
    assert all(
        m == frozenset({"bold"})
        for p in eb.render() for _, m in p.runs
    )
    # select ALL (spans the marker) and toggle: must clear
    ea.set_cursor(0)
    ea.set_cursor(ea.length, extend=True)
    ea.toggle_mark("bold")
    ca.flush()
    assert all(
        "bold" not in m for p in eb.render() for _, m in p.runs
    )


def test_comment_to_document_end_keeps_last_char():
    """A comment ending at the document end must cover the final
    character (end anchors on the last char with +1 bias — the clamp
    used to silently shorten the range)."""
    _, (ca, ea), (cb, eb) = make_pair()
    ea.type_text("note the last word")
    i = ea.plain_text().index("word")
    ea.add_comment(i, ea.length, "on the last word")
    ca.flush()
    c = eb.comments()[0]
    assert eb.text_span(c["start"], c["end"]) == "word"
