"""Local references + interval collections.

Mirrors packages/dds/sequence/src/test/intervalCollection.spec.ts and
merge-tree localReference tests: endpoints slide under concurrent edits,
delete-wins, pending-local-wins, reconnect rebase, convergence.
"""
import pytest

from fluidframework_tpu.models.mergetree import MergeTreeClient
from fluidframework_tpu.models.mergetree.localref import DETACHED_POSITION
from fluidframework_tpu.models.mergetree.ops import ReferenceType
from fluidframework_tpu.testing import MockCollabSession
from fluidframework_tpu.testing.runtime_mocks import ContainerSession


# ----------------------------------------------------------------------
# local references on a single client

def make_client(text="hello world"):
    c = MergeTreeClient("A")
    c.start_collaboration("A")
    c.insert_text_local(0, text)
    return c


def test_reference_tracks_position_under_inserts():
    c = make_client("abcdef")
    ref = c.create_reference(3, ReferenceType.SLIDE_ON_REMOVE)  # at 'd'
    assert c.reference_position(ref) == 3
    c.insert_text_local(0, "XY")  # shift right by 2
    assert c.reference_position(ref) == 5
    c.insert_text_local(8, "tail")  # after the ref: no move
    assert c.reference_position(ref) == 5


def test_reference_survives_segment_split():
    c = make_client("abcdef")
    ref = c.create_reference(4, ReferenceType.SLIDE_ON_REMOVE)  # at 'e'
    c.insert_text_local(2, "--")  # splits the abcdef segment
    assert c.reference_position(ref) == 6
    assert c.get_text() == "ab--cdef"


def test_reference_slides_forward_on_remove():
    """SlideOnRemove: anchor removed -> resolve to next surviving
    position (localReference.ts slide semantics)."""
    s, _ = make(2)
    a = s.client("A")
    s.do("A", "insert_text_local", 0, "abcdef")
    s.process_all()
    ref = a.create_reference(2, ReferenceType.SLIDE_ON_REMOVE)  # 'c'
    s.do("B", "remove_range_local", 1, 4)  # removes bcd
    s.process_all()
    assert a.get_text() == "aef"
    assert a.reference_position(ref) == 1  # slid to 'e'


def test_reference_slides_backward_at_document_end():
    s, _ = make(2)
    a = s.client("A")
    s.do("A", "insert_text_local", 0, "abc")
    s.process_all()
    ref = a.create_reference(2, ReferenceType.SLIDE_ON_REMOVE)  # 'c'
    s.do("B", "remove_range_local", 1, 3)  # removes bc, nothing after
    s.process_all()
    assert a.get_text() == "a"
    assert a.reference_position(ref) == 0  # slid backward to 'a'


def test_simple_reference_detaches_on_remove():
    s, _ = make(2)
    a = s.client("A")
    s.do("A", "insert_text_local", 0, "abc")
    s.process_all()
    ref = a.create_reference(1, ReferenceType.SIMPLE)
    s.do("B", "remove_range_local", 0, 3)
    s.process_all()
    assert a.reference_position(ref) == DETACHED_POSITION


def test_reference_survives_zamboni_compaction():
    """When the tombstone is compacted below the collab window, the
    reference transfers to its slide target and keeps resolving."""
    s, _ = make(2)
    a = s.client("A")
    s.do("A", "insert_text_local", 0, "abcdef")
    s.process_all()
    ref = a.create_reference(2, ReferenceType.SLIDE_ON_REMOVE)
    s.do("B", "remove_range_local", 1, 4)
    s.process_all()
    # advance the window far enough for zamboni with noop-ish traffic
    for _ in range(3):
        s.do("A", "insert_text_local", a.get_length(), "x")
        s.process_all()
        s.do("B", "insert_text_local", 0, "y")
        s.process_all()
    assert a.reference_position(ref) is not None
    pos = a.reference_position(ref)
    assert a.get_text()[pos] == "e"


def make(n=2):
    ids = [chr(ord("A") + i) for i in range(n)]
    return MockCollabSession(ids), ids


# ----------------------------------------------------------------------
# interval collections over container runtimes

def make_session(n=2):
    ids = [chr(ord("A") + i) for i in range(n)]
    s = ContainerSession(ids)
    for cid in ids:
        ds = s.runtime(cid).create_datastore("ds")
        ds.create_channel("sharedstring", "text")
    return s, ids


def strings(s, ids):
    return [
        s.runtime(cid).get_datastore("ds").get_channel("text")
        for cid in ids
    ]


def test_interval_add_converges():
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "hello world")
    s.process_all()
    sa.get_interval_collection("comments").add(0, 4, {"author": "A"})
    s.process_all()
    cb = sb.get_interval_collection("comments")
    assert len(cb) == 1
    iv = next(iter(cb))
    assert cb.endpoints(iv) == (0, 4)
    assert iv.props == {"author": "A"}
    assert sa.signature() == sb.signature()


def test_interval_slides_under_concurrent_text_edit():
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "hello world")
    s.process_all()
    # A intervals "world" while B inserts at the front concurrently
    ca = sa.get_interval_collection("c")
    ca.add(6, 10)
    sb.insert_text(0, ">> ")
    s.process_all()
    for ss in (sa, sb):
        coll = ss.get_interval_collection("c")
        iv = next(iter(coll))
        assert coll.endpoints(iv) == (9, 13)
        start, end = coll.endpoints(iv)
        assert ss.get_text()[start:end + 1] == "world"


def test_interval_endpoint_slides_when_text_removed():
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "abcdefgh")
    s.process_all()
    ca = sa.get_interval_collection("c")
    ca.add(2, 5)  # c..f
    s.process_all()
    sb.remove_text(0, 4)  # removes abcd; start anchor 'c' gone
    s.process_all()
    for ss in (sa, sb):
        coll = ss.get_interval_collection("c")
        iv = next(iter(coll))
        assert coll.endpoints(iv) == (0, 1)  # slid to 'e', end 'f'
    assert sa.signature() == sb.signature()


def test_interval_delete_wins_over_concurrent_change():
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "0123456789")
    s.process_all()
    ca = sa.get_interval_collection("c")
    iv = ca.add(1, 3)
    s.process_all()
    # A deletes while B concurrently changes
    ca.delete(iv.interval_id)
    sb.get_interval_collection("c").change(iv.interval_id, start=5, end=7)
    s.process_all()
    assert len(sa.get_interval_collection("c")) == 0
    assert len(sb.get_interval_collection("c")) == 0
    assert sa.signature() == sb.signature()


def test_interval_concurrent_change_lww():
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "0123456789")
    s.process_all()
    iv = sa.get_interval_collection("c").add(0, 1)
    s.process_all()
    # both change concurrently; B's op sequences second -> B wins
    sa.get_interval_collection("c").change(iv.interval_id, start=2, end=3)
    sb.get_interval_collection("c").change(iv.interval_id, start=6, end=7)
    s.flush("A")
    s.flush("B")
    s.process_all()
    for ss in (sa, sb):
        coll = ss.get_interval_collection("c")
        got = coll.endpoints(next(iter(coll)))
        assert got == (6, 7), got
    assert sa.signature() == sb.signature()


def test_interval_pending_local_change_wins_until_ack():
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "0123456789")
    s.process_all()
    iv = sa.get_interval_collection("c").add(0, 1)
    s.process_all()
    # B's change sequences first; A has a pending local change and must
    # keep its own value until the ack (then A's own op, sequenced
    # later, wins everywhere)
    sb.get_interval_collection("c").change(iv.interval_id, start=6, end=7)
    s.flush("B")
    sa.get_interval_collection("c").change(iv.interval_id, start=2, end=3)
    s.flush("A")
    ca = sa.get_interval_collection("c")
    assert ca.endpoints(next(iter(ca))) == (2, 3)
    s.process_all()
    for ss in (sa, sb):
        coll = ss.get_interval_collection("c")
        assert coll.endpoints(next(iter(coll))) == (2, 3)
    assert sa.signature() == sb.signature()


def test_interval_concurrent_prop_changes_merge_per_key():
    """Pending-wins is per aspect: A's pending prop 'a' must not drop
    B's concurrent change to prop 'b' (or B's endpoint change)."""
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "0123456789")
    s.process_all()
    iv = sa.get_interval_collection("c").add(0, 1)
    s.process_all()
    # B changes prop b and endpoints; sequences first
    sb.get_interval_collection("c").change(
        iv.interval_id, start=4, end=5, props={"b": 2}
    )
    s.flush("B")
    # A concurrently changes only prop a (no endpoints)
    sa.get_interval_collection("c").change(iv.interval_id, props={"a": 1})
    s.flush("A")
    s.process_all()
    for ss in (sa, sb):
        coll = ss.get_interval_collection("c")
        got = next(iter(coll))
        assert got.props == {"a": 1, "b": 2}, (ss, got.props)
        assert coll.endpoints(got) == (4, 5)
    assert sa.signature() == sb.signature()


def test_find_overlapping():
    s, ids = make_session(1)
    (sa,) = strings(s, ids)
    sa.insert_text(0, "0123456789")
    s.process_all()
    coll = sa.get_interval_collection("c")
    coll.add(0, 2)
    coll.add(4, 6)
    coll.add(8, 9)
    s.process_all()
    hits = coll.find_overlapping(1, 5)
    spans = sorted(coll.endpoints(iv) for iv in hits)
    assert spans == [(0, 2), (4, 6)]


def test_interval_summary_roundtrip():
    s, ids = make_session(1)
    (sa,) = strings(s, ids)
    sa.insert_text(0, "hello world")
    coll = sa.get_interval_collection("c")
    coll.add(6, 10, {"k": "v"})
    s.process_all()
    summary = sa.summarize_core()

    from fluidframework_tpu.models.sharedstring import SharedString
    fresh = SharedString("text")
    fresh.load_core(summary)
    assert fresh.get_text() == "hello world"
    lc = fresh.get_interval_collection("c")
    assert len(lc) == 1
    iv = next(iter(lc))
    assert lc.endpoints(iv) == (6, 10)
    assert iv.props == {"k": "v"}


def test_interval_reconnect_resubmits_pending_adds():
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "hello world")
    s.process_all()
    s.disconnect("A")
    # A adds an interval while offline; B edits text meanwhile
    sa.get_interval_collection("c").add(6, 10)
    sb.insert_text(0, ">> ")
    s.process_all()
    s.reconnect("A")
    s.process_all()
    for ss in (sa, sb):
        coll = ss.get_interval_collection("c")
        assert len(coll) == 1, ss
        iv = next(iter(coll))
        start, end = coll.endpoints(iv)
        assert ss.get_text()[start:end + 1] == "world"
    assert sa.signature() == sb.signature()


def test_interval_reconnect_resubmits_pending_prop_deletion():
    """ADVICE r1 #2: a pending property deletion ({key: None}) must
    survive reconnect as an explicit None entry, or peers keep the
    deleted key forever (signature divergence)."""
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "hello world")
    iv = sa.get_interval_collection("c").add(0, 4, props={"k": 1, "j": 2})
    s.process_all()
    s.disconnect("A")
    sa.get_interval_collection("c").change(
        iv.interval_id, props={"k": None})  # delete k while offline
    s.reconnect("A")
    s.process_all()
    for ss in (sa, sb):
        got = ss.get_interval_collection("c").get(iv.interval_id)
        assert "k" not in got.props, ss
        assert got.props["j"] == 2
    assert sa.signature() == sb.signature()


def test_interval_reconnect_resubmit_preserves_concurrent_remote_props():
    """Resubmission must cover ONLY locally-pending keys: a concurrent
    remote update to an untouched key must not be stomped by the
    reconnect replay."""
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "hello world")
    iv = sa.get_interval_collection("c").add(0, 4, props={"x": 1, "y": 1})
    s.process_all()
    s.disconnect("A")
    sa.get_interval_collection("c").change(iv.interval_id, props={"x": 9})
    sb.get_interval_collection("c").change(iv.interval_id, props={"y": 7})
    s.process_all()
    s.reconnect("A")
    s.process_all()
    for ss in (sa, sb):
        got = ss.get_interval_collection("c").get(iv.interval_id)
        assert got.props == {"x": 9, "y": 7}, ss
    assert sa.signature() == sb.signature()


def test_interval_reconnect_props_only_change_keeps_remote_endpoints():
    """A pending props-only change must not resubmit endpoints: a
    concurrent remote endpoint move would otherwise be overwritten."""
    s, ids = make_session(2)
    sa, sb = strings(s, ids)
    sa.insert_text(0, "hello world")
    iv = sa.get_interval_collection("c").add(0, 4, props={"x": 1})
    s.process_all()
    s.disconnect("A")
    sa.get_interval_collection("c").change(iv.interval_id, props={"x": 2})
    sb.get_interval_collection("c").change(iv.interval_id, start=6, end=10)
    s.process_all()
    s.reconnect("A")
    s.process_all()
    for ss in (sa, sb):
        coll = ss.get_interval_collection("c")
        got = coll.get(iv.interval_id)
        assert got.props == {"x": 2}, ss
        assert coll.endpoints(got) == (6, 10), ss
    assert sa.signature() == sb.signature()


# ---- endpoint stickiness (intervalCollection.ts IntervalStickiness) --

def _sticky_coll(stickiness, text="abcdef"):
    from fluidframework_tpu.models.intervals import IntervalCollection

    c = make_client(text)
    coll = IntervalCollection("x", c, lambda op: None)
    iv = coll.add(2, 4, stickiness=stickiness)  # "cd"
    return c, coll, iv


def test_stickiness_end_default_boundary_inserts():
    """Default (end-sticky): text at the END boundary joins, text at
    the START boundary stays out."""
    c, coll, iv = _sticky_coll("end")
    c.insert_text_local(4, "XY")          # end boundary
    lo, hi = coll.endpoints(iv)
    assert c.get_text()[lo:hi] == "cdXY"
    c.insert_text_local(2, "Z")           # start boundary
    lo, hi = coll.endpoints(iv)
    assert c.get_text()[lo:hi] == "cdXY"  # Z stayed outside


def test_stickiness_none_boundary_inserts_stay_out():
    c, coll, iv = _sticky_coll("none")
    c.insert_text_local(4, "XY")
    lo, hi = coll.endpoints(iv)
    assert c.get_text()[lo:hi] == "cd"
    c.insert_text_local(2, "Z")
    lo, hi = coll.endpoints(iv)
    assert c.get_text()[lo:hi] == "cd"


def test_stickiness_full_absorbs_both_boundaries():
    c, coll, iv = _sticky_coll("full")
    c.insert_text_local(4, "XY")
    c.insert_text_local(2, "Z")
    lo, hi = coll.endpoints(iv)
    assert c.get_text()[lo:hi] == "ZcdXY"


def test_stickiness_full_at_document_edges():
    """Sticky start at 0 stays 0; sticky end at the document end
    tracks appends."""
    from fluidframework_tpu.models.intervals import IntervalCollection

    c = make_client("abcdef")
    coll = IntervalCollection("x", c, lambda op: None)
    iv = coll.add(0, c.get_length(), stickiness="full")
    c.insert_text_local(0, ">>")
    c.insert_text_local(c.get_length(), "<<")
    lo, hi = coll.endpoints(iv)
    assert (lo, hi) == (0, c.get_length())


def test_stickiness_replicates_to_remote():
    """The add op carries stickiness; a remote replica anchors the
    same way and boundary inserts converge."""
    cs = ContainerSession(["A", "B"])
    for cid in ("A", "B"):
        cs.runtime(cid).create_datastore("d").create_channel(
            "sharedstring", "t")
    ta = cs.runtime("A").get_datastore("d").get_channel("t")
    tb = cs.runtime("B").get_datastore("d").get_channel("t")
    ta.insert_text(0, "abcdef")
    cs.process_all()
    ia = ta.get_interval_collection("c")
    ia.add(2, 4, stickiness="full")
    cs.process_all()
    tb.insert_text(4, "XY")
    tb.insert_text(2, "Z")
    cs.process_all()
    ib = tb.get_interval_collection("c")
    assert ia.signature() == ib.signature()
    iv_b = next(iter(ib))
    lo, hi = ib.endpoints(iv_b)
    assert tb.get_text()[lo:hi] == "ZcdXY"


def test_stickiness_survives_summary_roundtrip():
    from fluidframework_tpu.models.intervals import IntervalCollection

    c, coll, iv = _sticky_coll("full")
    entries = coll.summarize()
    assert entries[0]["stickiness"] == "full"
    c2 = make_client("abcdef")
    coll2 = IntervalCollection("x", c2, lambda op: None)
    coll2.load(entries)
    c2.insert_text_local(4, "XY")
    iv2 = next(iter(coll2))
    lo, hi = coll2.endpoints(iv2)
    assert c2.get_text()[lo:hi] == "cdXY"


def test_stickiness_anchor_removal_collapses_not_slides():
    """Removing an endpoint's anchor character must collapse the
    boundary backward, not slide it forward (code-review r4: the
    +1-bias representation absorbed/dropped a character here; the
    side-aware AFTER reference fixes it)."""
    from fluidframework_tpu.models.intervals import IntervalCollection

    c = make_client("abcdef")
    coll = IntervalCollection("x", c, lambda op: None)
    iv = coll.add(2, 4, stickiness="full")   # "cd"
    c.remove_range_local(1, 2)               # remove the start anchor
    lo, hi = coll.endpoints(iv)
    assert c.get_text()[lo:hi] == "cd"

    c2 = make_client("abcdef")
    coll2 = IntervalCollection("x", c2, lambda op: None)
    iv2 = coll2.add(2, 4, stickiness="none")  # "cd"
    c2.remove_range_local(3, 4)               # remove the end anchor
    lo, hi = coll2.endpoints(iv2)
    assert c2.get_text()[lo:hi] == "c"        # no absorb of 'e'


def test_empty_interval_with_nonsticky_end_stays_empty():
    from fluidframework_tpu.models.intervals import IntervalCollection

    c = make_client("abcdef")
    coll = IntervalCollection("x", c, lambda op: None)
    iv = coll.add(2, 2, stickiness="none")
    assert coll.endpoints(iv) == (2, 2)


def test_sticky_change_local_and_remote_partial():
    """change() on sticky intervals is sentinel-safe and exact; a
    remote PARTIAL change leaves the untouched endpoint's anchor alone
    (re-deriving it through the sender's older view diverged
    replicas — code-review r4)."""
    cs = ContainerSession(["A", "B"])
    for cid in ("A", "B"):
        cs.runtime(cid).create_datastore("d").create_channel(
            "sharedstring", "t")
    ta = cs.runtime("A").get_datastore("d").get_channel("t")
    tb = cs.runtime("B").get_datastore("d").get_channel("t")
    ta.insert_text(0, "abcdef")
    cs.process_all()
    ia = ta.get_interval_collection("c")
    iv = ia.add(2, 4, stickiness="full")
    cs.process_all()
    # concurrent: A inserts at the front while B changes ONLY start
    ib = tb.get_interval_collection("c")
    iv_b = next(iter(ib))
    ta.insert_text(0, "XX")
    ib.change(iv_b.interval_id, start=3)
    cs.process_all()
    assert ia.signature() == ib.signature()
    # local sticky change on sentinel endpoints doesn't crash
    iv0 = ia.add(0, 3, stickiness="full")
    ia.change(iv0.interval_id, start=1)
    cs.process_all()
    assert ia.signature() == ib.signature()


def test_stickiness_survives_zamboni_compaction():
    """Compaction transfers AFTER refs BACKWARD (code-review r4: the
    forward-first transfer made a collapsed endpoint jump forward one
    character once min_seq passed the removal)."""
    from fluidframework_tpu.models.intervals import IntervalCollection

    s, clients = _mock_session(2)
    a, b = clients
    s.do("c0", "insert_text_local", 0, "abcdef")
    s.process_all()
    coll = IntervalCollection("x", a, lambda op: None)
    iv = coll.add(2, 4, stickiness="none")    # 'cd', end AFTER 'd'
    ivf = coll.add(2, 4, stickiness="full")   # start AFTER 'b'
    s.do("c0", "remove_range_local", 3, 4)    # remove 'd'
    s.do("c0", "remove_range_local", 1, 2)    # remove 'b'
    s.process_all()
    lo, hi = coll.endpoints(iv)
    assert a.get_text()[lo:hi] == "c"
    lo_f, hi_f = coll.endpoints(ivf)
    assert a.get_text()[lo_f:hi_f] == "c"
    # advance min_seq well past the removals: BOTH clients must keep
    # submitting, or the silent client floors the msn at 0 and the
    # zamboni path under test never executes (code-review r4 caught
    # the first version of this test passing against the broken code)
    for i in range(20):
        s.do("c0", "insert_text_local", a.get_length(), "z")
        s.do("c1", "insert_text_local", b.get_length(), "y")
        s.process_all()
    assert a.mergetree.collab.min_seq > 4, "msn never advanced"
    a.zamboni() if hasattr(a, "zamboni") else a.mergetree.zamboni()
    lo, hi = coll.endpoints(iv)
    assert a.get_text()[lo:hi] == "c", (a.get_text(), lo, hi)
    lo_f, hi_f = coll.endpoints(ivf)
    assert a.get_text()[lo_f:hi_f].startswith("c"), (lo_f, hi_f)


def _mock_session(n):
    ids = [f"c{i}" for i in range(n)]
    s = MockCollabSession(ids)
    return s, [s.client(i) for i in ids]


@pytest.mark.parametrize("stickiness", ["none", "start", "end", "full"])
@pytest.mark.parametrize("removal", ["start", "end", "both"])
def test_zamboni_preserves_endpoints_matrix(stickiness, removal):
    """Stickiness x anchor-removal x compaction: once an endpoint's
    anchor char is removed and the interval has settled, running
    zamboni (which drops the tombstone the ref sits on) must not move
    either endpoint."""
    from fluidframework_tpu.models.intervals import IntervalCollection

    s, clients = _mock_session(2)
    a, b = clients
    s.do("c0", "insert_text_local", 0, "abcdef")
    s.process_all()
    coll = IntervalCollection("x", a, lambda op: None)
    iv = coll.add(2, 4, stickiness=stickiness)  # 'cd'
    if removal in ("start", "both"):
        s.do("c0", "remove_range_local", 2, 3)  # start anchor 'c'
    if removal in ("end", "both"):
        # end anchor region 'd' (shifted left if 'c' already removed)
        off = 1 if removal == "both" else 0
        s.do("c0", "remove_range_local", 3 - off, 4 - off)
    s.process_all()
    before = coll.endpoints(iv)
    # advance msn past the removals (both clients must submit)
    for _ in range(20):
        s.do("c0", "insert_text_local", a.get_length(), "z")
        s.do("c1", "insert_text_local", b.get_length(), "y")
        s.process_all()
    assert a.mergetree.collab.min_seq > 4, "msn never advanced"
    a.mergetree.zamboni()
    after = coll.endpoints(iv)
    assert before == after, (
        f"zamboni moved endpoints: {before} -> {after} "
        f"(stickiness={stickiness}, removal={removal})"
    )
    assert coll.signature()  # resolvable, no crash


def test_empty_interval_end_zero_resolves():
    """end==0 with start/none stickiness stores the DOC_START sentinel
    as the END ref; endpoints()/signature() must resolve it, not crash
    (code-review r4)."""
    from fluidframework_tpu.models.intervals import IntervalCollection

    c = make_client("abc")
    coll = IntervalCollection("x", c, lambda op: None)
    iv = coll.add(0, 0, stickiness="none")
    assert coll.endpoints(iv) == (0, 0)
    assert coll.signature()  # no AttributeError
    assert coll.summarize() is not None
