"""NoOp heartbeat / CollabWindowTracker (collabWindowTracker.ts).

Without heartbeats an idle write client pins the service msn at its
last submitted refSeq forever: zamboni never collects, tombstones grow
without bound.
"""
from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.loader.collab_window import CollabWindowTracker
from fluidframework_tpu.protocol.messages import MessageType
from fluidframework_tpu.service import LocalServer


def make_pair(server=None, noop_every=None):
    from fluidframework_tpu.utils.config import (
        CachedConfigProvider,
        ConfigProvider,
        MonitoringContext,
    )
    from fluidframework_tpu.utils.telemetry import TelemetryLogger

    server = server or LocalServer()
    factory = LocalDocumentServiceFactory(server)
    mc = None
    if noop_every is not None:
        mc = MonitoringContext(
            TelemetryLogger(),
            CachedConfigProvider(ConfigProvider(
                {"noopCountFrequency": noop_every})),
        )
    a = Container.load(factory.create_document_service("doc"),
                       client_id="alice", mc=mc)
    b = Container.load(factory.create_document_service("doc"),
                       client_id="bob", mc=mc)
    sa = a.runtime.create_datastore("d").create_channel("sharedstring", "t")
    a.flush()
    sb = b.runtime.get_datastore("d").get_channel("t")
    return server, a, b, sa, sb


def test_idle_client_emits_noop_and_msn_advances():
    server, a, b, sa, sb = make_pair(noop_every=10)
    orderer = server.get_orderer("doc")
    for i in range(25):
        sa.insert_text(0, "x")
        a.flush()
    # bob never typed, but his tracker must have heartbeated: the msn
    # advances past bob's join refSeq
    msn = orderer.sequencer.minimum_sequence_number
    assert msn > 10, f"msn pinned at {msn} by idle client"
    assert sa.get_text() == sb.get_text()


def test_msn_pinned_without_heartbeat():
    """Control: with an enormous threshold and no ticks, the idle
    client pins the msn — proving the heartbeat is what moves it."""
    server, a, b, sa, sb = make_pair(noop_every=10_000)
    orderer = server.get_orderer("doc")
    base_msn = orderer.sequencer.minimum_sequence_number
    for _ in range(30):
        sa.insert_text(0, "x")
        a.flush()
    assert orderer.sequencer.minimum_sequence_number <= base_msn + 1


def test_idle_tick_heartbeat():
    server, a, b, sa, sb = make_pair(noop_every=10_000)
    orderer = server.get_orderer("doc")
    for _ in range(10):
        sa.insert_text(0, "y")
        a.flush()
    b.collab_window.idle_s = 0.0  # fire on the next tick
    assert b.collab_window.tick(b.last_processed_seq)
    assert orderer.sequencer.minimum_sequence_number >= 10


def test_noop_heartbeat_unpins_zamboni():
    """The device-table-boundedness story: after heartbeats advance the
    msn, removed segments below the window actually get collected."""
    server, a, b, sa, sb = make_pair(noop_every=5)
    sa.insert_text(0, "hello world, this is a long line")
    a.flush()
    sa.remove_text(0, 6)
    a.flush()
    for _ in range(20):  # stream traffic so heartbeats fire
        sa.annotate_range(0, 4, {"bold": 1})
        a.flush()
    tree = sa.client.mergetree
    tree.zamboni()
    tombs = sum(1 for s in tree.segments if s.removed)
    assert tombs == 0, "tombstones survived despite heartbeat msn"


def test_tracker_no_noop_without_advance():
    sent = []
    t = CollabWindowTracker(lambda: sent.append(1), max_unacked_ops=5,
                            idle_s=0.0)
    t.on_op_sent(7)
    assert not t.tick(7)  # nothing unacknowledged
    t.on_op_processed(9)  # below threshold
    assert sent == []
    assert t.tick(9)  # idle with advance -> heartbeat
    assert sent == [1]


def test_own_ops_count_as_heartbeat():
    """A client actively typing must never emit noops: its real ops
    carry the refSeq."""
    server, a, b, sa, sb = make_pair(noop_every=8)
    submitted = []
    orig = a.collab_window._submit_noop
    a.collab_window._submit_noop = (
        lambda: submitted.append(1) or orig()
    )
    for _ in range(30):
        sa.insert_text(0, "z")
        a.flush()
        sb.insert_text(0, "w")
        b.flush()
    assert submitted == [], "active client emitted needless noops"


def test_idle_expiry_on_a_manual_clock():
    """The clock is injectable (the detcheck wall-clock-unrouted
    contract): idle-expiry heartbeats are driven entirely by the
    injected clock, so a test pins the schedule exactly — no real
    waiting, no wall-clock read."""
    sent = []
    t = 0.0
    tracker = CollabWindowTracker(
        lambda: sent.append(1), max_unacked_ops=0, idle_s=2.0,
        clock=lambda: t,
    )
    tracker.on_op_sent(3)
    t = 1.9
    assert not tracker.tick(9)      # advanced, but not idle enough
    t = 2.0
    assert tracker.tick(9)          # exactly idle_s since activity
    assert sent == [1]
    # the heartbeat itself counts as activity on the same clock
    t = 3.9
    assert not tracker.tick(12)
    t = 4.0
    assert tracker.tick(12)
    assert sent == [1, 1]
