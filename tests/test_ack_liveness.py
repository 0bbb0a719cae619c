"""Submit->ack liveness over TCP (the round-5 ~1-in-3 whiteboard
stall).

Root cause, reproduced deterministically here: the driver used to send
each op of a runtime batch as its own submitOp frame. Two sessions'
frames interleave arbitrarily on the server's event loop, so another
client's op could be SEQUENCED in the middle of a batch; every
receiver's ScheduleManager then (correctly) trips its
foreign-op-mid-batch assert — which executed on the driver's dispatch
thread, KILLING it, so every later broadcast (including the acks of
ops already submitted) was silently dropped and ``pending.count``
never reached zero.

The fix is two-sided and both sides are pinned:

- wire 1.2 boxcars a batch into ONE submitOp frame and the ingress
  tickets the array atomically on the event loop, so a batch can
  never interleave with another session's ops in sequenced order;
- the dispatch loop survives a delivery exception loudly instead of
  dying silently, so any future delivery bug degrades to a visible
  error rather than an ack blackhole.
"""
import asyncio
import threading
import time

import pytest

from fluidframework_tpu.drivers.socket_driver import (
    SocketDocumentService,
)
from fluidframework_tpu.loader import Container
from fluidframework_tpu.service.ingress import AlfredServer


@pytest.fixture
def server():
    srv = AlfredServer()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    async def _run():
        await srv.start()
        started.set()
        try:
            await srv.serve_forever()
        except asyncio.CancelledError:
            pass

    def runner():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(_run())

    thread = threading.Thread(target=runner, daemon=True)
    thread.start()
    assert started.wait(10)
    yield srv
    loop.call_soon_threadsafe(
        lambda: [t.cancel() for t in asyncio.all_tasks(loop)]
    )
    thread.join(timeout=5)


def _pump(svc, container, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        with svc.lock:
            if container.runtime.pending.count == 0:
                return True
        time.sleep(0.02)
    return False


def _load(port, doc, client_id):
    svc = SocketDocumentService("127.0.0.1", port, doc, timeout=15.0)
    with svc.lock:
        c = Container.load(svc, client_id=client_id)
    return svc, c


def _setup_pair(server, doc="doc"):
    svc_a, ca = _load(server.port, doc, "ana")
    with svc_a.lock:
        sa = ca.runtime.create_datastore("app").create_channel(
            "sharedstring", "s")
        ca.flush()
    assert _pump(svc_a, ca), "attach never acked"
    svc_b, cb = _load(server.port, doc, "ben")
    with svc_b.lock:
        sb = cb.runtime.get_datastore("app").get_channel("s")
    return (svc_a, ca, sa), (svc_b, cb, sb)


def test_forced_interleaving_cannot_lose_acks(server):
    """Force the exact lost-ack interleaving: B's flush hits the
    server WHILE A's batch is in flight (injected synchronously from
    A's send path, so the ordering is deterministic — B's frame
    reaches the event loop around A's batch frame). Pre-fix this
    sequenced B's op inside A's batch and both replicas' dispatch
    threads died on the ScheduleManager assert; post-fix the batch is
    one atomically-ticketed boxcar and every op acks."""
    (svc_a, ca, sa), (svc_b, cb, sb) = _setup_pair(server)

    orig_send = svc_a._send
    injected = {"n": 0}

    def interleaved_send(data):
        # inject B's traffic immediately before EVERY outbound frame
        # of A's flush — whatever the frame split, B lands mid-flush
        if data.get("type") == "submitOp":
            injected["n"] += 1
            with svc_b.lock:
                sb.insert_text(0, f"B{injected['n']}")
                cb.flush()
        orig_send(data)

    svc_a._send = interleaved_send
    try:
        with svc_a.lock:
            for i in range(6):
                sa.insert_text(0, f"a{i}")
            ca.flush()  # one 6-op batch
    finally:
        svc_a._send = orig_send
    assert injected["n"] >= 1, "the interleaving was never forced"

    assert _pump(svc_a, ca), "A's ops never acked (liveness stall)"
    assert _pump(svc_b, cb), "B's ops never acked (liveness stall)"
    assert svc_a._dispatcher.is_alive(), "A's dispatch thread died"
    assert svc_b._dispatcher.is_alive(), "B's dispatch thread died"

    deadline = time.time() + 10
    while time.time() < deadline:
        with svc_a.lock, svc_b.lock:
            if sa.get_text() == sb.get_text():
                break
        time.sleep(0.02)
    with svc_a.lock, svc_b.lock:
        assert sa.get_text() == sb.get_text(), "replicas diverged"
    svc_a.close()
    svc_b.close()


def test_batch_sequences_contiguously_under_crossfire(server):
    """The server-side half of the contract: a boxcarred batch
    occupies CONTIGUOUS sequence numbers even when another session
    submits concurrently — no foreign op can ever appear mid-batch in
    the sequenced order."""
    (svc_a, ca, sa), (svc_b, cb, sb) = _setup_pair(server, doc="contig")

    seen: list[tuple[int, str]] = []
    ca.on("processed", lambda msg: seen.append(
        (msg.sequence_number, msg.client_id or "<system>")))

    orig_send = svc_a._send

    def crossfire_send(data):
        if data.get("type") == "submitOp":
            with svc_b.lock:
                sb.insert_text(0, "x")
                cb.flush()
        orig_send(data)

    svc_a._send = crossfire_send
    try:
        with svc_a.lock:
            for i in range(5):
                sa.insert_text(0, f"c{i}")
            ca.flush()
    finally:
        svc_a._send = orig_send

    assert _pump(svc_a, ca) and _pump(svc_b, cb)
    with svc_a.lock:
        ana_seqs = [seq for seq, cid in seen if cid == "ana"]
    assert len(ana_seqs) == 5
    assert ana_seqs == list(range(ana_seqs[0], ana_seqs[0] + 5)), (
        f"batch interleaved in sequenced order: {seen}"
    )
    svc_a.close()
    svc_b.close()


def test_delivery_fault_tears_down_loudly_not_silently(server):
    """The liveness hardening: a delivery callback raising must be
    DETECTABLE — the fault is recorded, the transport torn down (a
    faulted runtime must not keep serving possibly-divergent state) —
    and a reloaded client recovers the document over a fresh
    connection. The pre-fix behavior was the worst of both: a
    silently-dead dispatch thread on a live-looking connection."""
    (svc_a, ca, sa), (svc_b, cb, sb) = _setup_pair(server, doc="fault")

    def faulty(msg):
        raise RuntimeError("injected delivery fault")

    svc_a._on_message = faulty
    with svc_b.lock:
        sb.insert_text(0, "boom")
        cb.flush()
    assert _pump(svc_b, cb)
    deadline = time.time() + 10
    while time.time() < deadline and svc_a.last_error is None:
        time.sleep(0.02)
    assert svc_a.last_error is not None and \
        "injected delivery fault" in svc_a.last_error
    assert svc_a._closed, "faulted transport must tear down"
    # the teardown ships a flight-recorder dump naming the last N
    # transport events — the postmortem the original stall lacked
    assert svc_a.last_flight_dump is not None
    assert "dispatch fault teardown" in svc_a.last_flight_dump
    assert "dispatch-fault" in svc_a.last_flight_dump
    assert "recv" in svc_a.last_flight_dump, (
        "the dump must name the frames that led up to the fault"
    )
    assert "type='op'" in svc_a.last_flight_dump, (
        "the faulting op broadcast should be among the recent events"
    )
    # B is unaffected, and a reloaded A catches up over a fresh
    # connection (the op log is the durable source)
    with svc_b.lock:
        sb.insert_text(0, "alive ")
        cb.flush()
    assert _pump(svc_b, cb)
    svc_a2, ca2 = _load(server.port, "fault", "ana2")
    with svc_a2.lock:
        sa2 = ca2.runtime.get_datastore("app").get_channel("s")
    deadline = time.time() + 10
    while time.time() < deadline:
        with svc_a2.lock, svc_b.lock:
            if sa2.get_text() == sb.get_text():
                break
        time.sleep(0.02)
    with svc_a2.lock, svc_b.lock:
        assert sa2.get_text() == sb.get_text()
    svc_a2.close()
    svc_b.close()


def test_malformed_boxcar_sequences_nothing(server):
    """Boxcar ticketing is all-or-nothing: a malformed op mid-array
    fails the WHOLE batch with an error frame before anything
    sequences — a partially-ticketed batch would put the torn-batch
    wire state back on the stream."""
    from fluidframework_tpu.service.ingress import (
        document_message_to_json,
    )
    from fluidframework_tpu.protocol.messages import (
        DocumentMessage,
        MessageType,
    )

    (svc_a, ca, sa), _b = _setup_pair(server, doc="torn")
    base_seq = ca.last_processed_seq

    def op_json(csn, text):
        return document_message_to_json(DocumentMessage(
            client_sequence_number=csn,
            reference_sequence_number=ca.last_processed_seq,
            type=MessageType.OPERATION,
            contents={"kind": "op", "address": "app", "channel": "s",
                      "contents": None},
        ))

    good = op_json(ca._csn + 1, "x")
    bad = dict(good)
    del bad["client_sequence_number"]  # malformed mid-array
    with pytest.raises(RuntimeError, match="KeyError"):
        svc_a._request({
            "type": "submitOp", "document_id": "torn",
            "ops": [good, bad, op_json(ca._csn + 2, "y")],
        })
    # nothing from the torn boxcar sequenced
    with svc_a.lock:
        msgs = svc_a.read_ops(base_seq)
    assert [m for m in msgs if m.client_id == "ana"] == []
    svc_a.close()
    _b[0].close()


def test_concurrent_batch_storm_drains(server):
    """Whiteboard-shaped end-to-end: both clients flush large batches
    concurrently for several rounds; every round must drain (the
    stalled pre-fix runs died on round 1 about 1 time in 3)."""
    (svc_a, ca, sa), (svc_b, cb, sb) = _setup_pair(server, doc="storm")
    for round_i in range(3):
        with svc_a.lock:
            for i in range(20):
                sa.insert_text(0, f"A{round_i}.{i} ")
            ca.flush()
        with svc_b.lock:
            for i in range(20):
                sb.insert_text(0, f"B{round_i}.{i} ")
            cb.flush()
        assert _pump(svc_a, ca), f"A stalled in round {round_i}"
        assert _pump(svc_b, cb), f"B stalled in round {round_i}"
    deadline = time.time() + 10
    while time.time() < deadline:
        with svc_a.lock, svc_b.lock:
            if sa.get_text() == sb.get_text():
                break
        time.sleep(0.02)
    with svc_a.lock, svc_b.lock:
        assert sa.get_text() == sb.get_text()
    svc_a.close()
    svc_b.close()
