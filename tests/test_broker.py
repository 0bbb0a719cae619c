"""Networked ordering broker: the rdkafka-tier
seam over framed TCP — at-least-once, committed-offset resume, durable
across broker restarts, partitions spanning processes.

Reference semantics: services-ordering-rdkafka/src/rdkafkaConsumer.ts
:37 (committed-offset consume) / rdkafkaProducer.ts:52.
"""
import asyncio
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest

from fluidframework_tpu.protocol.messages import DocumentMessage, MessageType
from fluidframework_tpu.service.broker import (
    BrokerServer,
    RemoteOrderingQueue,
)
from fluidframework_tpu.service.partitioning import (
    PartitionedOrderingService,
    partition_for,
)


@pytest.fixture()
def broker(tmp_path):
    """BrokerServer on a background loop; yields a factory so tests
    can restart it over the same data dir."""
    state = {}

    def start(n_partitions=2, durable=True):
        b = BrokerServer(
            n_partitions,
            str(tmp_path / "qdata") if durable else None,
        )
        loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(loop)
            loop.run_until_complete(b.start())
            started.set()
            loop.run_forever()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert started.wait(10)
        state.update(server=b, loop=loop, thread=t)
        return b

    def stop():
        if not state:
            return
        fut = asyncio.run_coroutine_threadsafe(
            state["server"].stop(), state["loop"])
        try:
            fut.result(timeout=10)
        except Exception:
            pass
        state["loop"].call_soon_threadsafe(state["loop"].stop)
        state["thread"].join(timeout=10)
        state.clear()

    start.stop = stop
    yield start
    stop()


def test_produce_read_commit_roundtrip(broker):
    b = broker()
    q = RemoteOrderingQueue("127.0.0.1", b.port)
    assert q.n_partitions == 2
    o0 = q.produce(0, "doc-a", {"x": 1})
    o1 = q.produce(0, "doc-b", {"x": 2})
    q.produce(1, "doc-c", {"x": 3})
    assert (o0, o1) == (0, 1)
    recs = list(q.read(0, 0))
    assert [(r.offset, r.document_id) for r in recs] == [
        (0, "doc-a"), (1, "doc-b")]
    assert q.committed(0) == -1
    q.commit(0, 1)
    assert q.committed(0) == 1
    # re-read from committed+1: nothing left (at-least-once resume)
    assert list(q.read(0, q.committed(0) + 1)) == []
    q.close()


def test_read_batches_past_server_limit(broker):
    b = broker()
    q = RemoteOrderingQueue("127.0.0.1", b.port)
    for i in range(1203):
        q.produce(1, "d", {"i": i})
    got = [r.payload["i"] for r in q.read(1, 0)]
    assert got == list(range(1203))  # spans 3 server batches
    q.close()


def test_partition_out_of_range_errors(broker):
    b = broker()
    q = RemoteOrderingQueue("127.0.0.1", b.port)
    with pytest.raises(RuntimeError, match="out of range"):
        q.produce(9, "d", {})
    q.close()


def test_broker_restart_preserves_offsets_and_client_reconnects(
        broker):
    b = broker()
    q = RemoteOrderingQueue("127.0.0.1", b.port)
    q.produce(0, "d", {"n": 1})
    q.commit(0, 0)
    port = b.port
    broker.stop()
    # restart over the same data dir on the same port
    b2 = BrokerServer(2, str(b.queue.root), port=port)

    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(b2.start())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(10)
    try:
        # the client's dead socket retries transparently
        assert q.committed(0) == 0
        q.produce(0, "d", {"n": 2})
        assert [r.payload["n"] for r in q.read(0, 0)] == [1, 2]
    finally:
        fut = asyncio.run_coroutine_threadsafe(b2.stop(), loop)
        fut.result(timeout=10)
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=10)
    q.close()


def _op(csn, ref=0):
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=ref,
        type=MessageType.OPERATION, contents={"n": csn},
    )


def test_partitioned_service_over_remote_queue(broker):
    """The full pipeline shape with the queue on the wire: produce ->
    pump -> sequenced; commits land on the broker so a replacement
    consumer starts past them."""
    from fluidframework_tpu.protocol.messages import ClientDetail

    b = broker()
    q = RemoteOrderingQueue("127.0.0.1", b.port)
    svc = PartitionedOrderingService(n_partitions=2, queue=q)
    doc = "doc-x"
    svc.produce_join(doc, ClientDetail("alice"))
    for i in range(1, 6):
        svc.produce_op(doc, "alice", _op(i))
    svc.pump()
    ord1 = svc.orderer(doc)
    assert ord1.sequencer.sequence_number == 6  # join + 5 ops
    seen1 = [m.contents["n"] for m in ord1.op_log.read(0)
             if m.type == MessageType.OPERATION]
    assert seen1 == [1, 2, 3, 4, 5]
    p = partition_for(doc, 2)
    assert q.committed(p) == 5  # all six records (offsets 0..5)
    # a replacement consumer (fresh service, same broker) reads
    # nothing below the committed offset: no duplicate sequencing
    q2 = RemoteOrderingQueue("127.0.0.1", b.port)
    svc2 = PartitionedOrderingService(n_partitions=2, queue=q2)
    assert svc2.pump() == 0
    q.close()
    q2.close()


@pytest.mark.slow
def test_partitions_span_processes_against_one_broker(tmp_path):
    """The scale-out deployment shape: a broker
    process + TWO consumer processes each pumping ONE partition of the
    same queue, with producers on a third process; every partition's
    records sequence exactly once per consumer."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    broker_proc = subprocess.Popen(
        [sys.executable, "-m", "fluidframework_tpu.service.broker",
         "--port", "0", "--partitions", "2",
         "--data-dir", str(tmp_path / "q")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=repo, env=env,
    )
    line = broker_proc.stdout.readline()
    m = re.search(r"listening on [\w.]+:(\d+)", line)
    assert m, line
    bport = int(m.group(1))

    consumer_code = """
import sys; sys.path.insert(0, '.')
from fluidframework_tpu.service.broker import RemoteOrderingQueue
from fluidframework_tpu.service.partitioning import (
    PartitionedOrderingService)
from fluidframework_tpu.protocol.messages import MessageType
import time
q = RemoteOrderingQueue('127.0.0.1', PORT)
svc = PartitionedOrderingService(n_partitions=2, queue=q)
part = svc.partitions[WHICH]
deadline = time.time() + 30
total = 0
while time.time() < deadline:
    total += part.pump()
    done = True
    for doc, dp in part.documents.items():
        ops = [m for m in dp.orderer.op_log.read(0)
               if m.type == MessageType.OPERATION]
        if len(ops) < 40:
            done = False
    if part.documents and done:
        break
    time.sleep(0.05)
for doc in sorted(part.documents):
    ops = [m.contents['n'] for m in
           part.documents[doc].orderer.op_log.read(0)
           if m.type == MessageType.OPERATION]
    print(f'DOC {doc} ' + ','.join(map(str, ops)))
"""
    producer_code = """
import sys; sys.path.insert(0, '.')
from fluidframework_tpu.service.broker import RemoteOrderingQueue
from fluidframework_tpu.service.partitioning import partition_for
from fluidframework_tpu.protocol.messages import MessageType
q = RemoteOrderingQueue('127.0.0.1', PORT)
docs = ['alpha', 'beta', 'gamma', 'delta']
for d in docs:
    p = partition_for(d, 2)
    q.produce(p, d, {'kind': 'join',
                     'detail': {'client_id': 'w'}})
for i in range(1, 41):
    for d in docs:
        p = partition_for(d, 2)
        q.produce(p, d, {'kind': 'op', 'client_id': 'w', 'op': {
            'client_sequence_number': i,
            'reference_sequence_number': 0,
            'type': int(MessageType.OPERATION),
            'contents': {'n': i}, 'metadata': None,
            'traces': []}})
print('PRODUCED')
"""
    try:
        prod = subprocess.run(
            [sys.executable, "-c",
             producer_code.replace("PORT", str(bport))],
            capture_output=True, text=True, cwd=repo, env=env,
            timeout=120,
        )
        assert prod.returncode == 0, prod.stderr[-1500:]
        consumers = [
            subprocess.Popen(
                [sys.executable, "-c",
                 consumer_code.replace("PORT", str(bport))
                 .replace("WHICH", str(w))],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=repo, env=env,
            )
            for w in (0, 1)
        ]
        outs = [c.communicate(timeout=120)[0] for c in consumers]
        assert all(c.returncode == 0 for c in consumers), outs
        docs_seen = {}
        for out in outs:
            for line in out.splitlines():
                if line.startswith("DOC "):
                    _, doc, ops = line.split(" ", 2)
                    docs_seen[doc] = ops
        want = ",".join(str(i) for i in range(1, 41))
        assert set(docs_seen) == {"alpha", "beta", "gamma", "delta"}
        for doc, ops in docs_seen.items():
            assert ops == want, (doc, ops)
    finally:
        os.kill(broker_proc.pid, signal.SIGKILL)
        broker_proc.wait()


def test_corrupt_frame_poisons_socket_and_reconnects_fresh():
    """A desynced/corrupt length prefix raises ValueError out of the
    frame reader; the client must DROP the cached socket (reusing it
    would parse mid-stream garbage as fresh frames) and the next
    request must reconnect from scratch."""
    from fluidframework_tpu.testing.fault_injection import (
        ScriptedFrameServer,
    )

    meta = {"type": "meta", "n_partitions": 2}
    with ScriptedFrameServer(
        [meta, ScriptedFrameServer.CORRUPT, meta]
    ) as srv:
        q = RemoteOrderingQueue("127.0.0.1", srv.port, timeout=5.0)
        with pytest.raises(ValueError, match="exceeds"):
            q._request({"type": "meta"})
        assert q._sock is None  # poisoned socket dropped, not cached
        # next request reconnects and succeeds on the fresh stream
        assert q._request({"type": "meta"})["n_partitions"] == 2
        q.close()
