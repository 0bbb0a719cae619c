"""Networked service plane: alfred-equivalent ingress + socket driver.

Reference parity targets: the connect_document/submitOp socket protocol
(lambdas/src/alfred/index.ts:465,500; driver-base/src/
documentDeltaConnection.ts:41) and the multi-process load runner
(test-service-load). In-proc tests run the asyncio server on a thread
and real TCP clients through the synchronous socket driver; the
heavyweight test spawns the dev service and workers as separate OS
processes via tools/net_stress.
"""
import asyncio
import threading

import pytest

from fluidframework_tpu.drivers.socket_driver import (
    SocketDocumentService,
)
from fluidframework_tpu.loader import Container
from fluidframework_tpu.protocol.messages import (
    DocumentMessage,
    MessageType,
)
from fluidframework_tpu.service.ingress import AlfredServer


@pytest.fixture()
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

    task_holder = {}

    def runner():
        task = loop.create_task(_run())
        task_holder["task"] = task
        try:
            loop.run_until_complete(task)
        except Exception:
            pass

    thread = threading.Thread(target=runner, daemon=True)
    thread.start()
    assert started.wait(10)
    yield srv
    loop.call_soon_threadsafe(task_holder["task"].cancel)
    thread.join(timeout=10)
    loop.call_soon_threadsafe(loop.stop)


def test_two_clients_converge_over_tcp(server):
    sa = SocketDocumentService("127.0.0.1", server.port, "doc")
    sb = SocketDocumentService("127.0.0.1", server.port, "doc")
    with sa.lock:
        a = Container.load(sa, client_id="alice")
    with sa.lock:
        ta = (a.runtime.create_datastore("d")
              .create_channel("sharedstring", "t"))
        a.flush()
        ta.insert_text(0, "hello")
        a.flush()

    with sb.lock:
        b = Container.load(sb, client_id="bob")
        tb = b.runtime.get_datastore("d").get_channel("t")
        assert tb.get_text() == "hello"
        tb.insert_text(5, " world")
        b.flush()

    deadline = 50
    import time

    for _ in range(deadline):
        with sa.lock:
            if ta.get_text() == "hello world":
                break
        time.sleep(0.05)
    with sa.lock, sb.lock:
        assert ta.get_text() == tb.get_text() == "hello world"
    a.close()
    b.close()
    sa.close()
    sb.close()


def test_read_ops_and_nack_over_tcp(server):
    svc = SocketDocumentService("127.0.0.1", server.port, "doc2")
    nacks = []
    got = []
    conn = svc.connect_to_delta_stream(
        "carol", on_message=got.append, on_nack=nacks.append
    )
    conn.submit(DocumentMessage(
        client_sequence_number=1, reference_sequence_number=1,
        type=MessageType.OPERATION, contents={"x": 1},
    ))
    import time

    for _ in range(100):
        if len(got) >= 2:  # join + the op
            break
        time.sleep(0.02)
    assert any(m.type == MessageType.OPERATION for m in got)

    # storage plane over the wire
    ops = svc.read_ops(0)
    assert [m.sequence_number for m in ops] == list(
        range(1, len(ops) + 1)
    )
    assert svc.get_latest_summary() is None

    # deterministic nack: client_sequence_number gap
    conn.submit(DocumentMessage(
        client_sequence_number=99, reference_sequence_number=2,
        type=MessageType.OPERATION, contents={"x": 2},
    ))
    for _ in range(100):
        if nacks:
            break
        time.sleep(0.02)
    assert nacks and "clientSequenceNumber" in nacks[0].message
    svc.close()


def test_multi_process_stress_converges():
    """Multiple OS processes over real
    sockets converge through the runnable dev service."""
    from fluidframework_tpu.tools.net_stress import run_net_stress

    report = run_net_stress(n_workers=3, n_ops=12, seed=77)
    assert len({w["text_sha"] for w in report["workers"]}) == 1
    assert report["replay_length"] == report["workers"][0]["length"]


def test_multi_process_stress_converges_partitioned():
    """Same multi-process convergence bar, through the PARTITIONED
    queue pipeline (produce -> broker -> partition consumer -> deli)."""
    from fluidframework_tpu.tools.net_stress import run_net_stress

    report = run_net_stress(n_workers=3, n_ops=12, seed=78,
                            partitions=2)
    assert len({w["text_sha"] for w in report["workers"]}) == 1
    assert report["replay_length"] == report["workers"][0]["length"]
