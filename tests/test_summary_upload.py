"""Client-driven summary upload over the wire:
the elected summarizer uploads the summary tree to service storage
(chunked, token-gated) and proposes only the HANDLE on the op stream;
scribe validates the handle and commits the version.

Reference flow: containerRuntime.ts:2477 (summarize -> upload ->
submit handle), driver-definitions/src/storage.ts:119
(uploadSummaryWithContext), historian summary routes; scribe ack in
server/routerlicious/packages/lambdas/src/scribe/lambda.ts.
"""
import asyncio
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from fluidframework_tpu.drivers.socket_driver import (
    SocketDocumentService,
)
from fluidframework_tpu.loader import Container
from fluidframework_tpu.protocol.messages import MessageType
from fluidframework_tpu.service.ingress import AlfredServer
from fluidframework_tpu.service.lambdas import SummaryStore
from fluidframework_tpu.service.tenancy import (
    SCOPE_READ,
    TenantManager,
    sign_token,
)




def _wait(pred, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


def _negotiate(svc, client_id="alice"):
    """The upload plane requires a prior connect_document (the wire
    version agreed there authorizes 1.1 frames); tests driving raw
    upload frames must negotiate like any real client."""
    return svc.connect_to_delta_stream(client_id, lambda m: None)


def test_summary_store_stage_commit_roundtrip():
    store = SummaryStore()
    root = store.stage({"a": {"x": 1}, "b": [1, 2]})
    assert store.has_tree(root)
    assert store.latest() is None  # staged, not committed
    store.commit(7, root)
    latest = store.latest()
    assert latest.sequence_number == 7
    assert latest.summary == {"a": {"x": 1}, "b": [1, 2]}
    assert not store.has_tree("not-a-sha")


def test_upload_then_summarize_handle_over_tcp(alfred):
    """Full wire loop: ops -> upload_summary (chunked) -> SUMMARIZE
    with handle -> scribe ack -> fetch_summary serves the
    client-uploaded tree; a second client loads from it."""
    server = alfred()
    svc = SocketDocumentService("127.0.0.1", server.port, "d",
                                timeout=15.0)
    try:
        with svc.lock:
            c = Container.load(svc, client_id="alice")
            t = c.runtime.create_datastore("ds").create_channel(
                "sharedstring", "t")
            c.flush()
            t.insert_text(0, "uploaded state")
            c.flush()
        assert _wait(lambda: c.runtime.pending.count == 0)
        with svc.lock:
            c.summarize()
        # scribe commits asynchronously via the sequenced ack
        assert _wait(lambda: svc.get_latest_summary() is not None)
        seq, summary = svc.get_latest_summary()
        assert "protocol" in summary and "runtime" in summary
        # the orderer's store holds exactly one committed version and
        # the op log truncated below the summarized refseq
        orderer = server.local.get_orderer("d")
        assert orderer.summary_store.version_count == 1
        with svc.lock:
            c.close()
    finally:
        svc.close()

    # a fresh client loads from the client-uploaded summary
    svc2 = SocketDocumentService("127.0.0.1", server.port, "d",
                                 timeout=15.0)
    try:
        with svc2.lock:
            c2 = Container.load(svc2, client_id="bob")
            t2 = c2.runtime.get_datastore("ds").get_channel("t")
            assert t2.get_text() == "uploaded state"
            c2.close()
    finally:
        svc2.close()


def test_upload_chunking_small_chunks(alfred):
    """Multi-chunk uploads reassemble exactly (chunk size forced tiny
    so even a small summary splits)."""
    server = alfred()
    svc = SocketDocumentService("127.0.0.1", server.port, "d",
                                timeout=15.0)
    try:
        with svc.lock:
            c = Container.load(svc, client_id="alice")
            t = c.runtime.create_datastore("ds").create_channel(
                "sharedstring", "t")
            c.flush()
            t.insert_text(0, "x" * 500)
            c.flush()
        assert _wait(lambda: c.runtime.pending.count == 0)
        svc._UPLOAD_CHUNK = 64  # force many chunks
        with svc.lock:
            c.summarize()
        assert _wait(lambda: svc.get_latest_summary() is not None)
        _, summary = svc.get_latest_summary()
        assert "runtime" in summary
        c.close()
    finally:
        svc.close()


def test_summarize_unknown_handle_nacked(alfred):
    """A summarize proposing a handle storage never saw must NACK,
    not commit garbage."""
    from fluidframework_tpu.protocol.messages import DocumentMessage

    server = alfred()
    acks = []
    svc = SocketDocumentService("127.0.0.1", server.port, "d",
                                timeout=15.0)
    try:
        conn = svc.connect_to_delta_stream(
            "alice", lambda m: acks.append(m))
        conn.submit(DocumentMessage(
            client_sequence_number=1, reference_sequence_number=0,
            type=MessageType.SUMMARIZE,
            contents={"handle": "deadbeef",
                      "referenceSequenceNumber": 0},
        ))
        assert _wait(lambda: any(
            m.type == MessageType.SUMMARY_NACK for m in acks))
        assert svc.get_latest_summary() is None
    finally:
        svc.close()


def test_upload_requires_write_scope(alfred):
    """Token-gated: a doc:read token can fetch but not upload."""
    tm = TenantManager()
    tenant = tm.create_tenant("acme")
    server = alfred(tenants=tm)
    ro = sign_token(tenant.key, "acme", "d", "alice",
                    scopes=[SCOPE_READ])
    # read-mode connect: the doc:read token passes the handshake (and
    # negotiates the wire version the upload plane now requires), then
    # the upload itself must still be rejected for missing doc:write
    svc = SocketDocumentService("127.0.0.1", server.port, "d",
                                timeout=15.0, tenant_id="acme",
                                token=ro, mode="read")
    try:
        _negotiate(svc)
        with pytest.raises(PermissionError, match="write"):
            svc.upload_summary({"runtime": {}})
    finally:
        svc.close()


def test_upload_out_of_order_chunk_rejected(alfred):
    server = alfred()
    svc = SocketDocumentService("127.0.0.1", server.port, "d",
                                timeout=15.0)
    try:
        _negotiate(svc)
        svc._request({
            "type": "upload_summary_chunk", "document_id": "d",
            "upload_id": "u1", "chunk": 0, "total": 3,
            "data": "xx",
        })
        with pytest.raises(RuntimeError, match="out of order"):
            svc._request({
                "type": "upload_summary_chunk", "document_id": "d",
                "upload_id": "u1", "chunk": 2, "total": 3,
                "data": "xx",
            })
    finally:
        svc.close()


@pytest.mark.slow
def test_sigkill_restart_resumes_from_client_uploaded_summary(
        tmp_path):
    """SIGKILL the service after a
    CLIENT-UPLOADED summary committed; the restarted service loads
    documents from that summary (op log truncated below it, so the
    summary — not the log — must carry the state)."""
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    data_dir = str(tmp_path / "data")

    def start_server():
        proc = subprocess.Popen(
            [sys.executable, "-m", "fluidframework_tpu.service",
             "--port", "0", "--data-dir", data_dir],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        line = proc.stdout.readline()
        m = re.search(r"listening on [\w.]+:(\d+)", line)
        assert m, line
        return proc, int(m.group(1))

    code = (
        "import sys, time; sys.path.insert(0, '.')\n"
        "from fluidframework_tpu.drivers.socket_driver import "
        "SocketDocumentService\n"
        "from fluidframework_tpu.loader import Container\n"
        "svc = SocketDocumentService('127.0.0.1', PORT, 'sum-doc')\n"
        "with svc.lock:\n"
        "    c = Container.load(svc, client_id='alice')\n"
        "    t = c.runtime.create_datastore('d')"
        ".create_channel('sharedstring', 't')\n"
        "    c.flush()\n"
        "    t.insert_text(0, 'summarized state')\n"
        "    c.flush()\n"
        "deadline = time.time() + 30\n"
        "while time.time() < deadline:\n"
        "    with svc.lock:\n"
        "        if c.runtime.pending.count == 0: break\n"
        "    time.sleep(0.02)\n"
        "with svc.lock:\n"
        "    c.summarize()\n"
        "deadline = time.time() + 30\n"
        "while time.time() < deadline:\n"
        "    if svc.get_latest_summary() is not None: break\n"
        "    time.sleep(0.05)\n"
        "else:\n"
        "    raise TimeoutError('summary never committed')\n"
        "print('UPLOADED')\n"
        "c.close(); svc.close()\n"
    )
    server, port = start_server()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code.replace("PORT", str(port))],
            capture_output=True, text=True, cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "UPLOADED" in proc.stdout
    finally:
        os.kill(server.pid, signal.SIGKILL)
        server.wait()

    server, port = start_server()
    try:
        check = (
            "import sys; sys.path.insert(0, '.')\n"
            "from fluidframework_tpu.drivers.socket_driver import "
            "SocketDocumentService\n"
            "from fluidframework_tpu.loader import Container\n"
            "svc = SocketDocumentService('127.0.0.1', PORT, "
            "'sum-doc')\n"
            "seq, summary = svc.get_latest_summary()\n"
            "print('SUMMARY_AT=' + str(seq))\n"
            "with svc.lock:\n"
            "    c = Container.load(svc, client_id='bob')\n"
            "    t = c.runtime.get_datastore('d').get_channel('t')\n"
            "    print('TEXT=' + t.get_text())\n"
            "c.close(); svc.close()\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", check.replace("PORT", str(port))],
            capture_output=True, text=True, cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "TEXT=summarized state" in proc.stdout
    finally:
        server.kill()
        server.wait()


def test_upload_concurrency_limit_rejects_new_not_evicts_old(alfred):
    """Hitting MAX_UPLOADS_IN_FLIGHT must reject the NEW upload with
    an explicit error; in-flight uploads keep working (ADVICE r4: the
    old eviction killed a live upload on a multiplexed connection and
    its next chunk then failed with a misleading out-of-order error)."""
    import json as _json

    server = alfred()
    svc = SocketDocumentService("127.0.0.1", server.port, "d",
                                timeout=15.0)
    try:
        _negotiate(svc)
        payload = _json.dumps({"runtime": {}})
        for i in range(4):  # MAX_UPLOADS_IN_FLIGHT
            svc._request({
                "type": "upload_summary_chunk", "document_id": "d",
                "upload_id": f"u{i}", "chunk": 0, "total": 2,
                "data": payload[:1],
            })
        with pytest.raises(RuntimeError,
                           match="too many concurrent uploads"):
            svc._request({
                "type": "upload_summary_chunk", "document_id": "d",
                "upload_id": "u-over", "chunk": 0, "total": 2,
                "data": payload[:1],
            })
        # the in-flight upload u0 is untouched: its final chunk lands
        resp = svc._request({
            "type": "upload_summary_chunk", "document_id": "d",
            "upload_id": "u0", "chunk": 1, "total": 2,
            "data": payload[1:],
        })
        assert resp.get("handle")
        # abandoned uploads are reclaimed once idle past the TTL:
        # u1-u3 are still staged; after the TTL, FOUR brand-new
        # uploads must all be accepted — impossible unless the three
        # abandoned ones were swept (non-vacuous: without the sweep
        # the second new id below hits the cap)
        server.UPLOAD_IDLE_TTL = 0.05
        time.sleep(0.2)
        for i in range(4):
            resp = svc._request({
                "type": "upload_summary_chunk", "document_id": "d",
                "upload_id": f"u-new{i}", "chunk": 0, "total": 2,
                "data": payload[:1],
            })
            assert resp.get("type") != "error", resp
    finally:
        svc.close()


def test_container_summarize_surfaces_permission_error():
    """An upload plane that rejects for auth must raise out of
    summarize(), not silently degrade to inline summaries forever
    (ADVICE r4: PermissionError is an OSError subclass and was
    swallowed by the transient-failure fallback)."""
    from fluidframework_tpu.drivers import LocalDocumentServiceFactory
    from fluidframework_tpu.loader import Container
    from fluidframework_tpu.service import LocalServer

    server = LocalServer()
    svc = LocalDocumentServiceFactory(server).create_document_service(
        "doc")

    def denied(summary):
        raise PermissionError("token lacks doc:write")

    svc.upload_summary = denied
    c = Container.load(svc, client_id="alice")
    c.runtime.create_datastore("ds").create_channel("sharedstring", "t")
    c.flush()
    with pytest.raises(PermissionError):
        c.summarize()
    c.close()


def test_upload_continuation_of_unknown_id_distinct_error(alfred):
    """chunk>0 for an id the server doesn't know (rejected at the cap,
    TTL-reclaimed, or never started) gets an accurate error, not the
    misleading 'out of order' from a freshly-created state
    (code-review r5)."""
    server = alfred()
    svc = SocketDocumentService("127.0.0.1", server.port, "d",
                                timeout=15.0)
    try:
        _negotiate(svc)
        with pytest.raises(RuntimeError,
                           match="rejected, expired, or never started"):
            svc._request({
                "type": "upload_summary_chunk", "document_id": "d",
                "upload_id": "ghost", "chunk": 1, "total": 3,
                "data": "xx",
            })
    finally:
        svc.close()
