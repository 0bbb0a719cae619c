"""Moira lambda: PropertyDDS changeset ops -> Materialized History
branch/commit graph over the framed-TCP MH service.

Mirrors server/routerlicious/packages/lambdas/src/moira/lambda.ts
(handler/sendPending/processMoiraCore/createBranch/createCommit) and
closes the last §2.7 service-inventory row.
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

from fluidframework_tpu.service.moira import (
    MaterializedHistoryClient,
    MaterializedHistoryServer,
    MoiraLambda,
    derived_guid,
)
from fluidframework_tpu.testing.runtime_mocks import ContainerSession

POINT = {
    "typeid": "test:point-1.0.0",
    "properties": [
        {"id": "x", "typeid": "Float64"},
        {"id": "label", "typeid": "String"},
    ],
}


@pytest.fixture()
def mh_server():
    state = {}

    def start(data_dir=None):
        server = MaterializedHistoryServer(data_dir=data_dir)
        loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(loop)
            loop.run_until_complete(server.start())
            started.set()
            loop.run_forever()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert started.wait(10)
        state.update(server=server, loop=loop, thread=t)
        return server

    yield start
    if state:
        fut = asyncio.run_coroutine_threadsafe(
            state["server"].stop(), state["loop"])
        try:
            fut.result(timeout=10)
        except Exception:
            pass
        state["loop"].call_soon_threadsafe(state["loop"].stop)
        state["thread"].join(timeout=10)


def _session_with_commits():
    """Two clients editing one SharedPropertyTree; returns the
    sequenced log and the number of changeset commits in it."""
    s = ContainerSession(["A", "B"])
    log = []
    orig = s._broadcast
    s._broadcast = lambda m: (log.append(m), orig(m))[1]
    for cid in ("A", "B"):
        s.runtime(cid).create_datastore("ds").create_channel(
            "sharedpropertytree", "pt")
        t = s.runtime(cid).get_datastore("ds").get_channel("pt")
        t.schemas.register(POINT)
    s.process_all()
    ta = s.runtime("A").get_datastore("ds").get_channel("pt")
    tb = s.runtime("B").get_datastore("ds").get_channel("pt")
    # also a non-PropertyDDS channel: its ops must NOT publish
    s.runtime("A").get_datastore("ds").create_channel(
        "sharedmap", "m")
    s.process_all()
    m = s.runtime("A").get_datastore("ds").get_channel("m")
    n_commits = 0
    for i in range(3):
        ta.insert_property(f"p{i}", "test:point-1.0.0")
        ta.commit()
        n_commits += 1
        m.set(f"k{i}", i)
        s.process_all()
    tb.set_value("p0.x", 4.5)
    tb.commit()
    n_commits += 1
    s.process_all()
    assert ta.signature() == tb.signature()
    return log, n_commits


def test_derived_guid_deterministic_uuid_shape():
    g1 = derived_guid("branch-a", "root")
    g2 = derived_guid("branch-a", "root")
    assert g1 == g2
    assert re.fullmatch(
        r"[0-9a-f]{8}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{4}"
        r"-[0-9a-f]{12}", g1)
    assert derived_guid("branch-a", "other") != g1


def test_lambda_publishes_commit_chain(mh_server):
    server = mh_server()
    log, n_commits = _session_with_commits()
    client = MaterializedHistoryClient("127.0.0.1", server.port)
    ckpts = []
    lam = MoiraLambda(client, "doc", checkpoint=ckpts.append)
    for i, msg in enumerate(log):
        lam.handler(msg, offset=i)
    assert lam.flush() == n_commits
    assert ckpts == [len(log) - 1]
    branch = derived_guid("doc", "ds/pt")
    state = client.get_branch(branch)
    assert state is not None
    commits = state["commits"]
    assert len(commits) == n_commits
    # parent chain: root -> c0 -> c1 -> ...
    parents = [c["parentGuid"] for c in commits]
    assert parents[0] == state["rootCommitGuid"]
    assert parents[1:] == [c["guid"] for c in commits[:-1]]
    # meta carries seq/msn; seqs strictly increase
    seqs = [c["meta"]["sequenceNumber"] for c in commits]
    assert seqs == sorted(seqs)
    assert all(c["rebase"] for c in commits)
    assert all("changeSet" in c for c in commits)
    # the sharedmap channel produced no branch
    assert client.get_branch(derived_guid("doc", "ds/m")) is None
    # nothing pending after a clean flush; repeat flush is a no-op
    assert lam.flush() == 0
    client.close()


def test_flush_failure_restores_pending_then_replays(mh_server):
    server = mh_server()
    log, n_commits = _session_with_commits()
    client = MaterializedHistoryClient("127.0.0.1", server.port)
    ckpts = []
    lam = MoiraLambda(client, "doc", checkpoint=ckpts.append)
    for i, msg in enumerate(log):
        lam.handler(msg, offset=i)

    real = client.create_commit
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise ConnectionError("mid-publish crash")
        return real(*a, **kw)

    client.create_commit = flaky
    with pytest.raises(ConnectionError):
        lam.flush()
    assert ckpts == []  # no checkpoint on failure
    assert lam.pending  # batch restored for replay
    client.create_commit = real
    # at-least-once replay: idempotent MH verbs dedupe the commit
    # that landed before the crash
    assert lam.flush() == n_commits - 1 + 1  # republishes all pending
    state = client.get_branch(derived_guid("doc", "ds/pt"))
    assert len(state["commits"]) == n_commits
    assert ckpts == [len(log) - 1]
    client.close()


@pytest.mark.slow
def test_moira_two_process_durable(tmp_path):
    """MH service in another OS process with a durable data dir: the
    lambda publishes over TCP; a SIGKILL + restart serves the same
    branch state back (the deployment shape of the reference's
    Materialized History endpoint)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def spawn():
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "fluidframework_tpu.service.moira",
             "--port", "0", "--data-dir", str(tmp_path / "mh")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=repo, env=env,
        )
        line = proc.stdout.readline()
        m = re.search(r"listening on [\w.]+:(\d+)", line)
        assert m, line
        return proc, int(m.group(1))

    proc, port = spawn()
    try:
        log, n_commits = _session_with_commits()
        client = MaterializedHistoryClient("127.0.0.1", port)
        lam = MoiraLambda(client, "doc")
        for i, msg in enumerate(log):
            lam.handler(msg, offset=i)
        assert lam.flush() == n_commits
        client.close()
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
        proc, port = spawn()
        client = MaterializedHistoryClient("127.0.0.1", port)
        state = client.get_branch(derived_guid("doc", "ds/pt"))
        assert state is not None and len(state["commits"]) == n_commits
        client.close()
    finally:
        proc.kill()
        proc.wait(timeout=10)


def test_mh_client_corrupt_frame_drops_socket():
    """Protocol faults (not just connection faults) must drop the MH
    client's cached socket: after a corrupt length prefix the stream
    position is garbage and reuse would return mis-parsed frames."""
    from fluidframework_tpu.testing.fault_injection import (
        ScriptedFrameServer,
    )

    with ScriptedFrameServer([ScriptedFrameServer.CORRUPT]) as srv:
        client = MaterializedHistoryClient("127.0.0.1", srv.port,
                                           timeout=5.0)
        with pytest.raises(ValueError, match="exceeds"):
            client.get_branch("b")
        assert client._sock is None  # not cached for reuse
        client.close()
