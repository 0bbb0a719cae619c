"""Durable storage plane: content-addressed summary trees, incremental
handle summaries, chunked snapshots, persisted op log + checkpoints,
and kill-and-restart resume across a real process boundary.

Reference parity: historian/gitrest (content-addressed summary
storage), SummaryType.Handle incremental summaries (summary.ts:55-59),
chunked merge-tree snapshots (snapshotV1.ts:36, snapshotChunks.ts),
scriptorium's durable op log, deli checkpoint/restore
(deli/checkpointContext.ts).
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.service.local_server import LocalServer
from fluidframework_tpu.service.storage import (
    ContentStore,
    DocumentStorage,
    FileContentStore,
    SummaryTreeStore,
)


# ----------------------------------------------------------------------
# content-addressed tree store

def test_tree_store_roundtrip_and_dedup():
    store = SummaryTreeStore(ContentStore())
    summary = {
        "protocol": {"members": ["a", "b"]},
        "runtime": {
            "datastores": {
                "d": {"root": True, "channels": {
                    "t": {"type": "sharedstring",
                          "content": {"chunks": [[1, 2], [3]]}},
                }},
            },
            "blobs": {},
        },
    }
    root1 = store.write(summary)
    assert store.read(root1) == summary
    n1 = store.store.object_count()
    # identical summary: zero new objects
    root2 = store.write(summary)
    assert root2 == root1
    assert store.store.object_count() == n1
    # change one channel chunk: only the changed path writes objects
    summary2 = json.loads(json.dumps(summary))
    summary2["runtime"]["datastores"]["d"]["channels"]["t"][
        "content"]["chunks"][1] = [3, 4]
    root3 = store.write(summary2, previous_root=root1)
    delta = store.store.object_count() - n1
    assert root3 != root1
    assert delta <= 8  # changed chunk + spine rewrite, not O(tree)
    # the chunk split must actually engage (a regression at depth 5
    # stored the whole multi-chunk snapshot as one blob)
    assert any(
        b"__chunklist__" in store.store._load(sha)
        for sha in store.store._objects
    )
    # unchanged chunk [1, 2] was reused: exactly one object holds it
    chunk_sha = store.store.put([1, 2])  # idempotent: already there
    assert store.store.has(chunk_sha)


def test_tree_store_handle_resolution():
    store = SummaryTreeStore(ContentStore())
    v1 = {"runtime": {"datastores": {"d": {"channels": {
        "t": {"type": "x", "content": {"v": 1}},
    }}}}}
    root1 = store.write(v1)
    v2 = {"runtime": {"datastores": {"d": {"channels": {
        "t": {"__summary_handle__": "runtime/datastores/d/channels/t"},
    }}}}}
    root2 = store.write(v2, previous_root=root1)
    assert store.read(root2) == v1
    with pytest.raises(ValueError):
        store.write(v2, previous_root=None)


def test_file_content_store_persists(tmp_path):
    root = str(tmp_path / "store")
    s1 = FileContentStore(root)
    sha = s1.put({"hello": [1, 2, 3]})
    s2 = FileContentStore(root)  # fresh instance, same dir
    assert s2.has(sha)
    assert s2.get(sha) == {"hello": [1, 2, 3]}


# ----------------------------------------------------------------------
# incremental summaries end to end (client handles -> store expansion)

def _mk_pair(server):
    factory = LocalDocumentServiceFactory(server)
    a = Container.load(factory.create_document_service("doc"),
                       client_id="alice")
    text = (a.runtime.create_datastore("d")
            .create_channel("sharedstring", "t"))
    other = a.runtime.get_datastore("d").create_channel(
        "sharedmap", "m"
    )
    a.flush()
    return a, text, other


def test_incremental_summary_unchanged_channel_is_handle():
    server = LocalServer()
    a, text, other = _mk_pair(server)
    text.insert_text(0, "hello world")
    other.set("k", 1)
    a.flush()
    a.summarize()  # full; ack arrives synchronously via local orderer

    # edit ONLY the map; the string must summarize as a handle
    other.set("k", 2)
    a.flush()
    summary = a.summarize(incremental=True)
    channels = summary["runtime"]["datastores"]["d"]["channels"]
    assert "__summary_handle__" in channels["t"]
    assert "content" in channels["m"]

    # the stored (expanded) version still loads with full content
    latest = server.get_orderer("doc").summary_store.latest()
    stored = latest.summary["summary"] if "summary" in latest.summary \
        else latest.summary
    chans = stored["runtime"]["datastores"]["d"]["channels"]
    assert chans["t"]["type"] == "sharedstring"
    b = Container.load(
        LocalDocumentServiceFactory(server)
        .create_document_service("doc"),
        client_id="bob",
    )
    tb = b.runtime.get_datastore("d").get_channel("t")
    assert tb.get_text() == "hello world"
    assert b.runtime.get_datastore("d").get_channel("m").get("k") == 2


def test_second_summary_of_unchanged_container_is_cheap():
    server = LocalServer()
    a, text, other = _mk_pair(server)
    text.insert_text(0, "stable content " * 50)
    a.flush()
    a.summarize()
    store = server.get_orderer("doc").summary_store
    n1 = store.object_count()
    # nothing changed except the collab window advancing via the
    # summarize op itself; the second incremental summary should write
    # O(1) new objects, not re-store every channel
    a.summarize(incremental=True)
    assert store.version_count == 2
    assert store.object_count() - n1 <= 10


def test_chunked_snapshot_roundtrip():
    server = LocalServer()
    a, text, _ = _mk_pair(server)
    from fluidframework_tpu.models import sharedstring as ss_mod

    # force multiple chunks with a small chunk size
    orig = ss_mod.SNAPSHOT_CHUNK_SEGMENTS
    ss_mod.SNAPSHOT_CHUNK_SEGMENTS = 4
    try:
        for i in range(30):
            text.insert_text(0, f"w{i} ")
        a.flush()
        summary = text.summarize_core()
        assert summary["format"] == 2
        assert len(summary["chunks"]) > 1
        clone = type(text)("t2")
        clone.load_core(summary)
        assert clone.get_text() == text.get_text()
        # format-1 (flat) snapshots must still load
        flat = {
            "segments": [e for c in summary["chunks"] for e in c],
            "minSeq": summary["minSeq"],
            "currentSeq": summary["currentSeq"],
            "intervals": {},
        }
        clone2 = type(text)("t3")
        clone2.load_core(flat)
        assert clone2.get_text() == text.get_text()
    finally:
        ss_mod.SNAPSHOT_CHUNK_SEGMENTS = orig


# ----------------------------------------------------------------------
# durable op log + checkpoint across a REAL process restart

def _run_worker(port, client_id, action):
    code = (
        "import sys; sys.path.insert(0, '.')\n"
        "from fluidframework_tpu.drivers.socket_driver import "
        "SocketDocumentService\n"
        "from fluidframework_tpu.loader import Container\n"
        f"svc = SocketDocumentService('127.0.0.1', {port}, 'dur-doc')\n"
        "with svc.lock:\n"
        f"    c = Container.load(svc, client_id={client_id!r})\n"
        "with svc.lock:\n"
        + action +
        "\nimport time\n"
        "deadline = time.time() + 30\n"
        "while time.time() < deadline:\n"
        "    with svc.lock:\n"
        "        if c.runtime.pending.count == 0: break\n"
        "    time.sleep(0.02)\n"
        "else:\n"
        "    raise TimeoutError('ops never acked')\n"
        "c.close(); svc.close()\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


@pytest.mark.slow
def test_service_survives_kill_and_restart(tmp_path):
    """The service resumes from durable
    state across a process restart (SIGKILL, no graceful shutdown)."""
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

    server, port = start_server()
    try:
        _run_worker(port, "alice", (
            "    t = c.runtime.create_datastore('d')"
            ".create_channel('sharedstring', 't')\n"
            "    c.flush()\n"
            "    t.insert_text(0, 'before the crash')\n"
            "    c.flush()\n"
        ))
    finally:
        os.kill(server.pid, signal.SIGKILL)
        server.wait()

    # restart over the same data dir: op log + checkpoint reload
    server, port = start_server()
    try:
        out = _run_worker(port, "bob", (
            "    t = c.runtime.get_datastore('d').get_channel('t')\n"
            "    print('TEXT=' + t.get_text())\n"
            "    t.insert_text(0, 'after: ')\n"
            "    c.flush()\n"
            "    print('FINAL=' + t.get_text())\n"
        ))
        assert "TEXT=before the crash" in out
        assert "FINAL=after: before the crash" in out
    finally:
        server.kill()
        server.wait()

# ----------------------------------------------------------------------
# crash-atomicity: the enumerated torn states (chaos PR; the write
# barriers are write-temp+fsync+rename for the checkpoint and
# fsync-before-fanout for the op log — docs/ROBUSTNESS.md)

def _drive_some_ops(durable_dir, n=5):
    """Crash-shaped teardown: the container is ABANDONED, not closed
    — a crash sequences no client-leave, so the log's tail is the
    last real op (what the tear tests truncate)."""
    server = LocalServer(durable_dir=str(durable_dir))
    factory = LocalDocumentServiceFactory(server)
    c = Container.load(factory.create_document_service("torn-doc"),
                       client_id="w")
    ds = c.runtime.create_datastore("app")
    ds.create_channel("sharedstring", "t")
    text = c.runtime.get_datastore("app").get_channel("t")
    for i in range(n):
        text.insert_text(0, f"x{i}.")
        c.flush()
    final = text.get_text()
    return server, final


def _reload_text(durable_dir):
    server = LocalServer(durable_dir=str(durable_dir))
    factory = LocalDocumentServiceFactory(server)
    c = Container.load(factory.create_document_service("torn-doc"),
                       client_id="r")
    out = c.runtime.get_datastore("app").get_channel("t").get_text()
    c.close()
    return server, out


def test_torn_checkpoint_final_recovers_from_op_log(tmp_path):
    """The reordered-write crash state (rename durable before data —
    what the missing fsync used to permit): a prefix-truncated
    checkpoint.json parses as garbage. read_checkpoint must degrade
    LOUDLY to None and the restart fast-forwards the full op log."""
    _, final = _drive_some_ops(tmp_path)
    ckpt = tmp_path / "torn-doc" / "checkpoint.json"
    data = ckpt.read_bytes()
    ckpt.write_bytes(data[: len(data) // 2])
    server, text = _reload_text(tmp_path)
    assert text == final
    # and sequencing continues contiguously after the recovery
    orderer = server.get_orderer("torn-doc")
    last = orderer.op_log.last_seq
    orderer.connect(__import__(
        "fluidframework_tpu.protocol.messages",
        fromlist=["ClientDetail"]).ClientDetail("w2"))
    assert orderer.op_log.last_seq == last + 1


def test_crash_between_checkpoint_write_and_rename(tmp_path):
    """A torn .tmp beside the intact checkpoint (crash inside the
    write-temp+fsync+rename window): the committed checkpoint is the
    truth; the debris is cleared on reload."""
    _, final = _drive_some_ops(tmp_path)
    tmp = tmp_path / "torn-doc" / "checkpoint.json.tmp"
    tmp.write_bytes(b'{"sequencer": {"torn')
    _, text = _reload_text(tmp_path)
    assert text == final
    assert not tmp.exists(), "stale checkpoint tmp must be cleared"


def test_torn_oplog_tail_is_discarded_and_rewritten(tmp_path):
    """Crash mid-append: a partial final JSONL line. The loader
    discards exactly that op (never fanned out, so no client has it
    — the fsync-before-fanout barrier) and rewrites the log so a
    second crash cannot stack onto the half record."""
    _, final = _drive_some_ops(tmp_path)
    oplog = tmp_path / "torn-doc" / "ops.jsonl"
    lines = oplog.read_bytes().splitlines(keepends=True)
    torn_away = json.loads(lines[-1])
    oplog.write_bytes(b"".join(lines[:-1]) + lines[-1][: len(lines[-1]) // 2])
    server, text = _reload_text(tmp_path)
    # the torn op's insert is gone (x4.), everything before it intact
    assert text == final.replace("x4.", "", 1)
    # the log was re-truncated to whole records and new sequencing
    # continues contiguously from the surviving head: the torn op's
    # seq slot is REUSED (here by the reader's join) — never left as
    # a gap, never still holding the torn OPERATION
    reread = [json.loads(ln) for ln in
              oplog.read_bytes().splitlines() if ln.strip()]
    seqs = [r["sequenceNumber"] for r in reread]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    reused = [r for r in reread
              if r["sequenceNumber"] == torn_away["sequenceNumber"]]
    assert all(r["type"] != torn_away["type"] for r in reused)
    orderer = server.get_orderer("torn-doc")
    assert orderer.sequencer.sequence_number == \
        orderer.op_log.last_seq


def test_torn_middle_oplog_line_is_corruption_not_crash(tmp_path):
    """A malformed line ANYWHERE but the tail is not a legal crash
    state (appends are sequential + fsynced): refuse loudly."""
    _, _ = _drive_some_ops(tmp_path)
    oplog = tmp_path / "torn-doc" / "ops.jsonl"
    lines = oplog.read_bytes().splitlines(keepends=True)
    lines[1] = lines[1][: len(lines[1]) // 2].rstrip() + b"\n"
    oplog.write_bytes(b"".join(lines))
    with pytest.raises(ValueError, match="corrupt at line 2"):
        _reload_text(tmp_path)


def test_torn_versions_tail_is_discarded_and_rewritten(tmp_path):
    """A torn versions.jsonl tail must be REWRITTEN on load, not just
    skipped: the next commit_summary appends, and stacking a fresh
    record onto the half line would turn a recoverable crash state
    into mid-file corruption at the load after that."""
    from fluidframework_tpu.service.storage import DocumentStorage

    st = DocumentStorage(str(tmp_path / "doc"))
    st.write_summary(1, {"runtime": {"a": 1}})
    st.write_summary(2, {"runtime": {"a": 2}})
    vpath = tmp_path / "doc" / "versions.jsonl"
    lines = vpath.read_bytes().splitlines(keepends=True)
    vpath.write_bytes(b"".join(lines[:-1]) + lines[-1][:10])
    st2 = DocumentStorage(str(tmp_path / "doc"))
    assert [v.sequence_number for v in st2.versions] == [1]
    # the append after recovery lands on a CLEAN file...
    st2.write_summary(3, {"runtime": {"a": 3}})
    # ...so the next load parses every line (no mid-file corruption)
    st3 = DocumentStorage(str(tmp_path / "doc"))
    assert [v.sequence_number for v in st3.versions] == [1, 3]


def test_queue_commit_offset_is_crash_atomic(tmp_path):
    """FileOrderingQueue.commit used to plain-overwrite the offset
    file — a crash mid-write could leave a TORN offset. It now routes
    through storage.atomic_write (asserted structurally), leaves no
    tmp debris, and tolerates pre-barrier debris on load."""
    from fluidframework_tpu.service import partitioning as part
    from fluidframework_tpu.service.partitioning import (
        FileOrderingQueue,
    )

    q = FileOrderingQueue(str(tmp_path / "q"), 1)
    q.produce(0, "d", {"v": 1})
    calls = []
    real = part.atomic_write

    def spy(path, data):
        calls.append(path)
        real(path, data)

    part.atomic_write = spy
    try:
        q.commit(0, 0)
    finally:
        part.atomic_write = real
    assert calls and calls[0].endswith("partition-0.offset"), (
        "commit must route through the shared crash-atomic barrier")
    assert not os.path.exists(
        str(tmp_path / "q" / "partition-0.offset.tmp"))
    # stale .tmp debris (crash between write and rename) is cleared
    # on load and the committed file stays the truth
    debris = tmp_path / "q" / "partition-0.offset.tmp"
    debris.write_text("99")
    q2 = FileOrderingQueue(str(tmp_path / "q"), 1)
    assert q2.committed(0) == 0
    assert not debris.exists()


def test_torn_queue_offset_states_are_pinned(tmp_path):
    """The enumerated torn-offset states the old plain overwrite
    permitted: (a) a numeric PREFIX ("1" torn from "15") silently
    rewinds the checkpoint — absorbed, because consumers re-read from
    the committed offset and the at-least-once dedupe drops replays;
    (b) garbage degrades LOUDLY to 'no commit' instead of crashing
    the partition load."""
    from fluidframework_tpu.service.partitioning import (
        FileOrderingQueue,
    )

    root = str(tmp_path / "q")
    q = FileOrderingQueue(root, 1)
    for i in range(16):
        q.produce(0, "d", {"v": i})
    q.commit(0, 14)
    offset = tmp_path / "q" / "partition-0.offset"
    # (a) torn numeric prefix: "1" of "14" — a legal rewind
    offset.write_text("1")
    q2 = FileOrderingQueue(root, 1)
    assert q2.committed(0) == 1
    assert [r.offset for r in q2.read(0, q2.committed(0) + 1)] == \
        list(range(2, 16)), "re-consume from the rewound offset"
    # monotone guard: a late commit below the head is still honored
    # forward, never backward
    q2.commit(0, 14)
    assert q2.committed(0) == 14
    # (b) garbage: degrade loudly to -1, never crash the load
    before = __import__(
        "fluidframework_tpu.obs.metrics",
        fromlist=["REGISTRY"]).REGISTRY.flat().get(
        'storage_torn_recoveries_total{file="queue-offset"}', 0)
    offset.write_text("not-a-number")
    q3 = FileOrderingQueue(root, 1)
    assert q3.committed(0) == -1
    assert [r.offset for r in q3.read(0, 0)][:2] == [0, 1]
    after = __import__(
        "fluidframework_tpu.obs.metrics",
        fromlist=["REGISTRY"]).REGISTRY.flat().get(
        'storage_torn_recoveries_total{file="queue-offset"}', 0)
    assert after == before + 1, "the degrade must be LOUD"


# ----------------------------------------------------------------------
# bit rot: per-record CRCs + the scrubber (docs/ROBUSTNESS.md
# "Partition tolerance & degraded mode")


def test_midfile_bit_flip_detected_and_read_repaired(tmp_path):
    """A parseable record whose bytes changed at rest (crc mismatch)
    is CORRUPTION, not a crash state: the load refuses loudly, and
    the scrubber read-repairs it from a peer-supplied copy."""
    from fluidframework_tpu.service.storage import (
        CorruptRecordError,
        scrub_jsonl,
        scrub_repair_jsonl,
    )

    _, final = _drive_some_ops(tmp_path)
    oplog = tmp_path / "torn-doc" / "ops.jsonl"
    lines = oplog.read_text().splitlines(keepends=True)
    pristine = json.loads(lines[1])
    row = json.loads(lines[1])
    row["contents"] = {"bitrot": True}  # stale _crc: mismatch
    lines[1] = json.dumps(row) + "\n"
    oplog.write_text("".join(lines))
    # the load refuses: rot must never be silently served
    with pytest.raises(CorruptRecordError, match="crc mismatch"):
        _reload_text(tmp_path)
    # detect-only scrub classifies it (and nothing else)
    report = scrub_jsonl(str(oplog), "oplog")
    assert report.corrupt == [1] and not report.torn_tail
    # read-repair from a "peer" copy makes the log whole again
    repaired = scrub_repair_jsonl(
        str(oplog), "oplog",
        lambda i, rows: dict(pristine) if i == 1 else None)
    assert repaired.repaired == 1
    _, text = _reload_text(tmp_path)
    assert text == final


def test_torn_tail_still_recovers_locally_not_via_scrub(tmp_path):
    """The scrubber DISTINGUISHES: a torn tail is the PR9-recoverable
    crash state — left byte-for-byte for the loader's local discard,
    never treated as rot needing a peer."""
    from fluidframework_tpu.service.storage import (
        scrub_jsonl,
        scrub_repair_jsonl,
    )

    _, final = _drive_some_ops(tmp_path)
    oplog = tmp_path / "torn-doc" / "ops.jsonl"
    lines = oplog.read_bytes().splitlines(keepends=True)
    oplog.write_bytes(b"".join(lines[:-1])
                      + lines[-1][: len(lines[-1]) // 2])
    report = scrub_jsonl(str(oplog), "oplog")
    assert report.torn_tail and report.corrupt == []
    # a repair pass with NO peer must succeed: nothing to repair
    repaired = scrub_repair_jsonl(str(oplog), "oplog",
                                  lambda i, rows: None)
    assert repaired.repaired == 0
    # the loader's torn-tail discard still applies (PR9 path)
    _, text = _reload_text(tmp_path)
    assert text == final.replace("x4.", "", 1)


def test_garbage_crc_with_no_surviving_peer_raises_loudly(tmp_path):
    """Unrepairable rot (every copy gone) must detonate, not degrade:
    serving a record whose bytes are provably wrong would be silent
    corruption."""
    from fluidframework_tpu.service.storage import (
        CorruptRecordError,
        scrub_repair_jsonl,
    )

    _drive_some_ops(tmp_path)
    oplog = tmp_path / "torn-doc" / "ops.jsonl"
    lines = oplog.read_text().splitlines(keepends=True)
    row = json.loads(lines[2])
    row["_crc"] = (row.get("_crc") or 0) + 1  # garbage checksum
    lines[2] = json.dumps(row) + "\n"
    oplog.write_text("".join(lines))
    with pytest.raises(CorruptRecordError, match="no surviving peer"):
        scrub_repair_jsonl(str(oplog), "oplog",
                           lambda i, rows: None)


def test_queue_record_crc_detected_and_scrubbed(tmp_path):
    """The partitioned plane's half: a bit-flipped queue record is
    refused on consume and read-repaired from a replica root by
    ReplicatedFileOrderingQueue.scrub()."""
    from fluidframework_tpu.service.partitioning import (
        ReplicatedFileOrderingQueue,
    )
    from fluidframework_tpu.service.storage import CorruptRecordError

    roots = [str(tmp_path / n) for n in ("lead", "f1", "f2")]
    q = ReplicatedFileOrderingQueue(roots[0], 1, roots[1:])
    for i in range(4):
        q.produce(0, "d", {"v": i})
    # flip a byte in one FOLLOWER root's record 1
    log = tmp_path / "f1" / "partition-0.jsonl"
    lines = log.read_text().splitlines(keepends=True)
    row = json.loads(lines[1])
    row["payload"] = {"v": 99}  # stale crc
    lines[1] = json.dumps(row) + "\n"
    log.write_text("".join(lines))
    from fluidframework_tpu.service.partitioning import (
        FileOrderingQueue,
    )

    broken = FileOrderingQueue(str(tmp_path / "f1"), 1)
    with pytest.raises(CorruptRecordError, match="crc"):
        list(broken.read(0, 0))
    assert q.scrub() == 1
    fixed = FileOrderingQueue(str(tmp_path / "f1"), 1)
    assert [r.payload["v"] for r in fixed.read(0, 0)] == [0, 1, 2, 3]


def test_legacy_rows_without_crc_still_load(tmp_path):
    """The PR4/PR6 interop discipline: pre-existing logs whose rows
    carry no _crc keep loading (nothing to verify), and the next
    rewrite stamps them."""
    from fluidframework_tpu.service.storage import (
        read_jsonl_tolerant,
    )

    _, final = _drive_some_ops(tmp_path)
    oplog = tmp_path / "torn-doc" / "ops.jsonl"
    rows = [json.loads(ln) for ln in
            oplog.read_text().splitlines()]
    for r in rows:
        r.pop("_crc", None)
    oplog.write_text("".join(json.dumps(r) + "\n" for r in rows))
    loaded, torn = read_jsonl_tolerant(str(oplog), "oplog")
    assert len(loaded) == len(rows) and not torn
    _, text = _reload_text(tmp_path)
    assert text == final


def test_gap_over_truncated_log_raises_actionably(tmp_path):
    """A replica behind a summary-truncated log whose reconnect-time
    catch-up was EMPTY (no trailing ops yet) must fail with the loud
    truncation error when the next fanout exposes the unfillable gap
    — not the bare inbound-contiguity assert."""
    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    a = Container.load(factory.create_document_service("trunc-doc"),
                       client_id="a")
    ds = a.runtime.create_datastore("app")
    ds.create_channel("sharedstring", "t")
    for i in range(4):
        ds.get_channel("t").insert_text(0, f"x{i}")
        a.flush()
    b = Container.load(factory.create_document_service("trunc-doc"),
                       client_id="b")
    b.disconnect()
    # while b is offline: more ops, then a summary truncates the log
    # above b's position, then NO trailing ops before b reconnects
    for i in range(3):
        ds.get_channel("t").insert_text(0, f"y{i}")
        a.flush()
    orderer = server.get_orderer("trunc-doc")
    orderer.op_log.truncate_below(orderer.sequencer.sequence_number)
    # reconnect: the direct catch-up read is empty (nothing trails
    # the truncation), but the join broadcast immediately exposes the
    # unfillable gap — loud and actionable, not the bare contiguity
    # assert three frames later
    with pytest.raises(RuntimeError, match="not in delta storage"):
        b.connect()
    a.close()
