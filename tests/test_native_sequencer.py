"""Native (C++) sequencer core: differential tests vs the Python
DocumentSequencer oracle, checkpoint parity, end-to-end service use.

SURVEY §4's TPU-kernel pillar applies to native host code too: the
scalar Python implementation is the spec; the native core must match
it op-for-op on fuzzed streams, including every nack path.
"""
import random
import shutil

import pytest

from fluidframework_tpu.protocol.messages import (
    ClientDetail,
    DocumentMessage,
    MessageType,
)
from fluidframework_tpu.service.sequencer import DocumentSequencer

native = pytest.importorskip("fluidframework_tpu.native")
try:
    native.NativeSequencerCore("probe")
    HAVE_NATIVE = True
except (RuntimeError, OSError):  # no toolchain in this environment
    HAVE_NATIVE = False

pytestmark = pytest.mark.skipif(
    not HAVE_NATIVE, reason="native toolchain unavailable"
)


def op(csn, refseq):
    return DocumentMessage(
        client_sequence_number=csn,
        reference_sequence_number=refseq,
        type=MessageType.OPERATION,
        contents={"csn": csn},
    )


def make_pair():
    return (DocumentSequencer("doc"),
            native.NativeSequencerCore("doc"))


def assert_same_result(py_result, nat_result):
    assert (py_result.message is None) == (nat_result.message is None)
    assert (py_result.nack is None) == (nat_result.nack is None)
    if py_result.message is not None:
        pm, nm = py_result.message, nat_result.message
        assert pm.sequence_number == nm.sequence_number
        assert pm.minimum_sequence_number == nm.minimum_sequence_number
        assert pm.client_sequence_number == nm.client_sequence_number


def test_join_ticket_leave_parity():
    py, nat = make_pair()
    for cid in ("A", "B", "C"):
        pj = py.client_join(ClientDetail(cid))
        nj = nat.client_join(ClientDetail(cid))
        assert pj.sequence_number == nj.sequence_number
        assert pj.minimum_sequence_number == nj.minimum_sequence_number
    assert_same_result(py.ticket("A", op(1, 2)), nat.ticket("A", op(1, 2)))
    assert_same_result(py.ticket("B", op(1, 3)), nat.ticket("B", op(1, 3)))
    pl, nl = py.client_leave("C"), nat.client_leave("C")
    assert pl.sequence_number == nl.sequence_number
    assert py.minimum_sequence_number == nat.minimum_sequence_number
    assert set(py.clients) == set(nat.clients)


def test_nack_paths_parity():
    py, nat = make_pair()
    for s in (py, nat):
        s.client_join(ClientDetail("A"))
    # unknown client
    assert_same_result(py.ticket("X", op(1, 0)), nat.ticket("X", op(1, 0)))
    # csn gap
    assert_same_result(py.ticket("A", op(5, 1)), nat.ticket("A", op(5, 1)))
    # duplicate (dropped)
    for s in (py, nat):
        s.ticket("A", op(1, 1))
    assert_same_result(py.ticket("A", op(1, 1)), nat.ticket("A", op(1, 1)))
    # refSeq ahead
    assert_same_result(py.ticket("A", op(2, 99)), nat.ticket("A", op(2, 99)))


def test_fuzzed_stream_parity():
    """Long random stream with joins/leaves/valid/invalid ops: the
    sequenced (seq, msn) streams must match exactly."""
    rng = random.Random(42)
    py, nat = make_pair()
    csn = {}
    alive = []
    for step in range(3000):
        action = rng.random()
        if action < 0.05 or not alive:
            cid = f"c{rng.randrange(8)}"
            pj = py.client_join(ClientDetail(cid))
            nj = nat.client_join(ClientDetail(cid))
            assert pj.sequence_number == nj.sequence_number
            assert (pj.minimum_sequence_number
                    == nj.minimum_sequence_number)
            if cid not in alive:
                alive.append(cid)
                csn.setdefault(cid, 0)
        elif action < 0.08 and len(alive) > 1:
            cid = rng.choice(alive)
            alive.remove(cid)
            pl, nl = py.client_leave(cid), nat.client_leave(cid)
            assert pl.sequence_number == nl.sequence_number
        else:
            cid = rng.choice(alive)
            if rng.random() < 0.1:  # invalid op variants
                bad_csn = csn[cid] + rng.choice([0, 2, 5])
                refseq = rng.randrange(py.sequence_number + 3)
                o = op(bad_csn, refseq)
            else:
                csn[cid] += 1
                refseq = rng.randrange(
                    py.minimum_sequence_number,
                    py.sequence_number + 1,
                )
                o = op(csn[cid], refseq)
            pr, nr = py.ticket(cid, o), nat.ticket(cid, o)
            assert_same_result(pr, nr)
            if pr.nack is not None and "gap" in pr.nack.message:
                # both rejected; keep oracle csn consistent
                pass
            if pr.message is None and pr.nack is None:
                pass  # duplicate dropped in both
    assert py.sequence_number == nat.sequence_number
    assert py.minimum_sequence_number == nat.minimum_sequence_number


def test_checkpoint_restore_parity():
    py, nat = make_pair()
    for s in (py, nat):
        s.client_join(ClientDetail("A"))
        s.client_join(ClientDetail("B"))
        s.ticket("A", op(1, 1))
        s.ticket("B", op(1, 2))
    py2 = DocumentSequencer.restore(py.checkpoint())
    nat2 = native.NativeSequencerCore.restore(nat.checkpoint())
    assert_same_result(py2.ticket("A", op(2, 3)), nat2.ticket("A", op(2, 3)))
    assert py2.minimum_sequence_number == nat2.minimum_sequence_number


def test_batch_ticketing_matches_sequential():
    nat_seq = native.NativeSequencerCore("doc")
    nat_batch = native.NativeSequencerCore("doc")
    for s in (nat_seq, nat_batch):
        s.client_join(ClientDetail("A"))
        s.client_join(ClientDetail("B"))
    ops = [("A", op(1, 1)), ("B", op(1, 2)), ("A", op(2, 2)),
           ("B", op(5, 2)), ("A", op(3, 4))]
    sequential = [nat_seq.ticket(cid, o) for cid, o in ops]
    batched = nat_batch.ticket_batch(ops)
    for s, b in zip(sequential, batched):
        assert (s.message is None) == (b.message is None)
        if s.message:
            assert s.message.sequence_number == b.message.sequence_number
            assert (s.message.minimum_sequence_number
                    == b.message.minimum_sequence_number)


def test_batch_nack_seq_matches_sequential_oracle():
    py, nat = make_pair()
    for s in (py, nat):
        s.client_join(ClientDetail("A"))
        s.client_join(ClientDetail("B"))
    ops = [("A", op(5, 2)), ("B", op(1, 2)), ("B", op(2, 2))]
    seq_results = [py.ticket(cid, o) for cid, o in ops]
    batch_results = nat.ticket_batch(ops)
    for s, b in zip(seq_results, batch_results):
        if s.nack is not None:
            assert b.nack.sequence_number == s.nack.sequence_number


def test_native_summarize_flow(monkeypatch):
    """summaryAck system ops must sequence through the native core."""
    monkeypatch.setenv("FFTPU_NATIVE_SEQUENCER", "1")
    from fluidframework_tpu.drivers import LocalDocumentServiceFactory
    from fluidframework_tpu.loader import Container
    from fluidframework_tpu.service.local_server import LocalServer

    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    a = Container.load(factory.create_document_service("doc"),
                       client_id="alice")
    m = a.runtime.create_datastore("d").create_channel("sharedmap", "m")
    a.flush()
    m.set("k", 1)
    a.flush()
    acks = []
    a.on("summaryAck", lambda ack: acks.append(ack))
    a.summarize()
    assert acks, "summary ack did not round-trip via native sequencer"
    late = Container.load(factory.create_document_service("doc"),
                          client_id="late")
    assert late.runtime.get_datastore("d").get_channel("m").get("k") == 1


def test_native_sequencer_serves_local_orderer(monkeypatch):
    monkeypatch.setenv("FFTPU_NATIVE_SEQUENCER", "1")
    from fluidframework_tpu.drivers import LocalDocumentServiceFactory
    from fluidframework_tpu.loader import Container
    from fluidframework_tpu.native import NativeSequencerCore
    from fluidframework_tpu.service.local_server import LocalServer

    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    a = Container.load(factory.create_document_service("doc"),
                       client_id="alice")
    b = Container.load(factory.create_document_service("doc"),
                       client_id="bob")
    assert isinstance(
        server.get_orderer("doc").sequencer, NativeSequencerCore
    )
    sa = a.runtime.create_datastore("d").create_channel("sharedstring", "t")
    a.flush()
    sa.insert_text(0, "native")
    a.flush()
    sb = b.runtime.get_datastore("d").get_channel("t")
    sb.insert_text(6, " path")
    b.flush()
    assert sa.get_text() == sb.get_text() == "native path"


def test_native_throughput_exceeds_python():
    """The array lane (one FFI call, numeric in/out, zero per-op Python
    objects — what the TPU sidecar consumes) must beat the Python
    per-op loop by >=5x at realistic quorum sizes. The object-building
    ``ticket_batch`` wrapper can't win — SequencedMessage construction
    dominates both sides — so the service plane feeds tensors, not
    dataclasses."""
    import time

    import numpy as np

    n_clients, n = 200, 20000
    py = DocumentSequencer("doc")
    nat = native.NativeSequencerCore("doc")
    names = [f"c{i}" for i in range(n_clients)]
    for s in (py, nat):
        for cid in names:
            s.client_join(ClientDetail(cid))
    base = py.sequence_number
    ops = [
        (names[i % n_clients],
         op(i // n_clients + 1, base))
        for i in range(n)
    ]

    t0 = time.perf_counter()
    for cid, o in ops:
        py.ticket(cid, o)
    t_py = time.perf_counter() - t0

    cids = np.array([nat.intern_id(cid) for cid, _ in ops], np.int64)
    csns = np.array([o.client_sequence_number for _, o in ops],
                    np.int64)
    refs = np.array([o.reference_sequence_number for _, o in ops],
                    np.int64)
    t0 = time.perf_counter()
    out_seq, out_msn, out_status = nat.ticket_batch_arrays(
        cids, csns, refs
    )
    t_nat = time.perf_counter() - t0
    print(f"python={n / t_py:.0f} ops/s native={n / t_nat:.0f} ops/s "
          f"speedup={t_py / t_nat:.1f}x")
    assert (out_status == 0).all()
    assert py.sequence_number == nat.sequence_number
    assert py.minimum_sequence_number == nat.minimum_sequence_number
    assert int(out_seq[-1]) == py.sequence_number
    assert int(out_msn[-1]) == py.minimum_sequence_number
    assert t_nat * 5 < t_py, (
        f"array lane only {t_py / t_nat:.1f}x vs Python"
    )


def test_ticket_batch_arrays_matches_scalar_oracle():
    """Differential: the array lane's (seq, msn, status) stream equals
    the Python oracle's op-for-op, including nack/duplicate statuses."""
    import numpy as np

    rng = random.Random(7)
    py = DocumentSequencer("doc")
    nat = native.NativeSequencerCore("doc")
    names = [f"c{i}" for i in range(6)]
    for s in (py, nat):
        for cid in names:
            s.client_join(ClientDetail(cid))
    csn_state = {cid: 0 for cid in names}
    ops = []
    for _ in range(400):
        cid = rng.choice(names)
        if rng.random() < 0.1:
            csn = csn_state[cid] + rng.choice([0, 2])  # dup or gap
        else:
            csn_state[cid] += 1
            csn = csn_state[cid]
        refseq = py.sequence_number - rng.choice([0, 0, 1])
        ops.append((cid, op(csn, max(0, refseq))))
        # tick the oracle as we go so refseq choices stay plausible
        py.ticket(cid, ops[-1][1])

    # replay the identical stream through both implementations fresh
    py2 = DocumentSequencer("doc")
    for cid in names:
        py2.client_join(ClientDetail(cid))
    expected = []
    for cid, o in ops:
        res = py2.ticket(cid, o)
        if res.message is not None:
            expected.append(
                (0, res.message.sequence_number,
                 res.message.minimum_sequence_number)
            )
        elif res.nack is None:
            expected.append((2, -1, -1))
        else:
            expected.append((-1, -1, -1))

    cids = np.array([nat.intern_id(cid) for cid, _ in ops], np.int64)
    csns = np.array([o.client_sequence_number for _, o in ops],
                    np.int64)
    refs = np.array([o.reference_sequence_number for _, o in ops],
                    np.int64)
    out_seq, out_msn, out_status = nat.ticket_batch_arrays(
        cids, csns, refs
    )
    for i, (status, seq, msn) in enumerate(expected):
        if status == 0:
            assert out_status[i] == 0
            assert out_seq[i] == seq
            assert out_msn[i] == msn
        elif status == 2:
            assert out_status[i] == 2
        else:
            assert out_status[i] not in (0, 2)


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_native_build_is_keyed_on_source(tmp_path, monkeypatch):
    """A library is found only under the hash of the source it was
    built from: after the source changes, the old build is never
    loaded — the new source is compiled, and the stale file removed."""
    import ctypes

    from fluidframework_tpu import native

    monkeypatch.setattr(native, "_build_status", {})
    src = tmp_path / "answer.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    first, err = native._compile(src, "_answer")
    assert err is None
    assert native.native_build_status() == {"_answer": "built"}
    assert ctypes.CDLL(str(first)).answer() == 1
    again, _ = native._compile(src, "_answer")
    assert again == first
    assert native.native_build_status() == {"_answer": "reused"}
    # an unkeyed library beside the source is never the one loaded
    (tmp_path / "_answer.so").write_bytes(first.read_bytes())
    src.write_text('extern "C" int answer() { return 2; }\n')
    rebuilt, err = native._compile(src, "_answer")
    assert err is None and rebuilt != first
    assert native.native_build_status() == {"_answer": "built"}
    assert ctypes.CDLL(str(rebuilt)).answer() == 2
    assert not first.exists()
