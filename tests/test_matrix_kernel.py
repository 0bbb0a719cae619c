"""SharedMatrix batched path vs the scalar model (BASELINE
config #3): two merge-kernel axes in one dispatch +
vectorized cell scatter must reproduce the converged to_lists() of the
live SharedMatrix replicas."""
import dataclasses
import random

import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.ops import fetch
from fluidframework_tpu.ops.matrix_bridge import (
    MatrixStream,
    apply_matrix_batch,
    extract_matrix,
)
from fluidframework_tpu.protocol.messages import MessageType
from fluidframework_tpu.service import LocalServer


def channel_stream(server, document_id, ds_id, ch_id):
    """Extract one channel's inner sequenced stream from the op log
    (the sidecar's envelope rule)."""
    out = []
    for msg in server.read_ops(document_id, 0):
        envelope = msg.contents if isinstance(msg.contents, dict) else {}
        if (
            msg.type == MessageType.OPERATION
            and envelope.get("kind", "op") == "op"
            and envelope.get("address") == ds_id
            and envelope.get("channel") == ch_id
        ):
            out.append(
                dataclasses.replace(msg, contents=envelope["contents"])
            )
        else:
            out.append(dataclasses.replace(
                msg, type=MessageType.NO_OP, contents=None,
                client_id=None,
            ))
    return out


def make_matrix_session(doc="m"):
    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    a = Container.load(factory.create_document_service(doc),
                       client_id="alice")
    b = Container.load(factory.create_document_service(doc),
                       client_id="bob")
    ma = a.runtime.create_datastore("d").create_channel("sharedmatrix", "m")
    a.flush()
    mb = b.runtime.get_datastore("d").get_channel("m")
    return server, a, b, ma, mb


def replay_kernel(server, doc="m"):
    ms = MatrixStream()
    for msg in channel_stream(server, doc, "d", "m"):
        ms.add_message(msg)
    table = apply_matrix_batch([ms], capacity=512)
    np_table = fetch(table)
    assert not np_table["overflow"].any()
    return extract_matrix(np_table, ms, 0)


def test_matrix_kernel_basic():
    server, a, b, ma, mb = make_matrix_session()
    ma.insert_rows(0, 3)
    ma.insert_cols(0, 2)
    a.flush()
    ma.set_cell(0, 0, "tl")
    ma.set_cell(2, 1, "br")
    a.flush()
    mb.set_cell(1, 1, "mid")
    b.flush()
    assert ma.to_lists() == mb.to_lists()
    assert replay_kernel(server) == ma.to_lists()


def test_matrix_kernel_concurrent_permutation_vs_cells():
    """Cells commute with concurrent permutation (handle stability)."""
    server, a, b, ma, mb = make_matrix_session()
    ma.insert_rows(0, 4)
    ma.insert_cols(0, 3)
    a.flush()
    for r in range(4):
        for c in range(3):
            ma.set_cell(r, c, f"{r}.{c}")
    a.flush()
    # concurrent: A removes row 1 while B writes into rows 1 and 2
    ma.remove_rows(1, 1)
    mb.set_cell(1, 0, "doomed")
    mb.set_cell(2, 0, "survives")
    a.flush()
    b.flush()
    assert ma.to_lists() == mb.to_lists()
    assert replay_kernel(server) == ma.to_lists()


def test_matrix_kernel_concurrent_row_inserts_tiebreak():
    server, a, b, ma, mb = make_matrix_session()
    ma.insert_rows(0, 2)
    ma.insert_cols(0, 1)
    a.flush()
    ma.insert_rows(0, 1)
    mb.insert_rows(0, 1)
    ma.set_cell(0, 0, "a-row")
    mb.set_cell(0, 0, "b-row")
    a.flush()
    b.flush()
    assert ma.to_lists() == mb.to_lists()
    assert replay_kernel(server) == ma.to_lists()


@pytest.mark.parametrize("seed", range(10))
def test_matrix_kernel_fuzz(seed):
    rng = random.Random(seed * 37 + 11)
    server, a, b, ma, mb = make_matrix_session()
    ma.insert_rows(0, 2)
    ma.insert_cols(0, 2)
    a.flush()
    clients = [(a, ma), (b, mb)]
    for step in range(60):
        c, m = clients[rng.randint(0, 1)]
        roll = rng.random()
        try:
            if roll < 0.2:
                m.insert_rows(rng.randint(0, m.row_count), rng.randint(1, 2))
            elif roll < 0.35:
                m.insert_cols(rng.randint(0, m.col_count), 1)
            elif roll < 0.45 and m.row_count > 1:
                m.remove_rows(rng.randint(0, m.row_count - 1), 1)
            elif roll < 0.5 and m.col_count > 1:
                m.remove_cols(rng.randint(0, m.col_count - 1), 1)
            elif m.row_count and m.col_count:
                m.set_cell(rng.randint(0, m.row_count - 1),
                           rng.randint(0, m.col_count - 1),
                           rng.randint(0, 999))
        except AssertionError:
            continue  # cell outside local view mid-churn
        if rng.random() < 0.5:
            c.flush()
    a.flush()
    b.flush()
    assert ma.to_lists() == mb.to_lists(), f"seed {seed} diverged"
    assert replay_kernel(server) == ma.to_lists(), f"seed {seed}"


def test_matrix_kernel_reconnect_resubmit_handles():
    """code-review r2: reconnect resubmission emits GroupOps and split
    inserts with handle=[alloc, base>0]; the device handle derivation
    must track both or cells miss after replay."""
    server, a, b, ma, mb = make_matrix_session()
    ma.insert_rows(0, 2)
    ma.insert_cols(0, 2)
    a.flush()
    a.disconnect()
    # offline: a run insert that will be split by b's concurrent edit
    ma.insert_rows(1, 3)
    ma.set_cell(2, 0, "offline")
    mb.insert_rows(0, 1)
    b.flush()
    a.connect()
    a.flush()
    b.flush()
    assert ma.to_lists() == mb.to_lists()
    assert replay_kernel(server) == ma.to_lists()


# ---- device cell path: sort + last-wins (matrix.ts:79 LWW) -----------

def _host_lww(streams):
    """Scalar LWW oracle: dict keyed by (row, col), window order."""
    out = []
    for s in streams:
        d = {}
        for rh, ch, v in zip(s.cell_rows, s.cell_cols, s.cell_vals):
            d[(rh, ch)] = v
        out.append(d)
    return out


@pytest.mark.parametrize("seed", range(6))
def test_cell_kernel_matches_host_lww(seed):
    import numpy as np

    from fluidframework_tpu.ops.matrix_cells import CellPack

    rng = random.Random(seed)
    streams = []
    for m in range(3):
        s = MatrixStream()
        n = rng.randint(0, 120)
        for _ in range(n):
            s.cell_rows.append(f"r{rng.randint(0, 15)}")
            s.cell_cols.append(f"c{rng.randint(0, 5)}")
            s.cell_vals.append(rng.randint(0, 10**6))
        streams.append(s)
    pack = CellPack(n_rows=16, n_cols=6)
    pack.pack(streams)
    grid = np.asarray(pack.apply())
    oracle = _host_lww(streams)
    for m, s in enumerate(streams):
        for (rh, ch), want in oracle[m].items():
            assert pack.lookup(grid, m, rh, ch) == want, (seed, m, rh, ch)
        # unwritten cells read None
        assert pack.lookup(grid, m, "r-none", "c0") is None
    # every grid entry that holds an index must be a winner
    for m in range(len(streams)):
        for r_h, r in pack.row_ids[m].items():
            for c_h, c in pack.col_ids[m].items():
                got = pack.lookup(grid, m, r_h, c_h)
                assert got == oracle[m].get((r_h, c_h))


def test_cell_kernel_empty_and_single():
    import numpy as np

    from fluidframework_tpu.ops.matrix_cells import CellPack

    empty = MatrixStream()
    one = MatrixStream()
    one.cell_rows.append("a:0")
    one.cell_cols.append("b:0")
    one.cell_vals.append("v")
    pack = CellPack(n_rows=4, n_cols=4)
    pack.pack([empty, one])
    grid = np.asarray(pack.apply())
    assert pack.lookup(grid, 0, "a:0", "b:0") is None
    assert pack.lookup(grid, 1, "a:0", "b:0") == "v"


def test_cell_kernel_window_segmentation():
    """Composite-key overflow splits the window into LWW-combined
    segments — exercised through the PRODUCTION CellPack.apply branch
    by shrinking the int32 budget — and must equal the single-kernel
    result."""
    import numpy as np

    from fluidframework_tpu.ops.matrix_cells import CellPack

    rng = random.Random(7)
    s = MatrixStream()
    for _ in range(50):
        s.cell_rows.append(f"r{rng.randint(0, 3)}")
        s.cell_cols.append(f"c{rng.randint(0, 3)}")
        s.cell_vals.append(rng.randint(0, 999))
    pack = CellPack(n_rows=4, n_cols=4)
    pack.pack([s])
    full = np.asarray(pack.apply())            # single-kernel path
    # budget 16*6 => max_n = 5 => ten ~5-op segments, real branch
    seg_grid = np.asarray(pack.apply(budget=4 * 4 * 6))
    assert np.array_equal(full, seg_grid)
    oracle = _host_lww([s])[0]
    for (rh, ch), want in oracle.items():
        assert pack.lookup(full, 0, rh, ch) == want
        assert pack.lookup(seg_grid, 0, rh, ch) == want
