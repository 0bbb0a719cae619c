"""Pallas TPU merge kernel: bit-equality vs the XLA scan executor.

Both executors run the identical ``merge_step.fused_step``; this suite
pins the Pallas grid/blocking/aliasing plumbing (interpret mode on CPU;
the same comparison runs against the real Mosaic lowering on TPU via
tools/tpu_evidence.py). The XLA executor itself is differential-tested
against the scalar oracle in test_merge_kernel.py, so transitively the
Pallas path inherits the reference semantics (mergeTree.ts:1705,1723).
"""
import numpy as np
import pytest

from fluidframework_tpu.ops import (
    build_batch,
    encode_stream,
    fetch,
    make_table,
)
from fluidframework_tpu.ops.merge_kernel import apply_window_impl
from fluidframework_tpu.testing import FuzzConfig, record_op_stream


def _fuzz_batch(docs, seed0, steps=40, clients=3):
    streams = []
    for d in range(docs):
        _, stream = record_op_stream(FuzzConfig(
            n_clients=clients, n_steps=steps, seed=seed0 + d,
            insert_weight=0.5, remove_weight=0.25, annotate_weight=0.1,
            process_weight=0.15,
        ))
        streams.append(encode_stream(stream))
    return build_batch(streams)


def _pallas_interpret(table, batch):
    from fluidframework_tpu.ops import pallas_merge as pm
    from fluidframework_tpu.ops.merge_step import (
        STATE_FIELDS,
        state_to_table,
        table_to_state,
    )
    from fluidframework_tpu.ops.segment_table import SegmentTable

    from fluidframework_tpu.ops.merge_step import OP_COLS

    ops = {f: getattr(batch, f) for f in OP_COLS}
    out = pm._pallas_call(
        table_to_state(table), ops, interpret=True
    )
    return state_to_table(out, SegmentTable)


@pytest.mark.parametrize("seed", [
    pytest.param(0, marks=pytest.mark.slow), 7,
    pytest.param(99, marks=pytest.mark.slow),
])
def test_pallas_interpret_matches_xla(seed):
    docs, cap = 4, 128
    batch = _fuzz_batch(docs, seed0=1000 + seed * 10, steps=30)
    ref = apply_window_impl(make_table(docs, cap), batch)
    got = _pallas_interpret(make_table(docs, cap), batch)
    ref_np, got_np = fetch(ref), fetch(got)
    for f in ref_np:
        np.testing.assert_array_equal(
            got_np[f], ref_np[f], err_msg=f"field {f} seed {seed}"
        )


def test_pallas_interpret_doc_padding_path():
    """The wrapper pads the doc axis to a block multiple; padded docs
    must be inert (NOOP ops only) and real docs identical after
    unpadding. Runs the same pad/unpad code as the TPU path, with the
    kernel itself in interpret mode."""
    import jax.numpy as jnp

    from fluidframework_tpu.ops import pallas_merge as pm
    from fluidframework_tpu.ops.merge_step import (
        OP_COLS,
        state_to_table,
        table_to_state,
    )
    from fluidframework_tpu.ops.segment_table import (
        KIND_NOOP,
        NOT_REMOVED,
        SegmentTable,
    )

    docs, cap = 5, 128  # not a multiple of any block size
    batch = _fuzz_batch(docs, seed0=4321, steps=25)
    ref = apply_window_impl(make_table(docs, cap), batch)

    # replicate apply_window_pallas's padding, run interpret, unpad
    table = make_table(docs, cap)
    block = pm._doc_block(cap, docs)
    padded = max(block, -(-docs // block) * block)
    assert padded > docs  # the padding path is actually exercised
    pad = padded - docs
    state = {
        f: jnp.pad(
            a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
            constant_values=NOT_REMOVED if f == "removed_seq" else 0,
        )
        for f, a in table_to_state(table).items()
    }
    ops = {
        f: jnp.pad(
            getattr(batch, f), [(0, pad), (0, 0)],
            constant_values=KIND_NOOP if f == "kind" else 0,
        )
        for f in OP_COLS
    }
    out = pm._pallas_call(state, ops, interpret=True)
    # padded docs stayed empty
    for d in range(docs, padded):
        assert int(out["count"][d, 0]) == 0
    got = state_to_table(
        {f: a[:docs] for f, a in out.items()}, SegmentTable
    )
    ref_np, got_np = fetch(ref), fetch(got)
    for f in ref_np:
        np.testing.assert_array_equal(
            got_np[f], ref_np[f], err_msg=f"field {f}"
        )


@pytest.mark.parametrize("capacity", [64, 128])
def test_pallas_opt_in_it_cannot_serve_raises(monkeypatch, capacity):
    """FFTPU_PALLAS=1 with a capacity off the 128 grid, or on a backend
    without Mosaic, fails loudly instead of quietly taking the scan."""
    from fluidframework_tpu.ops.merge_kernel import apply_window

    monkeypatch.setenv("FFTPU_PALLAS", "1")
    batch = _fuzz_batch(2, 7, steps=6)
    with pytest.raises(ValueError, match="FFTPU_PALLAS=1"):
        apply_window(make_table(2, capacity), batch)
