"""C++ scalar merge replayer vs batched kernel vs Python oracle.

The replayer (native/merge_replay.cpp) is a compiled baseline; its
semantics must match the kernel bit-for-bit on the sequenced path.
"""
import pytest

from fluidframework_tpu.native import load_merge_replay, merge_replay_error
from fluidframework_tpu.native.replay_baseline import (
    encode_ops_array,
    replay,
    table_checksum,
)
from fluidframework_tpu.ops import (
    apply_window,
    build_batch,
    encode_stream,
    fetch,
    make_table,
)
from fluidframework_tpu.testing import FuzzConfig, record_op_stream

pytestmark = pytest.mark.skipif(
    load_merge_replay() is None,
    reason=f"native toolchain unavailable: {merge_replay_error()}",
)


def kernel_checksum(stream, capacity=512):
    enc = encode_stream(stream)
    batch = build_batch([enc])
    table = apply_window(make_table(1, capacity), batch)
    np_table = fetch(table)
    assert not np_table["overflow"].any()
    return table_checksum(np_table, 0)


@pytest.mark.parametrize("seed", range(12))
def test_cpp_replay_matches_kernel(seed):
    text, stream = record_op_stream(FuzzConfig(
        n_clients=3, n_steps=100, seed=seed * 17 + 3,
        remove_weight=0.3, annotate_weight=0.15,
    ))
    enc = encode_stream(stream)
    got = replay(encode_ops_array(enc))
    assert got is not None
    cpp_checksum, live, _dt = got
    assert cpp_checksum == kernel_checksum(stream)
    # live char count = converged text length (+1 per marker, but the
    # fuzz workload here is text-only)
    assert live == len(text)


def test_cpp_replay_reps_deterministic():
    _, stream = record_op_stream(FuzzConfig(
        n_clients=2, n_steps=60, seed=99, remove_weight=0.25,
    ))
    enc = encode_ops_array(encode_stream(stream))
    one = replay(enc, reps=1)
    many = replay(enc, reps=5)
    assert one[0] == many[0] and one[1] == many[1]
