"""Batched sequenced-path merge kernel.

Applies a totally-ordered window of insert/remove/annotate ops to
thousands of documents in one XLA dispatch — the vectorized replacement
for the reference's per-op B-tree walk (mergeTree.ts ``insertingWalk``
:1723, ``markRangeRemoved`` :1908, ``annotateRange`` :1864) and its
``PartialSequenceLengths`` incremental structure (partialLengths.ts:234).

Position resolution = visibility mask + exclusive prefix-sum + first-
true reduction (see merge_step.fused_step for the fused three-phase
algorithm and its equivalence argument to the reference's
``breakTie``/``insertingWalk`` semantics).

Within one document ops are sequentially dependent (an op may address
positions created by the previous one), so the op window is a sequential
loop; parallelism is across documents (the reference's Kafka-partition
axis, SURVEY §2.9 axis 1).

``apply_window`` runs the XLA executor (``apply_window_impl``): a
``lax.scan`` of the fused step over the window. It runs on any
backend and shards over a doc-axis mesh; every scan step streams the
whole table through HBM. It is the sidecar's whole route off a TPU,
and on a TPU it applies the concurrent suffix of every egwalker
window (ops/event_graph.py) and every window the pools dispatch on a
one-device mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .merge_step import (
    OPOFF_BOUND,
    batch_to_window,
    fused_step,
    state_to_table,
    table_to_state,
)
from .segment_table import NOT_REMOVED, PROP_CHANNELS, OpBatch, SegmentTable


# TPU scan unroll: fewer, larger scan steps amortize per-step launch
# overhead. 4 was chosen on an earlier shared-chip setup at window ~70
# and has not been re-measured on the v5e.
_TPU_UNROLL = 4


def apply_window_impl(table: SegmentTable, batch: OpBatch) -> SegmentTable:
    """XLA executor: scan the fused step over the [docs, window] batch.
    Pure/jittable; doc axis shards cleanly under shard_map.

    Capacity bound: the phase-1 op_off composite (j*OPOFF_BOUND +
    op_off) must fit int32 (merge_step.OPOFF_BOUND).

    unroll=4 on TPU: fewer, larger scan steps amortize per-step
    launch overhead (unroll 16 compiles much longer for a marginal
    gain). Kept at 1 elsewhere — CPU tests would only pay extra
    compile.
    """
    assert table.capacity * OPOFF_BOUND < 2**31, (
        f"capacity {table.capacity} overflows the op_off composite"
    )
    st = table_to_state(table)
    ops_wd = batch_to_window(batch)

    def step(carry, op):
        with jax.named_scope("scan_step"):
            return fused_step(carry, op), None

    unroll = _TPU_UNROLL if jax.default_backend() == "tpu" else 1
    st, _ = jax.lax.scan(step, st, ops_wd, unroll=unroll)
    return state_to_table(st, SegmentTable)


# NO donate_argnums on the PLAIN dispatch: donating the live input
# table serializes back-to-back windows (the next window's input IS
# the previous output, so the runtime must wait for the buffer
# release before enqueueing). Donation rides the PING-PONG form below
# instead.
_apply_window_xla = jax.jit(apply_window_impl)


def _pingpong_impl(dead: SegmentTable, table: SegmentTable,
                   batch: OpBatch) -> SegmentTable:
    # ``dead`` is donation fodder only: a table two dispatches old
    # whose buffers XLA may reuse for this window's output. It is
    # never read — donating the LIVE input would forbid keeping the
    # pre-dispatch snapshot the sidecar's O(window) regrow needs.
    del dead
    return apply_window_impl(table, batch)


_apply_window_pingpong = jax.jit(_pingpong_impl, donate_argnums=(0,))


def apply_window_pingpong(dead: SegmentTable, table: SegmentTable,
                          batch: OpBatch) -> SegmentTable:
    """Double-buffered dispatch: apply ``batch`` to ``table`` while
    DONATING ``dead`` (a retired same-shape table) as the output
    buffer. This re-enables donation safely for back-to-back windows:
    round N+1 donates the round N-1 snapshot, which is provably free
    by the time N+1's output materializes (round N's input depended on
    it), so no serialization — and ``table`` survives as the
    pre-dispatch snapshot for overflow regrow. The caller must drop
    every reference to ``dead`` (its buffers are consumed).

    On backends without donation support (CPU) this silently degrades
    to the plain dispatch — same results, no buffer reuse."""
    if jax.default_backend() == "cpu":
        # CPU ignores donation with a per-call warning; skip the noise
        return _apply_window_xla(table, batch)
    return _apply_window_pingpong(dead, table, batch)


def compiled_window():
    """PUBLIC handle to the exact jit object ``apply_window``
    dispatches (for AOT compiles and cost analysis, as chip_smoke.py's
    compile phase does); keeps callers off the private alias."""
    return _apply_window_xla


def apply_window(table: SegmentTable, batch: OpBatch) -> SegmentTable:
    """Apply a [docs, window] op batch: the XLA scan, on every
    backend."""
    return _apply_window_xla(table, batch)


from functools import partial


@partial(jax.jit, static_argnums=(1,))
def pad_capacity(table: SegmentTable, new_capacity: int) -> SegmentTable:
    """Widen the slot slab without touching content: live slots and
    doc scalars carry over, new slots are garbage beyond ``count``.
    This is what makes regrow O(window): pad the pre-dispatch snapshot
    and re-apply just the failed window (the snapshot is a free handle
    — JAX arrays are immutable)."""
    grow = new_capacity - table.capacity
    assert grow > 0

    def pad(arr, fill=0):
        widths = [(0, 0), (0, grow)] + [(0, 0)] * (arr.ndim - 2)
        return jnp.pad(arr, widths, constant_values=fill)

    return table._replace(
        length=pad(table.length),
        seq=pad(table.seq),
        client=pad(table.client),
        removed_seq=pad(table.removed_seq, NOT_REMOVED),
        removers=pad(table.removers),
        op_id=pad(table.op_id),
        op_off=pad(table.op_off),
        is_marker=pad(table.is_marker),
        prop=pad(table.prop),
        overflow=jnp.zeros_like(table.overflow),
    )


def _shift_rows(x, s, right: bool):
    """x[:, i - s] (``right``) or x[:, i + s] along the row axis,
    zero-filled, for a traced shift 0 <= s <= C: a dynamic slice of the
    zero-padded plane, no gather."""
    C = x.shape[1]
    if right:
        return lax.dynamic_slice_in_dim(
            jnp.pad(x, ((0, 0), (C, 0))), C - s, C, axis=1)
    return lax.dynamic_slice_in_dim(
        jnp.pad(x, ((0, 0), (0, C))), s, C, axis=1)


def _partition_rows(planes: list, left_amt, right_amt):
    """Two log shifters over the [D, C] ``planes``: one moves row j
    left by ``left_amt[:, j]``, the other right by ``right_amt[:, j]``,
    least significant bit first; a row with amount 0 stays put.

    Stage b moves the rows whose amount has bit b set by 2^b, carrying
    their amount along; the slot a row leaves keeps stale values with
    amount 0, so it never moves again, and an arriving row overwrites
    it. This needs compaction's amounts: of two moving rows j1 < j2,
    the one shifted toward the other has the larger amount, by less
    than j2 - j1 (left, z(j2) - z(j1) counts non-kept rows strictly
    between them; right, r(j1) - r(j2) kept ones). After the stages
    below b a row has moved by its amount mod 2^b, and for a >= a'
    (a mod 2^b) - (a' mod 2^b) <= a - a' < j2 - j1: the gap between
    the two rows never closes, so moving rows keep their order and no
    arrival lands on a row that stays. Most significant bit first, as
    ``event_graph._merge_rows`` runs, does not hold here: two rows can
    cross.

    The stages run as a loop with a traced shift, not unrolled: device
    code is device memory for as long as the program is loaded, and on
    a TPU v5e the unrolled stages compiled to 18 MB of it at 1,024 x
    1,024, the loop to 8 MB."""

    def stage(b, carry):
        left, right, left_amt, right_amt = carry
        step = jnp.left_shift(jnp.int32(1), b)
        moved = []
        for cols, amt, to_right in ((left, left_amt, False),
                                    (right, right_amt, True)):
            go = (amt & step) != 0
            arrive = _shift_rows(go, step, to_right)
            cols = [jnp.where(arrive, _shift_rows(c, step, to_right), c)
                    for c in cols]
            amt = jnp.where(arrive, _shift_rows(amt, step, to_right),
                            jnp.where(go, 0, amt))
            moved.append((cols, amt))
        (left, left_amt), (right, right_amt) = moved
        return left, right, left_amt, right_amt

    stages = (planes[0].shape[1] - 1).bit_length()
    left, right, _, _ = lax.fori_loop(
        0, stages, stage, (planes, planes, left_amt, right_amt))
    return left, right


@jax.jit
def compact(table: SegmentTable) -> SegmentTable:
    """Zamboni kernel (mergeTree.ts:800): drop tombstones at/below the
    collab window, compacting live slots to the slab head.

    The stable partition of rows into kept ones first and the rest
    after, each group in its old order (what ``argsort(~keep,
    stable=True)`` gives, the tail past ``count`` included), with
    neither a sort nor a row-axis gather: a kept row moves left by the
    non-kept rows before it, any other row right by the kept rows after
    it, each group through a log shifter of lane shifts and
    selects (``_partition_rows``), and the two results join at the
    new count."""
    with jax.named_scope("compact"):
        j = jnp.arange(table.capacity, dtype=jnp.int32)[None, :]
        alive = j < table.count[:, None]
        drop = alive & (table.removed_seq != NOT_REMOVED) & (
            table.removed_seq <= table.min_seq[:, None]
        )
        keep = alive & ~drop
        kept_through = jnp.cumsum(keep, axis=1, dtype=jnp.int32)
        count = kept_through[:, -1]
        names = ("length", "seq", "client", "removed_seq", "removers",
                 "op_id", "op_off", "is_marker")
        left, right = _partition_rows(
            [getattr(table, f) for f in names] + [
                table.prop[:, :, c] for c in range(PROP_CHANNELS)],
            jnp.where(keep, j + 1 - kept_through, 0),
            jnp.where(keep, 0, count[:, None] - kept_through))
        head = j < count[:, None]
        out = [jnp.where(head, a, b) for a, b in zip(left, right)]
        n = len(names)
        return table._replace(
            **dict(zip(names, out[:n])),
            prop=jnp.stack(out[n:], axis=-1),
            count=count,
        )
