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

Two executors share the identical step function:

- XLA (`apply_window_impl`): ``lax.scan`` over the window. Runs on any
  backend, shards over a doc-axis mesh, and is the reference for the
  Pallas path. HBM-bound: every scan step streams the whole table.
- Pallas TPU (`pallas_merge.apply_window_pallas`): one kernel per doc
  block with the segment table VMEM-RESIDENT across the entire window —
  HBM traffic drops from O(window × table) to O(table + ops).

``apply_window`` runs the XLA scan by default everywhere; the Pallas
kernel is OPT-IN via FFTPU_PALLAS=1 on a TPU backend (correct and
bit-identical, but Mosaic's current lane-reduce codegen loses to the
pipelined scan on throughput — see _use_pallas).

STATUS of the Pallas route (round 4): the claim that it is "the route
to the HBM-optimal single-launch kernel" is RETIRED. The chunked
executor (ops/merge_chunk.py) now provides launch and HBM
amortization over K ops per step through plain XLA, without
depending on Mosaic codegen maturing; the Pallas kernel remains as a
correctness-proven alternative backend for the single-op step only.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .merge_step import (
    OPOFF_BOUND,
    batch_to_window,
    fused_step,
    state_to_table,
    table_to_state,
)
from .segment_table import NOT_REMOVED, OpBatch, SegmentTable


def _env_unroll() -> int:
    """TPU scan unroll, read ONCE at import (jit caches per shape, so
    later env changes would be silently ignored anyway). 4 was chosen
    on an earlier shared-chip setup at window ~70 and has not been
    re-measured on the v5e chip."""
    try:
        return max(1, int(os.environ.get("FFTPU_UNROLL", "4")))
    except ValueError:
        return 4


_TPU_UNROLL = _env_unroll()


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
    dispatches (for AOT cost analysis / instrumentation — bench's
    HBM accounting); keeps callers off the private alias."""
    return _apply_window_xla


def _use_pallas(table: SegmentTable) -> bool:
    """Opt-in (FFTPU_PALLAS=1). The Mosaic kernel lost to the XLA scan
    on throughput on an earlier chip setup (Mosaic's lane-reduce
    codegen makes the VMEM-resident body VPU-bound); it has not been
    timed on the v5e. An opt-in it cannot honour raises: serving the
    scan in its place would hide which kernel ran."""
    if os.environ.get("FFTPU_PALLAS") != "1":
        return False
    if table.capacity % 128 != 0:
        raise ValueError(
            f"FFTPU_PALLAS=1: capacity {table.capacity} is not a "
            "multiple of 128, which the Pallas kernel needs"
        )
    backend = jax.default_backend()
    if backend != "tpu":
        raise ValueError(
            f"FFTPU_PALLAS=1: the Pallas kernel needs a TPU backend, "
            f"not {backend!r}"
        )
    return True


def apply_window(table: SegmentTable, batch: OpBatch) -> SegmentTable:
    """Apply a [docs, window] op batch. XLA scan by default; the
    VMEM-resident Pallas kernel when FFTPU_PALLAS=1 on TPU. Both run
    the same fused step and agree bit-for-bit."""
    if _use_pallas(table):
        from .pallas_merge import apply_window_pallas

        return apply_window_pallas(table, batch)
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


@jax.jit
def compact(table: SegmentTable) -> SegmentTable:
    """Zamboni kernel (mergeTree.ts:800): drop tombstones at/below the
    collab window, compacting live slots to the slab head."""

    def one(tab: SegmentTable) -> SegmentTable:
        j = jnp.arange(tab.capacity, dtype=jnp.int32)
        alive = j < tab.count
        drop = alive & (tab.removed_seq != NOT_REMOVED) & (
            tab.removed_seq <= tab.min_seq
        )
        keep = alive & ~drop
        src = jnp.argsort(~keep, stable=True)
        return tab._replace(
            length=tab.length[src],
            seq=tab.seq[src],
            client=tab.client[src],
            removed_seq=tab.removed_seq[src],
            removers=tab.removers[src],
            op_id=tab.op_id[src],
            op_off=tab.op_off[src],
            is_marker=tab.is_marker[src],
            prop=tab.prop[src],
            count=keep.sum().astype(jnp.int32),
        )

    with jax.named_scope("compact"):
        return jax.vmap(one)(table)
