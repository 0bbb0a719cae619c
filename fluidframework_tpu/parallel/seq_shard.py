"""Sequence-axis sharding — long documents split ACROSS devices.

SURVEY §5.7: the reference handles long documents with B-tree blocking
(mergeTreeNodes.ts:373 MaxNodesInBlock=8), O(log n) positional queries
via PartialSequenceLengths (partialLengths.ts:234), and chunked
snapshots. The TPU-native equivalent is sharding the SEGMENT axis of a
document's slot slab over the mesh: each device holds a contiguous
block of slots, and the merge step's axis-global operations become
collectives riding ICI:

- exclusive prefix sum  -> local cumsum + all_gather of shard totals
  (the scan-collective form of PartialSequenceLengths);
- first-true / point lookups -> local reduce + pmin / psum;
- the restructure shift -> ppermute boundary exchange with the left
  neighbor (the "ring-style neighbor exchange only needed at shard
  boundaries" SURVEY §5.7 calls for).

``fused_step`` itself is unchanged — the collectives slot in through
its AxisPrims seam (ops/merge_step.py), so the sequence-sharded path
is bit-identical to the single-device executor by construction (the
differential test pins it: tests/test_seq_shard.py).

Composes with document sharding: the mesh may be 2-D (docs, seq), in
which case collectives reduce only over the seq axis and doc shards
stay independent lanes (SURVEY §2.9 axis 1 x §5.7).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.merge_step import (
    AxisPrims,
    DOC_FIELDS,
    batch_to_window,
    fused_step,
    state_to_table,
    table_to_state,
)
from ..ops.segment_table import OpBatch, SegmentTable

SEQ_AXIS = "seq"


def make_seq_mesh(devices: Optional[Sequence[jax.Device]] = None,
                  doc_shards: int = 1,
                  doc_axis: str = "docs") -> Mesh:
    """A (docs, seq) mesh: ``doc_shards`` independent document lanes,
    remaining devices split each document's segment axis."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % doc_shards:
        raise ValueError(f"{n} devices not divisible by {doc_shards}")
    arr = np.array(devices).reshape(doc_shards, n // doc_shards)
    return Mesh(arr, (doc_axis, SEQ_AXIS))


def seq_prims(axis: str = SEQ_AXIS) -> AxisPrims:
    """Collective AxisPrims for a shard_map body whose last (slot) axis
    is sharded on ``axis``."""

    def iota_j(D, C):
        base = lax.axis_index(axis).astype(jnp.int32) * C
        return base + lax.broadcasted_iota(jnp.int32, (D, C), 1)

    def excl_cumsum(x):
        # local scan + exclusive scan over shard totals: the collective
        # form of PartialSequenceLengths' prefix structure
        incl = jnp.cumsum(x, axis=-1)
        totals = lax.all_gather(incl[..., -1], axis)      # [n, D]
        i = lax.axis_index(axis)
        n = lax.axis_size(axis)
        k = lax.broadcasted_iota(jnp.int32, (n,), 0)
        offset = jnp.sum(
            jnp.where((k < i)[:, None], totals, 0), axis=0
        )[..., None]
        return incl - x + offset

    def shift_right(arr, k: int):
        # boundary exchange: my left neighbor's last k slots become my
        # first k (shard 0 zero-fills — ppermute drops non-targets)
        n = lax.axis_size(axis)
        recv = lax.ppermute(
            arr[..., arr.shape[-1] - k:], axis,
            [(s, s + 1) for s in range(n - 1)],
        )
        return jnp.concatenate([recv, arr[..., :-k]], axis=-1)

    def shift_right_many(arrs, k: int):
        # one boundary exchange for the whole slot-field family: stack
        # every field's k-column tail into a single ppermute payload
        # (32-bit fields bitcast to int32), then unstack — the per-op
        # collective count drops from O(fields) to 1 per shift distance
        n = lax.axis_size(axis)
        tails = []
        for a in arrs:
            t = a[..., a.shape[-1] - k:]
            if t.dtype != jnp.int32:
                t = lax.bitcast_convert_type(t, jnp.int32)
            tails.append(t)
        recv = lax.ppermute(
            jnp.stack(tails), axis, [(s, s + 1) for s in range(n - 1)]
        )
        out = []
        for i, a in enumerate(arrs):
            r = recv[i]
            if a.dtype != jnp.int32:
                r = lax.bitcast_convert_type(r, a.dtype)
            out.append(jnp.concatenate([r, a[..., :-k]], axis=-1))
        return out

    def first_true(mask, j, default):
        loc = jnp.min(jnp.where(mask, j, default), axis=-1,
                      keepdims=True)
        return lax.pmin(loc, axis)

    def at(arr, idx, j):
        loc = jnp.sum(jnp.where(j == idx, arr, 0), axis=-1,
                      keepdims=True)
        return lax.psum(loc, axis)

    def min_where(mask, arr, default):
        # masked min is shard-local then pmin — the collective form of
        # "value at the first masked slot" for monotone arrays (the
        # cross-shard monotonicity holds because excl_cumsum above adds
        # each shard's global offset)
        loc = jnp.min(jnp.where(mask, arr, default), axis=-1,
                      keepdims=True)
        return lax.pmin(loc, axis)

    def total(vlen, incl):
        return lax.psum(
            jnp.sum(vlen, axis=-1, keepdims=True), axis
        )

    def global_capacity(C):
        return C * lax.axis_size(axis)

    return AxisPrims(
        iota_j=iota_j, excl_cumsum=excl_cumsum, shift_right=shift_right,
        shift_right_many=shift_right_many,
        first_true=first_true, at=at, min_where=min_where, total=total,
        global_capacity=global_capacity,
    )


def _window_body(axis: str):
    prims = seq_prims(axis)

    def run(st: dict, ops: dict) -> dict:
        def step(carry, op):
            return fused_step(carry, op, prims=prims), None

        st, _ = lax.scan(step, st, ops)
        return st

    return run


_compiled_cache: dict = {}


def _compiled_window(mesh: Mesh, seq_axis: str,
                     doc_axis: Optional[str], field_names: tuple):
    """Cache the jitted shard_map program per (mesh, axes): jit caching
    keys on function identity, so rebuilding it per call would
    recompile the whole window scan on every dispatch (the XLA-path
    analogue is the module-scope _apply_window_xla)."""
    key = (mesh, seq_axis, doc_axis, field_names)
    if key not in _compiled_cache:
        slot_spec = P(doc_axis, seq_axis)
        doc_spec = P(doc_axis, None)
        op_spec = P(None, doc_axis, None)
        state_specs = {
            f: (doc_spec if f in DOC_FIELDS else slot_spec)
            for f in field_names
        }
        run = shard_map(
            _window_body(seq_axis), mesh=mesh,
            in_specs=(state_specs, op_spec), out_specs=state_specs,
            check_vma=False,
        )
        _compiled_cache[key] = jax.jit(run)
    return _compiled_cache[key]


def apply_window_seq_sharded(
    table: SegmentTable, batch: OpBatch, mesh: Mesh,
    seq_axis: str = SEQ_AXIS, doc_axis: Optional[str] = None,
) -> SegmentTable:
    """Apply a [docs, window] op batch with each document's slot slab
    sharded over ``seq_axis`` (and optionally docs over ``doc_axis``).

    Capacity must divide by the seq-axis size, and each shard must hold
    at least 2 slots (the restructure shifts by up to 2, and the
    boundary exchange only reaches one neighbor). Per-doc scalars
    (count/min_seq/overflow) are replicated over the seq axis and every
    shard derives identical updates (all decision inputs are globally
    reduced), so no post-hoc reconciliation is needed.
    """
    if doc_axis is None and len(mesh.axis_names) > 1:
        doc_axis = next(a for a in mesh.axis_names if a != seq_axis)
    n_seq = mesh.shape[seq_axis]
    if table.capacity % n_seq:
        raise ValueError(
            f"capacity {table.capacity} not divisible by seq axis "
            f"{n_seq}"
        )
    if table.capacity // n_seq < 2:
        raise ValueError(
            f"seq shard width {table.capacity // n_seq} < 2: the "
            f"two-slot restructure shift would cross more than one "
            f"shard boundary"
        )
    # iota_j is GLOBAL under seq sharding, so the op_off composite in
    # fused_step spans global_capacity * OPOFF_BOUND — it must fit
    # int32 or the masked min silently picks wrapped-negative entries
    from ..ops.segment_table import OPOFF_BOUND

    if table.capacity * OPOFF_BOUND >= 2**31:
        raise ValueError(
            f"global capacity {table.capacity} overflows the op_off "
            f"composite (max {(2**31 - 1) // OPOFF_BOUND})"
        )

    st = table_to_state(table)
    ops_wd = batch_to_window(batch)
    run = _compiled_window(
        mesh, seq_axis, doc_axis, tuple(sorted(st))
    )
    st = run(st, ops_wd)
    return state_to_table(st, SegmentTable)
