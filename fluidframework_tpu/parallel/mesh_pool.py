"""Mesh-sharded document pool — one logical pool across the full mesh.

SURVEY §2.9: the reference's service plane scales by partitioning
documents across workers (Kafka partitions). The TPU-native equivalent
is the DOC axis of the pooled segment table sharded over a
``jax.sharding.Mesh`` (``NamedSharding`` placement, ``shard_map``
dispatch), so pool capacity scales with the mesh, not with one chip's
HBM — the "millions of users" unlock of ROADMAP item 1. This
complements the SEQUENCE-sharded pool (service/tpu_sidecar.py's
``SeqShardedPool``, SURVEY §5.7): that one splits a single long
document's slot axis across devices; this one spreads MANY pooled
documents across shards. ``select_pool`` in the sidecar is the one
route-selection point between them.

Shape of the thing:

- ONE global table ``[n_shards * rows_per_shard, capacity]`` placed
  with ``NamedSharding(mesh, P(doc_axis))``; each shard owns a
  contiguous block of rows (shard ``s`` holds global rows
  ``[s*R, (s+1)*R)``).
- Dispatch is a ``shard_map`` over the doc axis whose body is the
  same ``fused_step`` scan every executor shares — documents are
  independent lanes, so the body needs NO collectives and the sharded
  dispatch is bit-identical to the single-shard pool by construction
  (the route-parity differential pins it: tests/test_mesh_pool.py).
- Each shard owns its own ``BucketLadder`` occupancy bookkeeping
  (member list, heat); admissions land on the least-occupied shard,
  and the shared pow2 row bucket grows only when a shard outgrows it.
- Per-member STREAM WATERMARKS (``applied_upto``) make incremental
  dispatch exactly-once across rebuilds — the identical contract (and
  field names) as ``SeqShardedPool``, so the sidecar drives either
  tier through one interface.
- A heat tracker (per-member EWMA of dispatched tail depth) drives
  LIVE MIGRATION of hot documents between shards, only ever at the
  settle boundary (``dispatch_pending`` runs inside the sidecar's
  ``_settle`` — the one sync point the dispatch-loop lint permits),
  only after the round's tails are applied, and only when no overflow
  is pending (recovery first). A migration is a row-permutation
  gather (``ops/shard_moves.py``) whose source table is DONATED — the
  op-ordered handoff of arXiv 1007.5093: with every watermark at its
  stream head and nothing in flight, moving a row commutes with the
  op order, so a migrated run serves bit-exactly what the
  never-migrated pool serves.

Rows not currently owned by a member are GARBAGE (a migration's
vacated row keeps a stale copy): count/overflow/text are only ever
read through ``row_of``, and every rebuild replaces the table.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import metrics as obs_metrics
from ..obs.heat import HeatLedger
from ..obs.trace import stamp as _trace_stamp
from ..ops.bucket_ladder import BucketLadder
from ..ops.host_bridge import coalesce_noops, pack_rows, replay_chunked
from ..ops.merge_kernel import apply_window, compact
from ..ops.merge_step import (
    batch_to_window,
    fused_step,
    state_to_table,
    table_to_state,
)
from ..ops.segment_table import (
    KIND_NOOP,
    OPOFF_BOUND,
    OpBatch,
    SegmentTable,
    make_table,
)
from ..ops.shard_moves import migrate_rows
from ..qos.faults import KIND_DEFER, PLANE as _CHAOS
from .mesh import DOC_AXIS

# chaos seams (docs/ROBUSTNESS.md), shared by NAME with the seq tier
# (tpu_sidecar registers the same sites): a deferred pool dispatch
# leaves tails past the watermark for the next settle; a deferred
# migration just skips one opportunistic move — both bit-exact by
# construction, which is exactly what the convergence differential
# pins
_SITE_POOL_DISPATCH = _CHAOS.site("sidecar.pool_dispatch", (KIND_DEFER,))
_SITE_POOL_MIGRATE = _CHAOS.site("sidecar.pool_migrate", (KIND_DEFER,))

# Registry families (process aggregates across every pool instance;
# exact per-instance counts stay on the owning object — tests read
# pool.migration_count etc.). Everything bumped from dispatch_pending
# is host-side only: it runs inside the sidecar's _settle boundary,
# where the overflow read already synced.
_M_MEMBERS = obs_metrics.REGISTRY.gauge(
    "mesh_pool_members", "pooled documents per shard",
    labelnames=("shard",))
_M_WATERMARK = obs_metrics.REGISTRY.gauge(
    "mesh_pool_watermark_ops", "sum of member stream watermarks")
_M_DISPATCH = obs_metrics.REGISTRY.counter(
    "mesh_pool_dispatches_total", "incremental mesh-pool dispatches")
_M_DEPTH = obs_metrics.REGISTRY.gauge(
    "mesh_pool_dispatch_depth", "ops in the last mesh-pool dispatch")
_M_MIGRATIONS = obs_metrics.REGISTRY.counter(
    "mesh_pool_migrations_total",
    "hot documents moved between shards at settle boundaries")
_M_IMBALANCE = obs_metrics.REGISTRY.gauge(
    "mesh_pool_shard_imbalance",
    "hottest-shard heat over mean shard heat (1.0 = balanced)")
_M_POOL_FAULTS = obs_metrics.REGISTRY.counter(
    "pool_faults_total",
    "pool operations deferred or retried under a transient fault "
    "(shared by NAME across the seq and mesh tiers, like the "
    "sidecar.pool_* chaos sites)", labelnames=("tier", "op"))


# ---------------------------------------------------------------------------
# the shard_map dispatch program


def _window_body():
    def run(st: dict, ops: dict) -> dict:
        def step(carry, op):
            # default (local) AxisPrims: documents never read across
            # the doc axis, so the sharded body IS the single-device
            # scan — bit-identical placement-independence for free
            return fused_step(carry, op), None

        st, _ = lax.scan(step, st, ops)
        return st

    return run


_compiled_cache: dict = {}


def _compiled_window(mesh: Mesh, doc_axis: str, field_names: tuple):
    """Cache the jitted shard_map program per (mesh, axis): jit caches
    on function identity, so rebuilding per call would recompile the
    window scan on every dispatch (same recipe as seq_shard's)."""
    key = (mesh, doc_axis, field_names)
    if key not in _compiled_cache:
        state_specs = {f: P(doc_axis, None) for f in field_names}
        op_spec = P(None, doc_axis, None)
        run = shard_map(
            _window_body(), mesh=mesh,
            in_specs=(state_specs, op_spec), out_specs=state_specs,
            check_vma=False,
        )
        _compiled_cache[key] = jax.jit(run)
    return _compiled_cache[key]


def apply_window_mesh_sharded(
    table: SegmentTable, batch: OpBatch, mesh: Mesh,
    doc_axis: str = DOC_AXIS,
) -> SegmentTable:
    """Apply a [docs, window] op batch with the DOC axis sharded over
    ``doc_axis``. Row count must divide by the axis size; capacity is
    per-shard-local (no cross-doc collectives), so the op_off
    composite bound is the single-device one."""
    n = mesh.shape[doc_axis]
    if table.docs % n:
        raise ValueError(
            f"{table.docs} pool rows not divisible by doc axis {n}"
        )
    if table.capacity * OPOFF_BOUND >= 2**31:
        raise ValueError(
            f"capacity {table.capacity} overflows the op_off composite"
        )
    st = table_to_state(table)
    ops_wd = batch_to_window(batch)
    run = _compiled_window(mesh, doc_axis, tuple(sorted(st)))
    st = run(st, ops_wd)
    return state_to_table(st, SegmentTable)


# ---------------------------------------------------------------------------
# the pool tier


class MeshShardedPool:
    """Doc-sharded pool tier: documents that outgrow the primary slab
    ladder spread across the mesh's doc shards and stay on the device
    path (host eviction remains the last resort, for documents that
    exceed even the pooled per-doc capacity or are
    tensor-inexpressible).

    Drives through the same interface as ``SeqShardedPool`` (admit /
    remove / rebuild / dispatch_pending / prewarm / overflowed_slots /
    fetch, plus ``row_of``/``applied_upto``/``members``), so
    ``TpuMergeSidecar`` route-selects between the two tiers without
    caring which one it holds (``select_pool``). Like that pool it
    runs the per-op scan whatever the sidecar's route: the shard_map
    window body on a multi-shard mesh, the plain ``apply_window`` on
    a one-shard mesh."""

    def __init__(self, mesh: Mesh, per_doc_capacity: int,
                 doc_axis: str = DOC_AXIS,
                 heat_decay: float = 0.5,
                 timeline=None,
                 heat: Optional[HeatLedger] = None):
        if doc_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh pool needs a {doc_axis!r} mesh axis "
                f"(got {mesh.axis_names})"
            )
        for axis in mesh.axis_names:
            if axis != doc_axis and mesh.shape[axis] != 1:
                raise ValueError(
                    f"mesh pool shards documents only: axis {axis!r} "
                    f"has size {mesh.shape[axis]} (slot-axis sharding "
                    "is SeqShardedPool's job)"
                )
        if per_doc_capacity < 16 or \
                per_doc_capacity * OPOFF_BOUND >= 2**31:
            raise ValueError(
                f"pool capacity {per_doc_capacity} invalid (needs "
                f">= 16 and * OPOFF_BOUND to fit int32)"
            )
        self.mesh = mesh
        self.doc_axis = doc_axis
        self.n_shards = mesh.shape[doc_axis]
        self.capacity = per_doc_capacity
        # per-shard ownership: shard_members[s][r] = sidecar slot at
        # local row r of shard s; global row = s * rows_per_shard + r
        self.shard_members: list[list[int]] = [
            [] for _ in range(self.n_shards)
        ]
        self.rows_per_shard = 1
        self.row_of: dict[int, int] = {}   # slot -> global row
        # per-member STREAM WATERMARK — the exactly-once contract
        # shared with SeqShardedPool (see its docstring): a rebuild
        # advances every watermark to the stream head, so ops it
        # subsumed can never dispatch again
        self.applied_upto: dict[int, int] = {}
        # per-member heat: EWMA of dispatched tail depth, decayed
        # every dispatching settle — what the migration policy reads.
        # Lives on the shared HeatLedger (obs/heat.py) since PR18, so
        # the same signal the migration heuristic reads is visible to
        # metrics/federation; pass a shared ledger to co-own it with
        # the attribution plane, or let the pool keep a private one.
        # The cap must exceed any member population the pool can hold
        # (an eviction here would silently zero a live member's heat).
        self.heat_decay = heat_decay
        self.heat = heat if heat is not None else HeatLedger(
            max_keys=1 << 16, decay=heat_decay)
        self._table: Optional[SegmentTable] = None
        self.dispatch_count = 0
        self.last_dispatch_depth = 0
        self.migration_count = 0
        # fleet observability (PR13): migrations are settle-boundary
        # EVENTS, not per-op hops, so each move stamps the canonical
        # pool:migrate hop onto the pool's OWN trace list (bounded
        # below) and lands on the attached FleetTimeline when one is
        # wired (obs/timeline.py — chaos/config12 read it there)
        self.timeline = timeline
        self.migration_traces: list = []

    # -- bookkeeping ---------------------------------------------------

    @property
    def members(self) -> list:
        """Slots in shard-then-row order (len() = pooled docs)."""
        return [s for shard in self.shard_members for s in shard]

    def _reindex(self, rows: Optional[int] = None) -> None:
        """Recompute ``row_of`` (and the pow2 row bucket, unless
        ``rows`` pins it — a migration must not shrink the bucket
        under the live table)."""
        need = max((len(m) for m in self.shard_members), default=0)
        if rows is None:
            rows = 1
            while rows < need:
                rows *= 2
        assert rows >= max(need, 1)
        self.rows_per_shard = rows
        self.row_of = {}
        for shard, members in enumerate(self.shard_members):
            for r, slot in enumerate(members):
                self.row_of[slot] = shard * rows + r

    def _set_member_gauges(self) -> None:
        for shard, members in enumerate(self.shard_members):
            _M_MEMBERS.labels(shard=str(shard)).set(len(members))

    def _placed(self, table: SegmentTable) -> SegmentTable:
        sharding = NamedSharding(self.mesh, P(self.doc_axis))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sharding), table
        )

    # -- dispatch ------------------------------------------------------

    def _apply(self, table, arrays):
        batch = OpBatch(**arrays)
        if self.n_shards == 1:
            out = apply_window(table, batch)
        else:
            out = apply_window_mesh_sharded(
                table, batch, self.mesh, self.doc_axis)
        # compact after every pool dispatch: remove-heavy histories
        # otherwise accumulate dead segments until they overflow a
        # pool that could easily hold the live text
        return compact(out)

    def _replay_all(self, streams) -> None:
        """Rebuild the pool table and re-replay every member's
        canonical stream in chunk-sized sharded dispatches (the same
        recipe — and the same replay helper — as the seq pool)."""
        self._reindex()
        if not self.row_of:
            self._table = None
            self.applied_upto = {}
            self._set_member_gauges()
            _M_WATERMARK.set(0)
            return
        table = self._placed(make_table(
            self.n_shards * self.rows_per_shard, self.capacity
        ))
        self._table = replay_chunked(
            self._apply, table,
            {row: streams[slot].ops
             for slot, row in self.row_of.items()},
            chunk=BucketLadder.replay_chunk(self.capacity),
        )
        self.applied_upto = {
            slot: len(streams[slot].ops) for slot in self.row_of
        }
        self._set_member_gauges()
        _M_WATERMARK.set(sum(self.applied_upto.values()))

    def admit(self, slots: list, streams) -> list:
        """Admit sidecar slots onto the least-occupied shards; returns
        the slots that FAILED (exceed even pooled capacity) and were
        rolled back out."""
        for slot in slots:
            if slot not in self.row_of:
                shard = min(
                    range(self.n_shards),
                    key=lambda i: (len(self.shard_members[i]), i),
                )
                self.shard_members[shard].append(slot)
                self._reindex()
        self._replay_all(streams)
        failed = self.overflowed_slots()
        if failed:
            for slot in failed:
                self.remove(slot)
            self._replay_all(streams)
        return failed

    def remove(self, slot: int) -> None:
        """Bookkeeping only — the table still holds the removed row's
        data at the OLD indices. Callers MUST follow with rebuild()
        before the next read or dispatch (same contract as
        SeqShardedPool.remove)."""
        for members in self.shard_members:
            if slot in members:
                members.remove(slot)
                break
        else:
            return
        self.applied_upto.pop(slot, None)
        self.heat.pop(slot)
        self._reindex()

    def rebuild(self, streams) -> None:
        self._replay_all(streams)

    def dispatch_pending(self, streams) -> list:
        """Apply every member's un-applied canonical-stream tail (past
        its watermark) in ONE sharded dispatch; returns slots that
        overflowed the pool. Runs inside the sidecar's ``_settle`` —
        after the tails land (and only when no overflow needs
        recovery first), the heat tracker may migrate one hot
        document (``_maybe_migrate``)."""
        if self._table is None:
            return []
        if _SITE_POOL_DISPATCH.fire(tier="mesh") is not None:
            # deferred: tails stay past the watermark and apply whole
            # at the next settle — exactly-once by construction (heat
            # also waits; a lagging dispatch must not decay it)
            _M_POOL_FAULTS.labels(tier="mesh", op="dispatch").inc()
            return []
        pending = {}
        depths = {}
        upto = {}
        for slot, row in self.row_of.items():
            tail = streams[slot].ops[self.applied_upto.get(slot, 0):]
            if tail:
                pending[row] = coalesce_noops(tail)
                # heat counts REAL ops only: every sequenced message
                # fans a noop into every other subscribed doc's
                # stream, so raw tail length is near-uniform across
                # members and would wash out the hot-spot signal
                depths[slot] = sum(
                    1 for op in tail if op["kind"] != KIND_NOOP
                )
                upto[slot] = len(streams[slot].ops)
        if not pending:
            return []
        self._update_heat(depths)
        depth = sum(len(ops) for ops in pending.values())
        self.dispatch_count += 1
        self.last_dispatch_depth = depth
        _M_DISPATCH.inc()
        _M_DEPTH.set(depth)
        arrays = pack_rows(self._table.docs, pending)
        self._table = self._apply(self._table, arrays)
        self.applied_upto.update(upto)
        _M_WATERMARK.set(sum(self.applied_upto.values()))
        overflowed = self.overflowed_slots()
        if not overflowed:
            # migration only on a clean settle: an overflow hands
            # control to the sidecar's recovery (evict + rebuild)
            # first, so a move can never race a recovery rebuild
            # within one settle
            self._maybe_migrate()
        return overflowed

    # -- migration -----------------------------------------------------

    def _update_heat(self, depths: dict) -> None:
        # one vectorized EWMA step on the shared ledger — bit-identical
        # to the per-slot dict update this replaced (the PR8 parity
        # differential pins it on a shared ledger too)
        self.heat.ewma_tick(self.row_of, depths, decay=self.heat_decay)

    def shard_loads(self) -> list:
        """Per-shard heat totals (what the migration policy reads)."""
        return [
            sum(self.heat.get(s, 0.0) for s in members)
            for members in self.shard_members
        ]

    def _maybe_migrate(self) -> None:
        """Move at most ONE document from the hottest shard to the
        coldest, choosing the member whose move minimizes the
        resulting hottest-shard load (so a viral doc's co-residents
        move away from it when moving the viral doc itself would just
        relocate the hot spot). Wholly deterministic: ties break on
        shard index, then slot id."""
        if self.n_shards < 2 or self._table is None:
            return
        if _SITE_POOL_MIGRATE.fire() is not None:
            # deferred: migration is opportunistic — heat persists, so
            # a genuinely hot shard re-offers the same move next settle
            _M_POOL_FAULTS.labels(tier="mesh", op="migrate").inc()
            return
        loads = self.shard_loads()
        hot = max(range(self.n_shards), key=lambda i: (loads[i], -i))
        mean = sum(loads) / self.n_shards
        _M_IMBALANCE.set(loads[hot] / mean if mean > 0 else 1.0)
        if len(self.shard_members[hot]) < 2:
            return
        # coldest shard that still has a free local row (a full shard
        # cannot receive without a row-bucket rebuild; the next
        # admission growth rebalances those)
        open_shards = [
            i for i in range(self.n_shards)
            if i != hot
            and len(self.shard_members[i]) < self.rows_per_shard
        ]
        if not open_shards:
            return
        cold = min(open_shards, key=lambda i: (loads[i], i))
        best = None
        best_peak = loads[hot]
        for slot in sorted(
                self.shard_members[hot],
                key=lambda s: (-self.heat.get(s, 0.0), s)):
            h = self.heat.get(slot, 0.0)
            if h <= 0.0:
                continue
            peak = max(loads[hot] - h, loads[cold] + h)
            if peak < best_peak - 1e-12:
                best, best_peak = slot, peak
        if best is None:
            return  # no move lowers the hottest shard
        self._move(best, hot, cold)

    def _move(self, slot: int, src: int, dst: int) -> None:
        old_rows = dict(self.row_of)
        self.shard_members[src].remove(slot)
        self.shard_members[dst].append(slot)
        # row bucket PINNED: the destination had a free local row, and
        # shrinking the bucket here would desync row_of from the table
        self._reindex(rows=self.rows_per_shard)
        perm = np.arange(self._table.docs, dtype=np.int32)
        for s, new_row in self.row_of.items():
            perm[new_row] = old_rows[s]
        # op-ordered handoff: every watermark is at its stream head
        # and nothing is in flight, so the permutation commutes with
        # the op order. The pre-move table is CONSUMED (donated) —
        # nothing may read it after this line
        self._table = migrate_rows(self._table, perm)
        self.migration_count += 1
        _M_MIGRATIONS.inc()
        _trace_stamp(self.migration_traces, "pool", "migrate")
        del self.migration_traces[:-64]  # bounded, newest kept
        if self.timeline is not None:
            self.timeline.record("migration", node=f"shard-{src}",
                                 slot=slot, src=src, dst=dst)
        self._set_member_gauges()

    # -- prewarm + reads ----------------------------------------------

    def prewarm(self) -> None:
        """Compile the pool's dispatch programs before any admission:
        the first-admission table (row bucket 1 per shard) at both
        window shapes the pool dispatches (the incremental floor
        bucket and the replay chunk bucket), both input-sharding
        signatures (fresh placement vs a table that came out of a
        pool dispatch), the compact that follows every dispatch, and
        the migration gather. Same honesty contract as
        ``SeqShardedPool.prewarm``: multi-slot row buckets and
        past-floor window buckets still pay on admission — admission
        is rare and already O(history)."""
        noop = dict(
            kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0,
            client=0, op_id=0, length=0, is_marker=0,
            prop_key=0, prop_val=0, min_seq=0,
        )
        docs = self.n_shards  # first-admission shape: row bucket 1
        chunk = BucketLadder.replay_chunk(self.capacity)
        out = None
        for floor in sorted({16, chunk}):
            arrays = pack_rows(docs, {0: [noop]}, bucket_floor=floor)
            out = self._apply(
                self._placed(make_table(docs, self.capacity)), arrays
            )
            out = self._apply(out, arrays)
        if self.n_shards > 1:
            # the migration gather: one program per table shape
            # (identity permutation; `out` is consumed — migrate_rows
            # donates its source)
            migrate_rows(out, np.arange(docs, dtype=np.int32))

    def overflowed_slots(self) -> list:
        if self._table is None:
            return []
        flags = np.asarray(self._table.overflow)
        # non-member rows are garbage (vacated by migrations, padding
        # up to the row bucket): only member rows are ever read
        return [
            slot for slot, row in sorted(
                self.row_of.items(), key=lambda kv: kv[1])
            if row < flags.shape[0] and flags[row]
        ]

    def fetch(self):
        from ..ops.host_bridge import fetch

        return fetch(self._table)
