"""Tree serving plane: SharedTree documents served doc-parallel
through the sidecar dispatch loop.

The merge sidecar's pipelined pack->dispatch->settle contract
(tpu_sidecar.py), instantiated for the second kernelized DDS
(ROADMAP item 6): forest state lives on device as SoA arrays
``[docs, slots]`` (ops/tree_apply.py) and every round's queued
insert/remove/move/annotate changesets apply across all tracked tree
documents in ONE dispatch — trunk-suffix rebase as a ``lax.scan``
over the per-doc trunk ring vmapped over docs, then the batched
forest-apply kernel on the validated executor route (``atom`` is the
per-atom parity reference, ``macro`` the one-sort macro step; both
bit-identical by the service differential suite).

The same tier policy as the merge plane, in the same order: primary
slab ladder (2x regrows re-applying the failed window from the
pre-dispatch snapshot), then the pooled tier (``TreeSeqPool`` — a
larger chip-local slab; the tree kernels' per-changeset sorts do not
decompose over a slot-sharded axis, so the pool's capacity unlock is
slab size, not slot sharding), then host eviction to a scalar
EditManager replica (full fidelity: nested fields, unbounded width).
Two tree-specific eviction triggers ride the same path: a
device-inexpressible changeset (``encode_tree_commit`` ValueError)
and a commit whose ref predates the device trunk ring
(``ring_safe`` — the ring holds the last ``TRUNK_RING`` rebased
trunk commits, and a straggler that must rebase over more than that
is host work by design).

``ChannelKindRouter`` is the ingress-side routing point: the attach
op announces ``channelType`` (the IChannelFactory boundary), and the
router feeds sharedstring channels to the merge sidecar and
sharedtree channels to this one — flat merge documents never
traverse tree code on their hot path, and vice versa.
"""
from __future__ import annotations

import copy
import json
import os
import time
from collections import deque
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..models.tree.editmanager import Commit, EditManager
from ..obs import metrics as obs_metrics
from ..obs.flight_recorder import FlightRecorder
from ..obs.profiler import device_trace
from ..ops.bucket_ladder import BucketLadder
from ..ops.event_graph import validate_executor
from ..ops.tree_apply import (
    DEFAULT_ATOMS,
    TREE_EXECUTOR_ROUTES,
    TRUNK_RING,
    apply_tree_window,
    decode_tree_row,
    encode_tree_commit,
    make_tree_table,
    noop_tree_commit,
    pack_tree_window,
    pad_tree_capacity,
    ring_safe,
)
from ..protocol.messages import MessageType, SequencedMessage
from ..protocol.tree_payload import tree_change_from_json
from ..qos.faults import KIND_ERROR, KIND_ERROR_BURST, PLANE as _CHAOS

_M_ROUNDS = obs_metrics.REGISTRY.counter(
    "tree_sidecar_rounds_total", "tree dispatch rounds flushed")
_M_COMMITS = obs_metrics.REGISTRY.counter(
    "tree_sidecar_commits_total",
    "sequenced tree changesets applied on device")
_M_GROW = obs_metrics.REGISTRY.counter(
    "tree_sidecar_grow_total", "tree capacity-ladder regrows")
_M_EVICT = obs_metrics.REGISTRY.counter(
    "tree_sidecar_evict_total",
    "tree documents evicted to host EditManager replicas")
_M_RING_EVICT = obs_metrics.REGISTRY.counter(
    "tree_sidecar_ring_evict_total",
    "tree documents evicted because a commit's ref predated the "
    "device trunk ring (ring_safe)")
_M_RECOVER = obs_metrics.REGISTRY.counter(
    "tree_sidecar_overflow_recoveries_total",
    "tree settle boundaries that found the overflow flag set")
_M_POOL_ADMIT = obs_metrics.REGISTRY.counter(
    "tree_sidecar_pool_admit_total",
    "tree documents admitted to the pooled tier")
_M_DUP_DROPS = obs_metrics.REGISTRY.counter(
    "tree_sidecar_duplicate_drops_total",
    "duplicate sequenced deliveries dropped by the per-document "
    "sequence-number guard")
_M_DISPATCH_FAULTS = obs_metrics.REGISTRY.counter(
    "tree_sidecar_dispatch_faults_total",
    "tree dispatch rounds that failed transiently before mutating "
    "anything (commits stay queued; the next apply retries exactly)")
_M_PACK_MS = obs_metrics.REGISTRY.histogram(
    "tree_sidecar_pack_ms", "host half of a tree round (encode+pack)")
_M_SETTLE_MS = obs_metrics.REGISTRY.histogram(
    "tree_sidecar_settle_ms",
    "device-wait at the tree settle boundary")
_M_TRACKED = obs_metrics.REGISTRY.gauge(
    "tree_sidecar_tracked_channels",
    "tree channels on the device batch path")
_M_HOSTED = obs_metrics.REGISTRY.gauge(
    "tree_sidecar_host_docs",
    "tree documents on host EditManager replicas")
_M_CAPACITY = obs_metrics.REGISTRY.gauge(
    "tree_sidecar_capacity",
    "current tree slab capacity (node slots/doc)")
_M_POOL_MEMBERS = obs_metrics.REGISTRY.gauge(
    "tree_pool_members", "tree documents on the pooled tier")
_M_POOL_DISPATCH = obs_metrics.REGISTRY.counter(
    "tree_pool_dispatches_total",
    "tree-pool incremental dispatches")

# chaos seam: fires BEFORE the round mutates anything (queues intact,
# so a retry is exact) — the same recovery-path contract as
# sidecar.dispatch (docs/ROBUSTNESS.md)
_SITE_DISPATCH = _CHAOS.site(
    "tree_sidecar.dispatch", (KIND_ERROR, KIND_ERROR_BURST))


def default_tree_executor() -> str:
    """Tree-plane route policy, mirroring ``default_executor``: the
    per-atom scan is the CPU default (launches are ~free there and a
    fused scan step beats the macro sort), the one-sort macro step is
    the launch-taxed TPU default (2 launches per changeset vs 2A scan
    steps). ``FFTPU_TREE_EXECUTOR=atom|macro`` overrides either way
    and fails LOUDLY on a typo."""
    env = os.environ.get("FFTPU_TREE_EXECUTOR")
    if env:
        validate_executor(env, "FFTPU_TREE_EXECUTOR",
                          routes=TREE_EXECUTOR_ROUTES)
        return env
    import jax

    # a backend that fails to initialise raises, as in default_executor
    return "macro" if jax.default_backend() == "tpu" else "atom"


def _fresh_replica(slot: int) -> EditManager:
    return EditManager(session_id=f"tree-host-{slot}")


class TreeSeqPool:
    """Pooled tier for tree documents that outgrow the primary slab
    ladder: a fixed-row table at a LARGER per-doc capacity. The tree
    kernels' per-changeset sorts (ops/tree_apply.py) do not decompose
    over a slot-sharded axis — the same reason SeqShardedPool keeps
    the scan-collective route — so this pool's capacity unlock is a
    bigger chip-local slab, with host eviction the last resort.
    Admission rebuilds the pool table at the next pow2 row bucket and
    replays every member's canonical encoded-commit stream in chunked
    dispatches; incremental traffic dispatches watermarked stream
    tails at the settle boundary (exactly-once by construction, the
    SeqShardedPool contract)."""

    def __init__(self, mesh, per_doc_capacity: int,
                 executor: Optional[str] = None,
                 ring: int = TRUNK_RING, atoms: int = DEFAULT_ATOMS,
                 ladder: Optional[BucketLadder] = None):
        validate_executor(executor, "executor",
                          routes=TREE_EXECUTOR_ROUTES)
        self.mesh = mesh  # accepted for select_pool API parity only
        self.capacity = per_doc_capacity
        self.executor = executor or default_tree_executor()
        self.ring = ring
        self.atoms = atoms
        self.ladder = ladder or BucketLadder()
        self.members: list[int] = []
        self.row_of: dict[int, int] = {}
        # per-member stream watermark: encoded commits already
        # reflected by the pool table (rebuilds advance it to the
        # head, so a tail a rebuild subsumed can never dispatch again)
        self.applied_upto: dict[int, int] = {}
        self._table = None
        self.dispatch_count = 0

    def _bucket(self) -> int:
        b = 1
        while b < max(1, len(self.members)):
            b *= 2
        return b

    def _replay_all(self, encoded: list[list[dict]]) -> None:
        if not self.members:
            self._table = None
            return
        rows = self._bucket()
        table = make_tree_table(rows, self.capacity, ring=self.ring,
                                atoms=self.atoms)
        chunk = BucketLadder.replay_chunk(self.capacity)
        depth = max(len(encoded[s]) for s in self.members)
        for start in range(0, max(depth, 1), chunk):
            queued = {
                row: encoded[slot][start:start + chunk]
                for row, slot in enumerate(self.members)
                if encoded[slot][start:start + chunk]
            }
            program = pack_tree_window(
                rows, queued, self.ladder, bucket_floor=chunk,
                width=self.atoms)
            table = apply_tree_window(table, program, self.executor)
        self._table = table
        self.applied_upto = {
            slot: len(encoded[slot]) for slot in self.members
        }
        _M_POOL_MEMBERS.set(len(self.members))

    def admit(self, slots: list, encoded: list[list[dict]]) -> list:
        """Admit sidecar slots; returns the slots that FAILED (exceed
        even pooled capacity) and were rolled back out."""
        for slot in slots:
            if slot not in self.row_of:
                self.row_of[slot] = len(self.members)
                self.members.append(slot)
        self._replay_all(encoded)
        failed = self.overflowed_slots()
        if failed:
            for slot in failed:
                self.remove(slot)
            self._replay_all(encoded)
        return failed

    def remove(self, slot: int) -> None:
        """Bookkeeping only — callers follow with rebuild() before
        the next read or dispatch (the SeqShardedPool contract)."""
        if slot not in self.row_of:
            return
        row = self.row_of.pop(slot)
        self.applied_upto.pop(slot, None)
        self.members.pop(row)
        for s2, r2 in self.row_of.items():
            if r2 > row:
                self.row_of[s2] = r2 - 1

    def rebuild(self, encoded: list[list[dict]]) -> None:
        self._replay_all(encoded)

    def dispatch_pending(self, encoded: list[list[dict]]) -> list:
        """Apply every member's un-applied stream tail in one
        dispatch; returns slots that overflowed the pool."""
        if self._table is None:
            return []
        pending = {}
        upto = {}
        for slot, row in self.row_of.items():
            tail = encoded[slot][self.applied_upto.get(slot, 0):]
            if tail:
                pending[row] = tail
                upto[slot] = len(encoded[slot])
        if not pending:
            return []
        self.dispatch_count += 1
        _M_POOL_DISPATCH.inc()
        program = pack_tree_window(
            self._table.docs, pending, self.ladder,
            width=self.atoms)
        self._table = apply_tree_window(
            self._table, program, self.executor)
        self.applied_upto.update(upto)
        return self.overflowed_slots()

    def prewarm(self) -> None:
        """Compile the first-admission shapes (row bucket 1 at the
        incremental floor bucket and the replay chunk bucket) before
        any admission reaches them mid-serve; wider row buckets and
        deeper windows pay as they land — admission is rare and
        already O(history), the SeqShardedPool discipline."""
        noop = noop_tree_commit(self.atoms)
        chunk = BucketLadder.replay_chunk(self.capacity)
        for floor in sorted({self.ladder.window_floor, chunk}):
            program = pack_tree_window(
                1, {0: [noop]}, self.ladder, bucket_floor=floor,
                width=self.atoms)
            table = make_tree_table(1, self.capacity, ring=self.ring,
                                    atoms=self.atoms)
            out = apply_tree_window(table, program, self.executor)
            apply_tree_window(out, program, self.executor)

    def overflowed_slots(self) -> list:
        if self._table is None:
            return []
        flags = np.asarray(self._table.overflow)
        return [self.members[r]
                for r in np.nonzero(flags)[0].tolist()
                if r < len(self.members)]


class TreeSidecar:
    """Batched forest state for up to ``max_docs`` sharedtree
    channels. One tracked channel (doc slot) = one (document,
    datastore, channel) sequenced changeset stream; ``ingest``
    consumes the document's sequenced envelope stream, ``apply``
    flushes accumulated commit windows in a single pipelined
    dispatch, and ``_settle`` is the ONLY host<->device sync (the
    merge sidecar's pipeline/settle contract)."""

    def __init__(self, max_docs: int = 64, capacity: int = 64,
                 max_capacity: int = 4096,
                 pool_mesh=None, pool_capacity: Optional[int] = None,
                 executor: Optional[str] = None,
                 pipeline: Optional[bool] = None,
                 ladder: Optional[BucketLadder] = None,
                 ring: int = TRUNK_RING,
                 width: int = DEFAULT_ATOMS):
        self.max_docs = max_docs
        self.capacity = capacity
        self.max_capacity = max_capacity
        self.ring = ring
        self.width = width
        # the constructor-arg route typo is exactly as loud as the
        # env one (the select_pool discipline)
        validate_executor(executor, "executor",
                          routes=TREE_EXECUTOR_ROUTES)
        self.executor = executor or default_tree_executor()
        if pipeline is not None:
            self.pipeline = pipeline
        else:
            env_pipe = os.environ.get("FFTPU_SIDECAR_PIPELINE")
            if env_pipe and env_pipe not in ("0", "1"):
                raise ValueError(
                    f"FFTPU_SIDECAR_PIPELINE={env_pipe!r}: expected "
                    "'0' or '1'"
                )
            self.pipeline = env_pipe != "0"
        self.ladder = ladder or BucketLadder()
        self.flight = FlightRecorder(256, name="tree-sidecar")
        self.last_flight_dump: Optional[str] = None
        self._pool = None
        if pool_mesh is not None:
            from .tpu_sidecar import select_pool

            self._pool = select_pool(
                pool_mesh, pool_capacity, executor=self.executor,
                max_capacity=max_capacity, plane="tree",
            )
            self._pool.ring = ring
            self._pool.atoms = width
        self.pool_admit_count = 0
        self._table = make_tree_table(max_docs, capacity, ring=ring,
                                      atoms=width)
        self._slots: dict[tuple[str, str, str], int] = {}
        self._doc_slots: dict[str, list[tuple[int, str, str]]] = {}
        self._last_ingested: dict[str, int] = {}
        # per-slot canonical histories: raw scalar commits (evictions
        # replay these into the EditManager replica) and the encoded
        # device form (grow re-applies the window; the pool replays
        # the encoded stream)
        self._raw: list[list[Commit]] = []
        self._encoded: list[list[dict]] = []
        self._queued: list[list[dict]] = []
        # host payload tables per slot (node content / value dicts;
        # device arrays carry only indices into these)
        self._content_tables: list[list] = []
        self._value_tables: list[list] = []
        # host mirror of the device ring occupancy: seqs of the last
        # ``ring`` encoded commits per slot (ring_safe reads it at
        # ingest — commits queued ahead of this one will have pushed
        # the device ring by the time this one rebases)
        self._ring_hist: list[deque] = []
        self._session_ord: dict[str, int] = {}
        self._host: dict[int, EditManager] = {}
        self._prev_table = None
        self._last_program = None
        self._unsettled = False
        self.grow_count = 0
        self.evict_count = 0
        self.ring_evict_count = 0
        self.stats = {"pack_s": 0.0, "settle_s": 0.0, "rounds": 0}
        _M_CAPACITY.set(self.capacity)

    # ------------------------------------------------------------------
    # registration + ingest

    def track(self, document_id: str, datastore_id: str,
              channel_id: str) -> int:
        key = (document_id, datastore_id, channel_id)
        if key in self._slots:
            return self._slots[key]
        if len(self._raw) >= self.max_docs:
            raise RuntimeError(
                "tree sidecar document capacity exhausted")
        slot = len(self._raw)
        self._slots[key] = slot
        self._doc_slots.setdefault(document_id, []).append(
            (slot, datastore_id, channel_id)
        )
        self._raw.append([])
        self._encoded.append([])
        self._queued.append([])
        self._content_tables.append([])
        self._value_tables.append([])
        self._ring_hist.append(deque(maxlen=self.ring))
        _M_TRACKED.set(len(self._raw))
        return slot

    def subscribe(self, server, document_id: str, datastore_id: str,
                  channel_id: str) -> None:
        """Attach to a LocalServer document's broadcaster (after deli,
        beside scriptorium — the merge sidecar's seat)."""
        self.track(document_id, datastore_id, channel_id)
        orderer = server.get_orderer(document_id)
        orderer.broadcaster.subscribe(
            f"tree-sidecar-{id(self)}/{document_id}/{datastore_id}/"
            f"{channel_id}",
            lambda msg: self.ingest(document_id, msg),
        )

    def _session(self, client_id: Optional[str]) -> int:
        sid = client_id or ""
        if sid not in self._session_ord:
            self._session_ord[sid] = len(self._session_ord) + 1
        return self._session_ord[sid]

    def ingest(self, document_id: str, msg: SequencedMessage) -> None:
        """Consume one sequenced message of a document. Only
        ``{"type": "tree"}`` channel ops for tracked channels carry
        forest state; everything else (joins, other channels,
        tree-schema ops) is ignored — the tree plane keeps no collab
        window, so non-changeset traffic has no device effect.

        AT-LEAST-ONCE GUARD: same per-document dedupe as the merge
        sidecar's ingest — a duplicate delivery would extend the
        canonical histories and apply twice."""
        last = self._last_ingested.get(document_id, 0)
        if msg.sequence_number <= last:
            _M_DUP_DROPS.inc()
            return
        self._last_ingested[document_id] = msg.sequence_number
        for slot, ds_id, ch_id in self._doc_slots.get(document_id, ()):
            envelope = msg.contents \
                if isinstance(msg.contents, dict) else {}
            if not (
                msg.type == MessageType.OPERATION
                and envelope.get("kind", "op") == "op"
                and envelope.get("address") == ds_id
                and envelope.get("channel") == ch_id
            ):
                continue
            changes = tree_change_from_json(envelope.get("contents"))
            if changes is None:
                continue  # tree-schema etc: no forest effect
            commit = Commit(
                session_id=msg.client_id or "",
                seq=msg.sequence_number,
                ref_seq=msg.reference_sequence_number,
                changes=copy.deepcopy(changes),
            )
            self._ingest_commit(slot, commit)

    def _ingest_commit(self, slot: int, commit: Commit) -> None:
        if slot in self._host:
            self._host[slot].add_sequenced_change(commit, False)
            return
        if not ring_safe(list(self._ring_hist[slot]), commit.ref_seq,
                         self.ring):
            # the commit must rebase over more trunk commits than the
            # device ring retains: host work by design
            self.ring_evict_count += 1
            _M_RING_EVICT.inc()
            self._settle()
            self._evict(slot)
            self._host[slot].add_sequenced_change(commit, False)
            return
        try:
            if set(commit.changes) - {"root"}:
                raise ValueError(
                    "non-root tree fields: host path only")
            enc = encode_tree_commit(
                commit.changes.get("root", []),
                self._content_tables[slot],
                self._value_tables[slot],
                seq=commit.seq, ref=commit.ref_seq,
                session=self._session(commit.session_id),
                width=self.width,
            )
        except ValueError:
            # device-inexpressible (nested fields, width overflow,
            # repair-store marks): the full-fidelity host replica
            # takes over — the merge sidecar's eviction discipline
            self._settle()
            self._evict(slot)
            self._host[slot].add_sequenced_change(commit, False)
            return
        self._raw[slot].append(commit)
        self._encoded[slot].append(enc)
        self._queued[slot].append(enc)
        self._ring_hist[slot].append(commit.seq)

    # ------------------------------------------------------------------
    # device application (the dispatch pipeline)

    @property
    def queued_commits(self) -> int:
        return sum(len(q) for q in self._queued)

    def apply(self) -> int:
        """Flush all queued commit windows in one batched dispatch;
        returns the number of commits dispatched. Pipelined (the
        default): returns at enqueue — this round's overflow flag is
        read at the next apply/read, inside ``_settle``."""
        if self.queued_commits == 0:
            return 0
        real = self._dispatch()
        if not self.pipeline:
            self._settle()
        return real

    def sync(self) -> None:
        """Barrier: settle the in-flight round (overflow recovery,
        pool dispatch)."""
        self._settle()

    def _dispatch(self) -> int:
        # chaos seam BEFORE any mutation: queues intact, a retry is
        # exactly the same round
        fault = _SITE_DISPATCH.fire(queued=self.queued_commits)
        if fault is not None:
            _M_DISPATCH_FAULTS.inc()
            raise _SITE_DISPATCH.transient(fault)
        t0 = time.perf_counter()
        packed: dict[int, list[dict]] = {}
        pool_commits = 0
        for slot, q in enumerate(self._queued):
            if not q:
                continue
            if self._pool is not None and slot in self._pool.row_of:
                # pooled docs dispatch from their watermarked encoded
                # streams at the settle boundary
                pool_commits += len(q)
                continue
            packed[slot] = list(q)
        program = pack_tree_window(
            self.max_docs, packed, self.ladder, width=self.width)
        real = sum(len(v) for v in packed.values())
        for q in self._queued:
            q.clear()
        pack_s = time.perf_counter() - t0
        self.stats["pack_s"] += pack_s
        self.stats["rounds"] += 1
        _M_ROUNDS.inc()
        _M_COMMITS.inc(real + pool_commits)
        _M_PACK_MS.observe(pack_s * 1000.0)
        self.flight.record(
            "dispatch", round=self.stats["rounds"], commits=real,
            pool_commits=pool_commits,
            pack_ms=round(pack_s * 1000.0, 3),
            capacity=self.capacity,
        )
        # SYNC BOUNDARY — read the previous round's overflow flag
        # before its snapshot is retired below
        self._settle()
        self._prev_table = self._table
        self._last_program = program
        self._unsettled = True
        with device_trace(
                f"tree-sidecar:dispatch:r{self.stats['rounds']}"):
            self._table = apply_tree_window(
                self._prev_table, program, self.executor)
        return real + pool_commits

    def _settle(self) -> None:
        """The designated host<->device sync boundary: read the
        in-flight round's overflow flag, run recovery if set, flush
        the pool dispatch. Reads and the next dispatch both funnel
        through here; nothing else in the apply loop may force a
        device->host transfer."""
        if not self._unsettled:
            return
        self._unsettled = False
        t0 = time.perf_counter()
        overflowed = bool(np.asarray(self._table.overflow).any())
        settle_s = time.perf_counter() - t0
        self.stats["settle_s"] += settle_s
        _M_SETTLE_MS.observe(settle_s * 1000.0)
        self.flight.record(
            "settle", settle_ms=round(settle_s * 1000.0, 3),
            overflow=overflowed,
        )
        if overflowed:
            _M_RECOVER.inc()
            self.last_flight_dump = self.flight.dump_to(
                reason="tree _settle found the overflow flag set "
                       "(recovery running)")
            self._recover()
        self._prev_table = None
        self._last_program = None
        if self._pool is not None and self._pool.members:
            # inside the just-settled branch on purpose (the merge
            # sidecar's tier-consistency rule): the pool advances
            # only when a flush was in flight
            for slot in self._pool.dispatch_pending(self._encoded):
                self._evict(slot)  # beyond even pooled capacity

    # ------------------------------------------------------------------
    # overflow recovery: grow ladder -> pooled tier -> host eviction

    def _recover(self) -> None:
        while True:
            overflowed = np.nonzero(
                np.asarray(self._table.overflow))[0]
            if overflowed.size == 0:
                return
            if self.capacity * 2 <= self.max_capacity:
                self._grow(self.capacity * 2)
            elif self._pool is not None:
                failed = self._admit_to_pool(overflowed.tolist())
                for slot in failed:
                    self._evict(slot)
                return
            else:
                for slot in overflowed.tolist():
                    self._evict(slot)
                return

    def _grow(self, new_capacity: int) -> None:
        """Grow the slab 2x and retry the failed window: pad the
        pre-dispatch snapshot and re-apply the SAME window at the new
        capacity — O(window), exact, because a parked doc's state,
        ring and overflow flag all predate the window (the kernel's
        park contract), so the snapshot re-apply is the first time
        the window touches it."""
        self.grow_count += 1
        _M_GROW.inc()
        self.capacity = new_capacity
        _M_CAPACITY.set(new_capacity)
        self.flight.record("recover-grow", capacity=new_capacity)
        if self._prev_table is None:  # pragma: no cover - first flush
            self._prev_table = make_tree_table(
                self.max_docs, new_capacity, ring=self.ring,
                atoms=self.width)
        else:
            self._prev_table = pad_tree_capacity(
                self._prev_table, new_capacity)
        self._table = apply_tree_window(
            self._prev_table, self._last_program, self.executor)

    def _retire_rows(self, slots: list) -> None:
        """Zero the primary-table count/overflow of ``slots`` — reads
        route elsewhere for these docs, and a stale overflow flag
        would re-trigger (or wedge) recovery."""
        if not slots:
            return
        count = np.asarray(self._table.count).copy()
        overflow = np.asarray(self._table.overflow).copy()
        for slot in slots:
            count[slot] = 0
            overflow[slot] = 0
        self._table = self._table._replace(
            count=jnp.asarray(count), overflow=jnp.asarray(overflow),
        )

    def _admit_to_pool(self, slots: list) -> list:
        """Move slots to the pooled tier; retire their primary rows.
        Returns slots the pool could not hold. Already-members can
        reappear via the pipelined straggler window (the merge
        sidecar's case): they need only the row retirement again."""
        fresh = [s for s in slots if s not in self._pool.row_of]
        failed = self._pool.admit(fresh, self._encoded) \
            if fresh else []
        admitted = [s for s in slots if s not in failed]
        newly = len([s for s in fresh if s not in failed])
        self.pool_admit_count += newly
        _M_POOL_ADMIT.inc(newly)
        self.flight.record("recover-pool", admitted=newly,
                           failed=len(failed))
        self._retire_rows(admitted)
        for slot in admitted:
            self._queued[slot].clear()  # replayed from the stream
        return failed

    def _evict(self, slot: int) -> None:
        """Move one document to a host-side scalar EditManager
        replica — full fidelity, off the device batch path."""
        # retire device state FIRST, even for an already-evicted doc
        # (a pipelined straggler round can re-flag a retired row)
        self._retire_rows([slot])
        if slot in self._host:
            return
        self.evict_count += 1
        _M_EVICT.inc()
        self.flight.record("recover-evict", slot=slot)
        if self._pool is not None and slot in self._pool.row_of:
            self._pool.remove(slot)
            self._pool.rebuild(self._encoded)
        replica = _fresh_replica(slot)
        for commit in self._raw[slot]:
            replica.add_sequenced_change(
                Commit(commit.session_id, commit.seq, commit.ref_seq,
                       copy.deepcopy(commit.changes)),
                False,
            )
        self._host[slot] = replica
        _M_HOSTED.set(len(self._host))
        if self._pool is not None:
            _M_POOL_MEMBERS.set(len(self._pool.members))
        self._queued[slot].clear()

    # ------------------------------------------------------------------
    # prewarm

    def prewarm(self, max_bucket: Optional[int] = None) -> float:
        """Compile every shape the (docs, window, capacity) ladder
        can reach on BOTH executor routes — steady windows, regrows
        and a route-flipped shadow sidecar all hit warm programs —
        plus the pad step between rungs and the pool tier's
        first-admission shapes. Returns seconds spent."""
        t0 = time.perf_counter()
        noop = noop_tree_commit(self.width)
        dummy_prev = None
        for rung in BucketLadder.capacity_rungs(
                self.capacity, self.max_capacity):
            table = make_tree_table(self.max_docs, rung,
                                    ring=self.ring, atoms=self.width)
            for bucket in self.ladder.window_buckets(max_bucket):
                program = pack_tree_window(
                    self.max_docs, {0: [noop]}, self.ladder,
                    bucket_floor=bucket, width=self.width)
                for route in TREE_EXECUTOR_ROUTES:
                    # each shape needs BOTH input signatures (the
                    # merge pool's prewarm rule): a fresh
                    # make_tree_table and a table that came out of a
                    # dispatch, which carries the committed output
                    # sharding — a distinct jit signature every
                    # steady-state round after the first one uses
                    out = apply_tree_window(table, program, route)
                    out = apply_tree_window(out, program, route)
                table = out
            if dummy_prev is not None:
                pad_tree_capacity(dummy_prev, rung)
            dummy_prev = table
        if self._pool is not None:
            self._warm_pool()
        np.asarray(table.count)  # force completion
        return time.perf_counter() - t0

    def _warm_pool(self) -> None:
        """Walk the pool tier's dispatch programs (see
        ``TreeSeqPool.prewarm``) — reached through the attribute-held
        pool, the shapecheck.PREWARM_INDIRECT edge."""
        self._pool.prewarm()

    # ------------------------------------------------------------------
    # reads (service-side summarization / validation)

    def _slot(self, document_id: str, datastore_id: str,
              channel_id: str) -> int:
        return self._slots[(document_id, datastore_id, channel_id)]

    def nodes(self, document_id: str, datastore_id: str,
              channel_id: str) -> list:
        """The served root-field node list (every tier)."""
        self._settle()
        slot = self._slot(document_id, datastore_id, channel_id)
        if slot in self._host:
            content = self._host[slot].forest().content()
            return copy.deepcopy(content.get("root", []))
        if self._pool is not None and slot in self._pool.row_of:
            table, row = self._pool._table, self._pool.row_of[slot]
        else:
            table, row = self._table, slot
        return decode_tree_row(
            np.asarray(table.content)[row],
            np.asarray(table.value)[row],
            int(np.asarray(table.count)[row]),
            self._content_tables[slot], self._value_tables[slot],
        )

    def signature(self, document_id: str, datastore_id: str,
                  channel_id: str) -> str:
        """Canonical forest signature (the Forest.signature
        convention: sorted-key JSON over the served fields)."""
        nodes = self.nodes(document_id, datastore_id, channel_id)
        return json.dumps({"root": nodes}, sort_keys=True,
                          default=str)

    def host_mode_docs(self) -> int:
        return len(self._host)

    def pooled_docs(self) -> int:
        return len(self._pool.members) if self._pool else 0

    def overflowed(self) -> bool:
        self._settle()
        return bool(np.asarray(self._table.overflow).any())


class ChannelKindRouter:
    """Ingress-side channel-kind routing at the IChannelFactory
    boundary: subscribe once per document, watch the sequenced stream
    for attach ops, and feed each announced channel's stream to the
    sidecar serving its kind — ``sharedstring`` to the merge sidecar,
    ``sharedtree`` to the tree sidecar. A document's flat merge
    channels never traverse tree code (and vice versa); channels of
    other kinds stay unrouted."""

    KINDS = {"sharedstring": "merge", "sharedtree": "tree"}

    def __init__(self, merge=None, tree=None):
        self.merge = merge
        self.tree = tree
        # (document, datastore, channel) -> sidecar already routed
        self._routed: dict[tuple[str, str, str], object] = {}

    def subscribe(self, server, document_id: str) -> None:
        orderer = server.get_orderer(document_id)
        orderer.broadcaster.subscribe(
            f"kind-router-{id(self)}/{document_id}",
            lambda msg: self.route(document_id, msg),
        )

    def _sidecar_for(self, channel_type: str):
        plane = self.KINDS.get(channel_type)
        return self.merge if plane == "merge" else \
            self.tree if plane == "tree" else None

    def route(self, document_id: str, msg: SequencedMessage) -> None:
        envelope = msg.contents if isinstance(msg.contents, dict) \
            else {}
        if (
            msg.type == MessageType.OPERATION
            and envelope.get("kind") == "attach"
            and isinstance(envelope.get("contents"), dict)
        ):
            ctype = envelope["contents"].get("channelType")
            sidecar = self._sidecar_for(ctype)
            ds, ch = envelope.get("address"), envelope.get("channel")
            key = (document_id, ds, ch)
            if sidecar is not None and key not in self._routed:
                sidecar.track(document_id, ds, ch)
                self._routed[key] = sidecar
        # forward to every sidecar serving a channel of this document
        # (each sidecar's own ingest filters by address/channel and
        # runs the per-document dedupe guard)
        seen = []
        for (doc, _ds, _ch), sidecar in self._routed.items():
            if doc == document_id and sidecar not in seen:
                seen.append(sidecar)
                sidecar.ingest(document_id, msg)
