"""TPU merge sidecar: device-resident merge state for the service
plane.

The north star (BASELINE.json): the ordering service's op stream is
batched into padded tensors and merge resolution runs on-device across
thousands of documents per dispatch, while the per-client host path
stays untouched. The sidecar subscribes to sequenced channel streams
(deli out-topic / broadcaster fan-out), accumulates per-document
windows, applies them with the backend's executor route, and serves
text/summary state — powering service-side summarization, replay
validation, and the benchmark.

DISPATCH PIPELINE (docs/PERF.md): the apply loop is a two-stage
pipeline. The host half (noop coalescing, vectorized ``_pack_rows``,
the event-graph build) runs for round N+1 while the device still
computes round N; the only host<->device sync is ``_settle`` — the
designated boundary where round N's overflow flag is read and recovery
runs. Two executor routes exist (``EXECUTOR_ROUTES``): on a TPU the
egwalker route (``ops/event_graph.py``: the walker over each
document's critical prefix, the scan over its concurrent suffix),
elsewhere the per-op scan (``ops/merge_kernel.py``); both are
bit-identical for live state. ``default_executor`` derives the route
from the backend; ``executor=`` names one explicitly (tests run the
chip's route on the CPU that way). Donation is re-enabled through double
buffering: round N+1 donates the round N-1 table (provably idle —
round N's input depended on it), never the live input, so the
pre-dispatch snapshot regrow needs stays alive.

Overflow recovery: a document that outgrows its
slab or exceeds the interned property channels is never silently
wrong. On overflow the sidecar REGROWS the slab (2x) by padding the
pre-dispatch table snapshot and re-applying just the failed window —
O(window), not O(history); JAX tables are immutable so the snapshot
is a free handle — or, past ``max_capacity``, admits the document to
the sequence-sharded pool / EVICTS it to a host-side scalar oracle
replica (the retained per-document encoded stream is the durable
source for those paths). The walker PARKS an overflowed document at
its pre-span state instead of applying past the flag; that difference
is absorbed here at the policy layer — recovery re-applies the whole
failed window from the snapshot (or replays the canonical stream), so
both routes converge to the same served state. ``prewarm`` walks the
shared ``BucketLadder`` so neither bucket jumps nor regrows ever hit
an XLA compile mid-serve.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.mergetree import MergeTreeClient
from ..obs import metrics as obs_metrics
from ..obs.flight_recorder import FlightRecorder
from ..obs.heat import HeatLedger, attribute_round
from ..obs.profiler import (
    device_trace,
    device_trace_enabled,
    self_timed,
)
from ..obs.trace import stamp as trace_stamp
from ..ops import (
    DocStream,
    OpBatch,
    apply_window,
    compact,
    extract_signature,
    extract_text,
    fetch,
    make_table,
)
from ..ops.bucket_ladder import BucketLadder
from ..ops.event_graph import (
    EG_K,
    _get_jit,
    _get_jit_pingpong,
    _packed_scan,
    apply_suffix,
    apply_window_egwalker,
    apply_window_egwalker_pingpong,
    build_event_graph,
    validate_executor,
)
from ..ops.host_bridge import (
    OP_FIELDS,
    pack_rows as _pack_rows,
    replay_chunked as _replay_chunked,
)
from ..ops.merge_kernel import (
    _pingpong_impl,
    apply_window_impl,
    apply_window_pingpong,
    pad_capacity,
)
from ..ops.segment_table import KIND_NOOP
from ..protocol.messages import MessageType, SequencedMessage
from ..qos.faults import (
    KIND_DEFER,
    KIND_ERROR,
    KIND_ERROR_BURST,
    PLANE as _CHAOS,
)

# _pack_rows and _replay_chunked are host_bridge.pack_rows /
# replay_chunked (both pool tiers share them with this module);
# tests import _pack_rows from here.

# Registry families (process aggregates across every sidecar/pool
# instance; exact per-instance counts stay on the owning object —
# tests read sidecar.grow_count etc.). IMPORTANT: everything bumped
# from inside the dispatch loop is host-side only — a registry inc and
# a flight-recorder record never touch the device; fluidlint's
# dispatch-loop-sync rule covers these call sites.
_M_ROUNDS = obs_metrics.REGISTRY.counter(
    "sidecar_rounds_total", "dispatch rounds flushed")
_M_OPS = obs_metrics.REGISTRY.counter(
    "sidecar_real_ops_total", "non-noop ops applied on device")
_M_GROW = obs_metrics.REGISTRY.counter(
    "sidecar_grow_total", "capacity-ladder regrows")
_M_EVICT = obs_metrics.REGISTRY.counter(
    "sidecar_evict_total", "documents evicted to host replicas")
_M_POOL_ADMIT = obs_metrics.REGISTRY.counter(
    "sidecar_pool_admit_total", "documents admitted to the seq pool")
_M_RECOVER = obs_metrics.REGISTRY.counter(
    "sidecar_overflow_recoveries_total",
    "settle boundaries that found the overflow flag set")
_M_PACK_MS = obs_metrics.REGISTRY.histogram(
    "sidecar_pack_ms", "host half of a round (pack + compile)")
_M_SETTLE_MS = obs_metrics.REGISTRY.histogram(
    "sidecar_settle_ms", "device-wait at the settle boundary")
_M_TRACKED = obs_metrics.REGISTRY.gauge(
    "sidecar_tracked_channels", "channels on the device batch path")
_M_POOLED = obs_metrics.REGISTRY.gauge(
    "sidecar_pooled_docs", "documents on the seq-sharded pool tier")
_M_HOSTED = obs_metrics.REGISTRY.gauge(
    "sidecar_host_docs", "documents evicted to host replicas")
_M_CAPACITY = obs_metrics.REGISTRY.gauge(
    "sidecar_capacity", "current primary slab capacity (slots/doc)")
_M_POOL_DISPATCH = obs_metrics.REGISTRY.counter(
    "pool_dispatches_total", "seq-pool incremental dispatches")
_M_POOL_DEPTH = obs_metrics.REGISTRY.gauge(
    "pool_dispatch_depth", "ops in the last pool dispatch")
_M_POOL_WATERMARK = obs_metrics.REGISTRY.gauge(
    "pool_watermark_ops", "sum of member stream watermarks")
_M_POOL_MEMBERS = obs_metrics.REGISTRY.gauge(
    "pool_members", "documents admitted to the pool")
_M_DUP_DROPS = obs_metrics.REGISTRY.counter(
    "sidecar_duplicate_drops_total",
    "already-ingested sequenced messages dropped by the per-document "
    "sequence-number check (at-least-once delivery upstream)")
_M_SPAN_SPLITS = obs_metrics.REGISTRY.counter(
    "egwalker_span_splits_total",
    "would-be span breaks the egwalker compiler absorbed by event "
    "splitting (each one is a saved walker launch)")
_M_DISPATCH_FAULTS = obs_metrics.REGISTRY.counter(
    "sidecar_dispatch_faults_total",
    "device dispatch rounds that failed transiently before mutating "
    "anything (ops stay queued; the next apply retries exactly)")
_M_POOL_FAULTS = obs_metrics.REGISTRY.counter(
    "pool_faults_total",
    "pool operations deferred or retried under a transient fault "
    "(shared by NAME across the seq and mesh tiers, like the "
    "sidecar.pool_* chaos sites)", labelnames=("tier", "op"))

# the round span when the device-trace switch is off: one shared no-op
_NO_SPAN = contextlib.nullcontext()

# chaos seams (docs/ROBUSTNESS.md): the dispatch site fires BEFORE the
# round mutates anything (queues intact, so a retry is exact); the
# pool sites model a lagging pool dispatch / a deferred migration / a
# transiently-failing admission — every one a recovery path the
# convergence differential must hold through
_SITE_DISPATCH = _CHAOS.site(
    "sidecar.dispatch", (KIND_ERROR, KIND_ERROR_BURST))
_SITE_POOL_DISPATCH = _CHAOS.site("sidecar.pool_dispatch", (KIND_DEFER,))
_SITE_POOL_ADMIT = _CHAOS.site("sidecar.pool_admit", (KIND_ERROR,))
_SITE_POOL_MIGRATE = _CHAOS.site("sidecar.pool_migrate", (KIND_DEFER,))


@jax.jit
def _overflow_any(overflow):
    """The settle's read on a document mesh: one flag reduced on the
    devices (a scalar collective), not the [docs] flags gathered."""
    return jnp.any(overflow != 0)


def default_executor() -> str:
    """The sidecar's executor route, derived from the backend: the
    egwalker route on a TPU, the per-op scan elsewhere (the
    macro-step's resolve and row merge cost several times a fused
    scan step on the CPU, where launches are nearly free)."""
    import jax

    # a backend that fails to initialise raises here: routing it as
    # the CPU would serve a TPU deployment from the wrong route
    return "egwalker" if jax.default_backend() == "tpu" else "scan"


class SeqShardedPool:
    """Long-document tier (SURVEY §5.7 in the PRODUCT path): documents
    that outgrow the primary slab ladder move to a table whose SLOT
    axis is sharded across a device mesh — per-document capacity =
    n_seq_devices x the primary ladder top — instead of leaving the
    device path entirely (host eviction becomes the LAST resort, for
    documents that exceed even the pooled capacity or are
    tensor-inexpressible).

    Admissions are rare (a document must exhaust the primary ladder),
    so the pool keeps its machinery simple and correct: admitting
    rebuilds the pool table at the next power-of-two row count and
    re-replays every member's canonical encoded stream in chunk-sized
    sequence-sharded dispatches (same recipe as the primary ladder's
    regrow).

    The pool runs the per-op scan whatever the sidecar's route: the
    walker's global row merge does not decompose over a slot-sharded
    axis, so a real seq mesh applies the scan collective
    (``parallel/seq_shard.py``), and a one-device mesh applies the
    plain ``apply_window``."""

    def __init__(self, mesh, per_doc_capacity: int):
        from ..parallel.seq_shard import SEQ_AXIS

        if SEQ_AXIS not in mesh.axis_names:
            raise ValueError(
                f"seq pool needs a {SEQ_AXIS!r} mesh axis (got "
                f"{mesh.axis_names}); a docs-sharded mesh routes to "
                "MeshShardedPool (select_pool)"
            )
        n_seq = mesh.shape[SEQ_AXIS]
        if per_doc_capacity % n_seq or per_doc_capacity // n_seq < 2:
            raise ValueError(
                f"pool capacity {per_doc_capacity} invalid for "
                f"{n_seq}-way seq mesh"
            )
        doc_axes = [a for a in mesh.axis_names if a != SEQ_AXIS]
        if doc_axes and mesh.shape[doc_axes[0]] != 1:
            raise ValueError(
                "pool requires an unsharded doc axis (doc_shards=1): "
                "row admissions don't track a sharded row axis"
            )
        self.mesh = mesh
        self.n_seq = n_seq
        self.capacity = per_doc_capacity
        self.members: list[int] = []      # sidecar slot per pool row
        self.row_of: dict[int, int] = {}  # sidecar slot -> row
        # per-member STREAM WATERMARK: how many of the slot's canonical
        # stream ops the pool table already reflects. This is what
        # makes incremental dispatch rebuild-proof: a full-stream
        # rebuild (_replay_all) advances every watermark to the stream
        # head, so ops it subsumed can never be dispatched again —
        # the review-confirmed double-apply of a deferred-op batch
        # racing a recovery rebuild is impossible by construction.
        self.applied_upto: dict[int, int] = {}
        self._table = None
        # per-instance observability counters (registry families hold
        # the process aggregates)
        self.dispatch_count = 0
        self.last_dispatch_depth = 0

    def _bucket(self) -> int:
        n = max(1, len(self.members))
        b = 1
        while b < n:
            b *= 2
        return b

    def _apply(self, table, arrays):
        from ..parallel import apply_window_seq_sharded

        batch = OpBatch(**arrays)
        if self.n_seq == 1:
            out = apply_window(table, batch)
        else:
            out = apply_window_seq_sharded(table, batch, self.mesh)
        # compact after every pool dispatch: remove-heavy histories
        # otherwise accumulate dead segments until they overflow a
        # pool that could easily hold the live text (the primary
        # ladder's _grow compacts per chunk for the same reason)
        return compact(out)

    def _replay_all(self, streams) -> None:
        """Rebuild the pool table and re-replay every member's stream
        (chunk-sized pool dispatches)."""
        if not self.members:
            self._table = None
            return
        table = make_table(self._bucket(), self.capacity)
        self._table = _replay_chunked(
            self._apply, table,
            {row: streams[slot].ops
             for row, slot in enumerate(self.members)},
            chunk=BucketLadder.replay_chunk(self.capacity),
        )
        self.applied_upto = {
            slot: len(streams[slot].ops) for slot in self.members
        }
        _M_POOL_MEMBERS.set(len(self.members))
        _M_POOL_WATERMARK.set(sum(self.applied_upto.values()))

    def admit(self, slots: list, streams) -> list:
        """Admit sidecar slots; returns the slots that FAILED (exceed
        even pooled capacity) and were rolled back out."""
        for slot in slots:
            if slot not in self.row_of:
                self.row_of[slot] = len(self.members)
                self.members.append(slot)
        self._replay_all(streams)
        failed = self.overflowed_slots()
        if failed:
            for slot in failed:
                self.remove(slot)
            self._replay_all(streams)
        return failed

    def remove(self, slot: int) -> None:
        """Bookkeeping only — the table still holds the removed row's
        data and flags at the OLD indices. Callers MUST follow with
        rebuild()/ _replay_all() before the next read or dispatch, or
        remaining members read the wrong rows and stale overflow flags
        evict innocent documents."""
        if slot not in self.row_of:
            return
        row = self.row_of.pop(slot)
        self.applied_upto.pop(slot, None)
        self.members.pop(row)
        for s2, r2 in self.row_of.items():
            if r2 > row:
                self.row_of[s2] = r2 - 1

    def rebuild(self, streams) -> None:
        self._replay_all(streams)

    def dispatch_pending(self, streams) -> list:
        """Apply every member's un-applied canonical-stream tail (past
        its watermark) in one dispatch; returns slots that overflowed
        the pool. Tails a rebuild already subsumed are empty here, so
        calling this at any point after any mix of rebuilds and
        incremental dispatches is exactly-once by construction."""
        if self._table is None:
            return []
        if _SITE_POOL_DISPATCH.fire(tier="seq") is not None:
            # deferred: tails stay past the watermark and apply whole
            # at the next settle — exactly-once by construction
            _M_POOL_FAULTS.labels(tier="seq", op="dispatch").inc()
            return []
        from ..ops.host_bridge import coalesce_noops

        pending = {}
        upto = {}
        for slot, row in self.row_of.items():
            tail = streams[slot].ops[self.applied_upto.get(slot, 0):]
            if tail:
                pending[row] = coalesce_noops(tail)
                upto[slot] = len(streams[slot].ops)
        if not pending:
            return []
        depth = sum(len(ops) for ops in pending.values())
        self.dispatch_count += 1
        self.last_dispatch_depth = depth
        _M_POOL_DISPATCH.inc()
        _M_POOL_DEPTH.set(depth)
        arrays = _pack_rows(self._table.docs, pending)
        self._table = self._apply(self._table, arrays)
        self.applied_upto.update(upto)
        _M_POOL_WATERMARK.set(sum(self.applied_upto.values()))
        return self.overflowed_slots()

    def prewarm(self) -> None:
        """Compile the pool's dispatch programs before any admission:
        the first-admission table (row bucket 1, pool capacity) at
        both window shapes the pool dispatches — the incremental
        ``dispatch_pending`` floor bucket and the ``_replay_all``
        chunk bucket — plus the compact that follows every pool
        dispatch. This covers the COMMON first overflow recovery (one
        slot overflows a settle, its tail stays under the floor
        bucket), which used to stall the settle boundary 20-40s on
        the real chip. Shapes beyond that still compile on admission
        and are unbounded by construction: a multi-slot same-settle
        admission builds a wider row bucket, a pending tail past the
        floor packs a higher window bucket, and later pow2
        member-growth rebuilds each have their own signature —
        admission is rare and already O(history), so those pay as
        they land (shapecheck's prewarm-coverage rule pins the
        ROOTS reachable, not every shape)."""
        noop = dict(
            kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0,
            client=0, op_id=0, length=0, is_marker=0,
            prop_key=0, prop_val=0, min_seq=0,
        )
        chunk = BucketLadder.replay_chunk(self.capacity)
        for floor in sorted({16, chunk}):
            arrays = _pack_rows(1, {0: [noop]}, bucket_floor=floor)
            # each floor needs BOTH input signatures: a fresh
            # make_table (the first _replay_all chunk) and a table
            # that came out of a pool dispatch, which carries the
            # mesh's committed sharding — a distinct jit signature
            # the single-apply prewarm missed (every incremental
            # dispatch_pending after admission uses it)
            out = self._apply(make_table(1, self.capacity), arrays)
            self._apply(out, arrays)

    def overflowed_slots(self) -> list:
        if self._table is None:
            return []
        flags = np.asarray(self._table.overflow)
        return [self.members[r]
                for r in np.nonzero(flags)[0].tolist()
                if r < len(self.members)]

    def fetch(self):
        return fetch(self._table)


def select_pool(mesh, per_doc_capacity: Optional[int] = None,
                executor: Optional[str] = None,
                route: Optional[str] = None,
                max_capacity: int = 16384,
                plane: str = "merge"):
    """THE route-selection point between the pool tiers — every
    sidecar pool (merge AND tree plane) is constructed here, nowhere
    else. ``plane='tree'`` admits tree documents to the pooled tier
    (``TreeSeqPool``): the tree kernels' per-changeset sorts do not
    decompose over a slot-sharded axis, so that pool's capacity
    unlock is a larger chip-local slab and the merge-plane
    ``route`` knob does not apply. ``executor`` is the tree pool's
    route; string pools run the scan and take none.

    - a mesh with a real ``seq`` axis (size > 1) -> ``SeqShardedPool``
      (one long document's SLOT axis split across devices);
    - a mesh with a sharded ``docs`` axis -> ``MeshShardedPool``
      (many pooled documents spread across shards, live migration);
    - a single-shard mesh -> whichever tier matches its axis names
      (a degenerate ``seq`` mesh keeps the existing SeqShardedPool
      path; a ``docs`` mesh gets a 1-shard MeshShardedPool — both
      apply the plain ``apply_window`` there).

    ``route='seq'|'mesh'`` (constructor arg) or
    ``FFTPU_SIDECAR_POOL=seq|mesh`` (env, arg wins) overrides; an
    unknown value fails LOUDLY, and an override that does not fit the
    mesh fails in the chosen pool's own validation — an emergency
    route change must never silently not happen.

    Default ``per_doc_capacity``: the seq pool multiplies the primary
    ladder top by its seq-shard count (per-doc capacity is the point
    of slot sharding); the mesh pool grants 4x the ladder top (its
    capacity unlock is MEMBER COUNT — per-doc stays chip-local)."""
    if plane not in ("merge", "tree"):
        raise ValueError(
            f"plane={plane!r}: expected 'merge' or 'tree'")
    if plane == "tree":
        from .tree_sidecar import TreeSeqPool

        # executor validation happens against the TREE route registry
        # inside TreeSeqPool (the merge routes don't apply here)
        return TreeSeqPool(
            mesh,
            per_doc_capacity if per_doc_capacity is not None
            else min(max_capacity * 4, 16384),
            executor=executor,
        )
    if executor is not None:
        raise ValueError(
            f"executor={executor!r}: string pools run the scan and "
            "take no route")
    source = "pool_route"
    if route is None:
        route = os.environ.get("FFTPU_SIDECAR_POOL") or None
        source = "FFTPU_SIDECAR_POOL"
    if route is not None and route not in ("seq", "mesh"):
        # BOTH spellings of the escape hatch fail loudly on a typo —
        # a constructor-arg route change must never silently not
        # happen any more than an env one
        raise ValueError(
            f"{source}={route!r}: expected 'seq' or 'mesh'"
        )
    from ..parallel.mesh import DOC_AXIS
    from ..parallel.seq_shard import SEQ_AXIS

    seq_n = mesh.shape.get(SEQ_AXIS, 1) \
        if SEQ_AXIS in mesh.axis_names else 1
    doc_n = mesh.shape.get(DOC_AXIS, 1) \
        if DOC_AXIS in mesh.axis_names else 1
    if route is None:
        if seq_n > 1:
            route = "seq"
        elif doc_n > 1:
            route = "mesh"
        else:
            route = "seq" if SEQ_AXIS in mesh.axis_names else "mesh"
    if route == "mesh":
        from ..parallel.mesh_pool import MeshShardedPool

        if per_doc_capacity is None:
            # capped: per-doc capacity is chip-local here, and the
            # merge step's op_off composite needs
            # capacity * OPOFF_BOUND < 2^31 (segment_table.py)
            per_doc_capacity = min(max_capacity * 4, 8192)
        return MeshShardedPool(mesh, per_doc_capacity)
    if per_doc_capacity is None:
        per_doc_capacity = max_capacity * seq_n
    return SeqShardedPool(mesh, per_doc_capacity)


class TpuMergeSidecar:
    """Batched merge state for up to ``max_docs`` sequence channels.

    One tracked channel (doc slot) = one (document, datastore, channel)
    sequence stream. ``ingest`` consumes the document's sequenced
    envelope stream; ``apply`` flushes accumulated windows to the
    device in a single pipelined dispatch (see the module docstring
    for the pipeline/settle contract).

    ``doc_mesh`` (a ``jax.sharding.Mesh`` with the one axis ``docs``)
    splits the primary plane by document over its devices, as deli's
    documents are partitioned: slot ``s`` lives on device ``s // (
    max_docs / mesh size)``. Every table the sidecar holds and every
    packed buffer it sends is placed with ``parallel.mesh.doc_sharding``
    and every program runs each device's own documents (no collective
    but the settle's one reduced flag). ``None`` keeps the plane on one
    device.
    """

    def __init__(self, max_docs: int = 1024, capacity: int = 1024,
                 compact_every: int = 8, max_capacity: int = 16384,
                 seq_mesh=None, doc_mesh=None,
                 pool_capacity: Optional[int] = None,
                 pool_route: Optional[str] = None,
                 executor: Optional[str] = None,
                 pipeline: Optional[bool] = None,
                 donate: Optional[bool] = None,
                 ladder: Optional[BucketLadder] = None,
                 trace_ops: Optional[bool] = None,
                 breaker=None,
                 heat: Optional[HeatLedger] = None,
                 usage: Optional[HeatLedger] = None,
                 tenant_of: Optional[Callable] = None,
                 attr_clock: Optional[Callable[[], float]] = None):
        self.max_docs = max_docs
        self.capacity = capacity
        self.max_capacity = max_capacity
        if doc_mesh is not None:
            from ..parallel.mesh import DOC_AXIS

            if tuple(doc_mesh.axis_names) != (DOC_AXIS,):
                raise ValueError(
                    f"doc_mesh needs the one axis {DOC_AXIS!r} (got "
                    f"{doc_mesh.axis_names})")
            if max_docs % doc_mesh.size:
                raise ValueError(
                    f"max_docs={max_docs} does not split over the "
                    f"{doc_mesh.size} devices of doc_mesh: each device "
                    "holds max_docs / mesh size documents, so max_docs "
                    "must be a multiple of the mesh size")
        self.doc_mesh = doc_mesh
        self._shards = 1 if doc_mesh is None else doc_mesh.size
        # per-op trace stamping (sidecar:pack / sidecar:settle hops on
        # the ingested messages' trace lists). OPT-IN: it costs one
        # Python append per op per round on the serving path, so the
        # default stays off; the op-trace example and tests enable it.
        if trace_ops is not None:
            self.trace_ops = trace_ops
        else:
            env_trace = os.environ.get("FFTPU_SIDECAR_TRACE")
            if env_trace and env_trace not in ("0", "1"):
                raise ValueError(
                    f"FFTPU_SIDECAR_TRACE={env_trace!r}: expected "
                    "'0' or '1'"
                )
            self.trace_ops = env_trace == "1"
        # messages ingested since the last dispatch / packed into the
        # in-flight round (trace_ops bookkeeping; cleared every round)
        self._round_msgs: list[SequencedMessage] = []
        self._inflight_msgs: list[SequencedMessage] = []
        self.last_settled_msgs: list[SequencedMessage] = []
        # dispatch-loop flight recorder: last N rounds' host-side
        # events, dumped automatically when _settle finds the overflow
        # flag set (the postmortem the PR-2 stall lacked)
        self.flight = FlightRecorder(256, name="sidecar")
        self.last_flight_dump: Optional[str] = None
        # optional qos.CircuitBreaker around device dispatch: repeated
        # dispatch faults open it (apply() then returns 0 and ops stay
        # queued — the growing queued_ops backlog is exactly what the
        # qos pressure signal samples, so ingress starts shedding),
        # and the reset timeout admits probe dispatches that close it
        # when the device recovers. Opening dumps THIS flight
        # recorder: the postmortem of what tripped it is captured at
        # trip time.
        self.breaker = breaker
        if breaker is not None and breaker.on_open is None:
            def _dump_on_open(b) -> None:
                self.last_flight_dump = self.flight.dump_to(
                    reason=f"circuit breaker {b.name!r} opened "
                           f"(last error: {b.last_error!r})")
            breaker.on_open = _dump_on_open
        # dispatch-route knobs (env-overridable escape hatches). The
        # CONSTRUCTOR-ARG spelling of a route typo must be exactly as
        # loud as the env one (the select_pool discipline): an
        # executor='egwalkr' silently serving the backend default is
        # an emergency route change that never happened.
        validate_executor(executor, "executor")
        self.executor = executor or default_executor()
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
        if donate is not None:
            self.donate = donate
        else:
            env_donate = os.environ.get("FFTPU_SIDECAR_DONATE")
            if env_donate:
                if env_donate not in ("0", "1"):
                    raise ValueError(
                        f"FFTPU_SIDECAR_DONATE={env_donate!r}: "
                        "expected '0' or '1'"
                    )
                self.donate = env_donate == "1"
            else:
                # backend-aware like the executor route: the ping-pong
                # wrappers fall back to the plain dispatch on CPU (no
                # donation support), so holding fodder there is pure
                # dead weight (an extra [max_docs, capacity] table)
                import jax

                self.donate = jax.default_backend() == "tpu"
        self.ladder = ladder or BucketLadder()
        # pool tier: past the ladder top, docs move to a mesh pool —
        # slot-sharded (SeqShardedPool, SURVEY §5.7) or doc-sharded
        # (MeshShardedPool, SURVEY §2.9) per the mesh's axes — before
        # any host eviction. ``select_pool`` is the ONE routing point;
        # ``pool_route``/FFTPU_SIDECAR_POOL override it.
        self._pool = None
        if seq_mesh is not None:
            self._pool = select_pool(
                seq_mesh, pool_capacity, route=pool_route,
                max_capacity=max_capacity,
            )
        self.pool_admit_count = 0
        self._table = self._new_table(capacity)
        self._slots: dict[tuple[str, str, str], int] = {}
        # per-document slot index: ingest is called once per sequenced
        # message per document — scanning every tracked channel there
        # was accidentally O(docs) per message (O(docs^2) per window)
        self._doc_slots: dict[str, list[tuple[int, str, str]]] = {}
        # per-document last ingested seq (the at-least-once dedupe
        # guard in ingest)
        self._last_ingested: dict[str, int] = {}
        # per-slot APPLIED-HEAD seq watermark (egwalker route): the
        # max sequence number of any op already dispatched for the
        # slot. build_event_graph judges the criticality of ops whose
        # refseq predates the window against it — conservative (a
        # stale-low head demotes ops to the exact scan suffix, never
        # the reverse), updated at each dispatch AFTER the window's
        # program is compiled against the pre-window value.
        self._slot_head = np.zeros(max_docs, np.int64)
        # the encoded stream is the single canonical per-doc history:
        # grow re-replays it on device, eviction decodes it back into
        # sequenced messages for the scalar replica (no duplicate raw
        # log — advisor r2)
        self._streams: list[DocStream] = []
        self._queued: list[list[dict]] = []
        # slot -> host oracle replica (evicted documents)
        self._host: dict[int, MergeTreeClient] = {}
        # pipeline state: pre-dispatch snapshot + the window it
        # predates (regrow re-applies it), the retired table offered
        # as donation fodder, and whether the in-flight round's
        # overflow flag has been read yet
        self._prev_table = None
        self._last_program = None
        self._dead = None
        self._unsettled = False
        self._applies = 0
        self._compact_every = compact_every
        self.grow_count = 0
        self.evict_count = 0
        # round counters, always on: plain adds, no lock and no
        # registry call on the per-op path (docs/OBSERVABILITY.md
        # "Sidecar round telemetry"). Read their deltas over a window:
        # benchmark/metrics/pack_ms_per_round.* and
        # settle_ms_per_round.* read pack_s, settle_s and rounds.
        # Seconds: ingest_s (inside ingest, timed on one call in
        # obs.profiler.SELF_TIME_EVERY and scaled; ingest_msgs calls),
        # pack_s (host half of a round), graph_s (its
        # _compile_program, part of pack_s), enqueue_s (the round's
        # own _apply_program call; a regrow re-apply runs in _settle),
        # settle_s (the overflow-flag read, i.e. the wait on the
        # device), compact_s. Lanes, summed over rounds: real_ops
        # (real + pooled ops), active_docs (rows with a real op),
        # walker_steps (the prefix walker's while trip count),
        # scan_steps (lane width of each scan dispatched), lane_slots
        # (walker_steps x docs x K + scan_steps x docs). Programs the
        # egwalker route enqueues (every _apply_program, prewarm's and
        # a regrow re-apply's included): walker_launches (one walker
        # program per critical prefix), suffix_launches (one scan
        # program per concurrent suffix). place_s: on a document mesh,
        # the packed buffers' device_put inside each _apply_program
        # (part of enqueue_s; 0 on one device, where the kernels' own
        # calls copy them). Per device of the document mesh (one entry without one), as
        # lists replaced each round so a shallow copy of stats is a
        # snapshot: shard_real_ops, shard_active_docs.
        self.stats = {
            "pack_s": 0.0, "settle_s": 0.0, "rounds": 0,
            "ingest_s": 0.0, "ingest_msgs": 0, "graph_s": 0.0,
            "enqueue_s": 0.0, "compact_s": 0.0, "real_ops": 0,
            "active_docs": 0, "walker_steps": 0, "scan_steps": 0,
            "lane_slots": 0, "walker_launches": 0, "suffix_launches": 0,
            "place_s": 0.0, "shard_real_ops": [0] * self._shards,
            "shard_active_docs": [0] * self._shards,
        }
        # device-trace switch as the last apply/sync read it, and the
        # round the in-flight dispatch belongs to (its settle span)
        self._trace_spans = False
        self._inflight_round = 0
        # device-time attribution plane (obs/heat.py, OPT-IN): when a
        # heat ledger is attached, each round's wall-ms (dispatch
        # start -> that round's settle; consecutive pipelined spans
        # overlap by the next round's pack on purpose) splits across
        # the documents active that round proportional to ops applied.
        # Counts are captured host-side at pack time and charged at
        # the _settle sync boundary — never a mid-loop device read.
        # attr_clock is injectable so differential runs (bench
        # config16) can pin bit-identical tables under a manual clock.
        self.heat = heat
        self.usage = usage
        self.tenant_of = tenant_of
        self._attr_clock = (attr_clock if attr_clock is not None
                            else time.perf_counter)
        self._attr_counts: dict[str, int] = {}
        self._attr_t0 = 0.0
        # slot -> document id (attribution reads counts per doc)
        self._slot_doc: dict[int, str] = {}
        _M_CAPACITY.set(self.capacity)

    # ------------------------------------------------------------------
    # registration + ingest

    def track(self, document_id: str, datastore_id: str,
              channel_id: str) -> int:
        key = (document_id, datastore_id, channel_id)
        if key in self._slots:
            return self._slots[key]
        if len(self._streams) >= self.max_docs:
            raise RuntimeError("sidecar document capacity exhausted")
        slot = len(self._streams)
        self._slots[key] = slot
        self._slot_doc[slot] = document_id
        self._doc_slots.setdefault(document_id, []).append(
            (slot, datastore_id, channel_id)
        )
        self._streams.append(DocStream())
        self._queued.append([])
        _M_TRACKED.set(len(self._streams))
        return slot

    def subscribe(self, server, document_id: str, datastore_id: str,
                  channel_id: str) -> None:
        """Attach to a LocalServer document's broadcaster (the
        sidecar's place in the pipeline: after deli, beside
        scriptorium)."""
        self.track(document_id, datastore_id, channel_id)
        orderer = server.get_orderer(document_id)
        # id(self) in the key: two sidecars (e.g. a shadow validating
        # the other executor route) may track the same channel without
        # silently replacing each other's subscription
        orderer.broadcaster.subscribe(
            f"tpu-sidecar-{id(self)}/{document_id}/{datastore_id}/"
            f"{channel_id}",
            lambda msg: self.ingest(document_id, msg),
        )

    @self_timed("ingest_s", "ingest_msgs")
    def ingest(self, document_id: str, msg: SequencedMessage) -> None:
        """Consume one sequenced message of a document: channel ops for
        tracked channels encode as kernel ops; everything else becomes
        a NOOP that still advances the collab window.

        AT-LEAST-ONCE GUARD: a message at/below the document's last
        ingested sequence number is a duplicate delivery (a chaos-
        duplicated frame, a replayed broker record, an overlapping
        catch-up) and is DROPPED here — without this check a
        duplicate would extend the canonical encoded stream and the
        pool watermark would faithfully apply the op twice (the
        watermark dedupes double DISPATCH of the same stream ops, not
        a double-encoded stream). Same contract as the container's
        inbound seq check (loader/container.py _on_message)."""
        last = self._last_ingested.get(document_id, 0)
        if msg.sequence_number <= last:
            _M_DUP_DROPS.inc()
            return
        self._last_ingested[document_id] = msg.sequence_number
        if self.trace_ops and any(
            slot not in self._host
            for slot, _, _ in self._doc_slots.get(document_id, ())
        ):
            # one entry per ingested message: the pack/settle hops of
            # the round that carries it stamp this object later
            # (dataclasses.replace below shares the traces list, so
            # stamps land on the original message too). Fully-evicted
            # docs skip this — their ops never reach a dispatch round,
            # so buffering them here would grow without bound.
            self._round_msgs.append(msg)
        for slot, ds_id, ch_id in self._doc_slots.get(document_id, ()):
            stream = self._streams[slot]
            envelope = msg.contents if isinstance(msg.contents, dict) else {}
            if (
                msg.type == MessageType.OPERATION
                and envelope.get("kind", "op") == "op"
                and envelope.get("address") == ds_id
                and envelope.get("channel") == ch_id
            ):
                inner = dataclasses.replace(
                    msg, contents=envelope["contents"]
                )
            else:
                inner = dataclasses.replace(
                    msg, type=MessageType.NO_OP, contents=None,
                    client_id=None,
                )
            if slot in self._host:
                # evicted: the live replica is the state; no history
                # retention needed (eviction is one-way)
                self._host[slot].apply_msg(inner)
                continue
            before = len(stream.ops)
            before_payloads = len(stream.payloads)
            try:
                self._encode(stream, inner)
            except ValueError:
                # inexpressible in tensor form (more interned property
                # channels than PROP_CHANNELS, or a 33rd client): this
                # document leaves the device path. Roll the partial
                # encode back so the canonical stream stays exact, then
                # the full-fidelity host replica takes over — seeded by
                # decoding the stream, plus the message that failed.
                del stream.ops[before:]
                del stream.payloads[before_payloads:]
                self._settle()
                self._evict(slot)
                self._host[slot].apply_msg(inner)
                continue
            self._queued[slot].extend(stream.ops[before:])

    @staticmethod
    def _encode(stream: DocStream, inner: SequencedMessage) -> None:
        if inner.type == MessageType.OPERATION:
            stream.add_message(inner)
        else:
            stream.add_noop(inner.minimum_sequence_number)

    # ------------------------------------------------------------------
    # device application (the dispatch pipeline)

    @property
    def queued_ops(self) -> int:
        return sum(len(q) for q in self._queued)

    def apply(self) -> int:
        """Flush all queued windows in one batched dispatch. Returns
        the number of real (non-noop) ops applied.

        Pipelined (the default): this call returns at enqueue — the
        overflow flag of THIS round is read (and recovery run) at the
        next apply/read, inside ``_settle``, so the host can pack the
        next round while the device computes. ``pipeline=False`` keeps
        the old synchronous contract (settle before returning)."""
        if not self._queued or self.queued_ops == 0:
            return 0
        self._trace_spans = device_trace_enabled()
        if self.breaker is not None:
            if not self.breaker.allow():
                # open (or out of probes): ops stay queued; the
                # backlog surfaces through queued_ops -> qos pressure
                return 0
            try:
                real = self._dispatch()
            except Exception as e:  # noqa: BLE001 - breaker records all
                self.breaker.record_failure(e)
                raise
            self.breaker.record_success()
        else:
            real = self._dispatch()
        self._applies += 1
        if self._applies % self._compact_every == 0:
            t0 = time.perf_counter()
            with self._span("compact", self.stats["rounds"]):
                self._table = self._compact(self._table)
            self.stats["compact_s"] += time.perf_counter() - t0
        if not self.pipeline:
            self._settle()
        return real

    def sync(self) -> None:
        """Barrier: settle the in-flight round (overflow recovery,
        deferred pool dispatch). Reads settle implicitly; hosts that
        inspect recovery counters (grow/evict/pool) right after an
        ``apply`` call this first — under the pipelined default those
        advance at the NEXT settle boundary, not inside ``apply``."""
        self._trace_spans = device_trace_enabled()
        self._settle()

    def _span(self, stage: str, rnd: int):
        """The round span ``sidecar:<stage>:r<rnd>``: a profiler
        annotation on the device trace's clock when the switch (as the
        last ``apply``/``sync`` read it) is on; off, one boolean read."""
        if not self._trace_spans:
            return _NO_SPAN
        return device_trace(f"sidecar:{stage}:r{rnd}")

    def prewarm(self, max_bucket: Optional[int] = None) -> float:
        """Compile every shape the (docs, window, capacity) ladder can
        reach — each capacity rung's dispatch at every window bucket
        of the shared ``BucketLadder``, compact, and the pad step
        between rungs — so neither steady traffic (a window crossing
        into a new bucket) nor a regrow ever hits an XLA compile
        mid-serve (the persistent compilation cache makes repeat
        processes skip the cost entirely). Warms
        the ACTIVE executor route, including the donated ping-pong
        form when donation is on. Returns seconds spent."""
        t0 = time.perf_counter()
        noop = dict(
            kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0,
            client=0, op_id=0, length=0, is_marker=0,
            prop_key=0, prop_val=0, min_seq=0,
        )
        dummy_prev = None
        for rung in BucketLadder.capacity_rungs(
                self.capacity, self.max_capacity):
            table = self._new_table(rung)
            for bucket in self.ladder.window_buckets(max_bucket):
                arrays = _pack_rows(
                    self.max_docs, {0: [noop]}, bucket_floor=bucket
                )
                program = self._compile_program(arrays)
                # fresh donation fodder per bucket: the ping-pong jit
                # is a distinct program per window shape, and steady
                # serving dispatches through it — every rung x bucket
                # must compile here, not mid-serve
                dead = self._new_table(rung) if self.donate else None
                table = self._apply_program(table, program, dead)
                if self.executor == "egwalker":
                    # the egwalker route's concurrent SUFFIX rides its
                    # own plain scan jit (never a donating form — its
                    # input is the walker stage's live output), so the
                    # prewarm walk must compile that program per
                    # rung x bucket too; an all-noop prewarm window is
                    # fully critical and would never reach it
                    suffix = np.stack([arrays[f] for f in OpBatch._fields])
                    table = self._apply_program(table, {
                        "egwalker": True, "prefix": None,
                        "suffix": suffix})
            table = self._compact(table)
            if dummy_prev is not None:
                self._pad(dummy_prev, rung)
            dummy_prev = table
        if self._pool is not None:
            self._warm_pool()
        if self.doc_mesh is not None:
            _overflow_any(table.overflow)  # the settle's read
        np.asarray(table.count)  # force completion
        return time.perf_counter() - t0

    def _warm_pool(self) -> None:
        """Walk the pool tier's dispatch programs (see
        ``SeqShardedPool.prewarm`` / ``MeshShardedPool.prewarm``) —
        reached through the attribute-held pool, so both edges are
        declared in shapecheck.PREWARM_INDIRECT."""
        self._pool.prewarm()

    def _on_mesh(self, fn, arg_axes: tuple, donate_argnums: tuple = (),
                  static: tuple = ()):
        """A kernel's function (or its jit's) as one program over the
        documents of ``doc_mesh`` (``parallel.mesh.doc_program``): the
        sidecar is the one place that maps the kernels onto the mesh."""
        from ..parallel.mesh import doc_program

        return doc_program(getattr(fn, "__wrapped__", fn), self.doc_mesh,
                           arg_axes, donate_argnums, static)

    def _new_table(self, capacity: int):
        """A fresh table of the plane: on one device, or made split by
        document on the mesh (never whole on one device first)."""
        if self.doc_mesh is None:
            return make_table(self.max_docs, capacity)
        return self._on_mesh(make_table, (), static=(
            self.max_docs // self._shards, capacity))()

    def _compact(self, table):
        if self.doc_mesh is None:
            return compact(table)
        return self._on_mesh(compact, (0,))(table)

    def _pad(self, table, capacity: int):
        if self.doc_mesh is None:
            return pad_capacity(table, capacity)
        return self._on_mesh(pad_capacity, (0,), static=(capacity,))(table)

    def _place(self, buffers, axis: int):
        """One device_put of a packed buffer, split by document over
        the mesh along ``axis``: a ``sidecar:place`` span of the round
        and ``stats["place_s"]``."""
        from ..parallel.mesh import doc_sharding

        t0 = time.perf_counter()
        with self._span("place", self.stats["rounds"]):
            placed = jax.device_put(buffers,
                                    doc_sharding(self.doc_mesh, axis))
        self.stats["place_s"] += time.perf_counter() - t0
        return placed

    def _compile_program(self, arrays: dict, base_head=None) -> dict:
        """Host half of one dispatch: raw packed arrays for the scan
        route, the event-graph program (critical prefix + concurrent
        suffix) for the egwalker route."""
        if self.executor == "egwalker":
            return build_event_graph(
                arrays, base_head=base_head, k_max=EG_K,
                window_floor=self.ladder.window_floor,
            )
        return arrays

    def _apply_program(self, table, program: dict, dead=None):
        """Device half of one dispatch. ``dead`` (optional) is a
        retired same-shape table donated as the output buffer — the
        double-buffer scheme; see ``apply_window_pingpong``."""
        if dead is not None and (
            dead.capacity != table.capacity or dead.docs != table.docs
        ):
            dead = None  # shape changed (regrow): fodder is useless
        if self.doc_mesh is not None:
            return self._apply_on_mesh(table, program, dead)
        if not program.get("egwalker"):
            batch = OpBatch(**{f: program[f] for f in OpBatch._fields})
            if dead is not None:
                return apply_window_pingpong(dead, table, batch)
            return apply_window(table, batch)
        # egwalker: walker over every doc's critical prefix first,
        # then the per-op scan over the concurrent suffixes (per doc
        # the suffix strictly follows the prefix in sequenced order;
        # across docs the stages touch disjoint lanes). Donation
        # rides the WALKER stage; the suffix input is that stage's
        # live output, so it always dispatches plain.
        if program["prefix"] is not None:
            self.stats["walker_launches"] += 1
            if dead is not None:
                table = apply_window_egwalker_pingpong(
                    dead, table, program["prefix"], K=EG_K
                )
            else:
                table = apply_window_egwalker(
                    table, program["prefix"], K=EG_K
                )
        if program["suffix"] is not None:
            self.stats["suffix_launches"] += 1
            table = apply_suffix(table, program["suffix"])
        return table

    def _apply_on_mesh(self, table, program: dict, dead=None):
        """``_apply_program`` on the document mesh: the same kernels,
        in the same order (a buffer is placed just before the program
        that reads it), each run by every device on its own documents.
        As on one device, the CPU takes the plain forms (it ignores
        donation)."""
        if jax.default_backend() == "cpu":
            dead = None
        if not program.get("egwalker"):
            batch = self._place(
                OpBatch(**{f: program[f] for f in OpBatch._fields}), 0)
            if dead is not None:
                return self._on_mesh(_pingpong_impl, (0, 0, 0), (0,))(
                    dead, table, batch)
            return self._on_mesh(apply_window_impl, (0, 0))(table, batch)
        if program["prefix"] is not None:
            self.stats["walker_launches"] += 1
            prefix = self._place(program["prefix"], 1)
            if dead is not None:
                table = self._on_mesh(_get_jit_pingpong(EG_K), (0, 0, 1),
                                      (0,))(dead, table, prefix)
            else:
                table = self._on_mesh(_get_jit(EG_K), (0, 1))(table, prefix)
        if program["suffix"] is not None:
            self.stats["suffix_launches"] += 1
            suffix = self._place(program["suffix"], 1)
            table = self._on_mesh(_packed_scan, (0, 1))(table, suffix)
        return table

    def _dispatch(self) -> int:
        from ..ops.host_bridge import coalesce_noops

        # chaos seam, BEFORE any mutation: queues are intact, so the
        # raised transient is exactly a failed device dispatch — the
        # breaker (when wired) records it, ops stay queued, and the
        # next apply() retries the identical round
        fault = _SITE_DISPATCH.fire(queued=self.queued_ops)
        if fault is not None:
            _M_DISPATCH_FAULTS.inc()
            raise _SITE_DISPATCH.transient(fault)
        docs = self.max_docs
        rnd = self.stats["rounds"] + 1
        t0 = time.perf_counter()
        # attribution span opens at round start (host clock, opt-in)
        attr_t0 = self._attr_clock() if self.heat is not None else 0.0
        with self._span("pack", rnd):
            # HOST HALF — runs while the device still computes the
            # previous round. Coalesce noop runs at pack time (safe here:
            # the queue is consumed whole), then pad the window to a
            # ladder bucket: the executors compile per (docs, window)
            # shape, and an exact-fit window would recompile on nearly
            # every flush (20-40s each on the real chip). Pow2 bucketing
            # bounds the shape count to log(n).
            packed = [coalesce_noops(q) for q in self._queued]
            attr_counts: dict[str, int] = {}
            if self.heat is not None:
                # per-document real-op counts off the pack metadata (host
                # ints; BEFORE the pool tier zeroes its slots out of the
                # primary window, so pooled docs attribute too). Committed
                # to self._attr_* only after the in-flight round settles
                # below — the mid-dispatch _settle charges the PREVIOUS
                # round from the previous snapshot.
                for slot, ops in enumerate(packed):
                    if not ops:
                        continue
                    n = sum(1 for op in ops if op["kind"] != KIND_NOOP)
                    if n:
                        doc = self._slot_doc.get(slot)
                        if doc is not None:
                            attr_counts[doc] = attr_counts.get(doc, 0) + n
            pool_real = 0
            if self._pool is not None:
                # pooled docs dispatch from their canonical-stream tails at
                # the settle boundary (watermarked, rebuild-proof — see
                # SeqShardedPool.dispatch_pending); their queued copies are
                # counted here and dropped from the primary window
                for slot in list(self._pool.row_of):
                    if packed[slot]:
                        pool_real += sum(
                            1 for op in packed[slot]
                            if op["kind"] != KIND_NOOP
                        )
                        packed[slot] = []
            arrays = _pack_rows(
                docs, {slot: ops for slot, ops in enumerate(packed) if ops},
                bucket_floor=self.ladder.window_floor,
            )
            g0 = time.perf_counter()
            with self._span("graph", rnd):
                program = self._compile_program(
                    arrays, base_head=self._slot_head
                )
            graph_s = time.perf_counter() - g0
            if program.get("egwalker") and "span_splits" in program:
                # host-side scalar (the compiler counts absorbed breaks on
                # the way down); no device read
                _M_SPAN_SPLITS.inc(int(program["span_splits"].sum()))
            if self.executor == "egwalker":
                # advance the applied-head watermarks AFTER compiling: the
                # program's criticality was judged against the pre-window
                # heads (a grow re-apply reuses the compiled program, so
                # it never re-reads these)
                for slot, ops in enumerate(packed):
                    for op in reversed(ops):
                        if op["kind"] != KIND_NOOP:
                            if op["seq"] > self._slot_head[slot]:
                                self._slot_head[slot] = op["seq"]
                            break
            real_lanes = arrays["kind"] != KIND_NOOP
            real = int(np.count_nonzero(real_lanes))
            active = int(np.count_nonzero(real_lanes.any(axis=1)))
            for queue in self._queued:
                queue.clear()
        pack_s = time.perf_counter() - t0
        # lanes the round dispatches: the walker's K lanes per row per
        # step, the scan's one per row per window slot
        if program.get("egwalker"):
            walker = program["walker_steps"]
            suffix = program["suffix"]
            scan = 0 if suffix is None else suffix.shape[2]
        else:
            walker, scan = 0, arrays["kind"].shape[1]
        stats = self.stats
        stats["pack_s"] += pack_s
        stats["rounds"] += 1
        stats["graph_s"] += graph_s
        stats["real_ops"] += real + pool_real
        stats["active_docs"] += active
        stats["walker_steps"] += walker
        stats["scan_steps"] += scan
        stats["lane_slots"] += docs * (walker * EG_K + scan)
        if self.doc_mesh is None:
            shard_real, shard_active = [real], [active]
        else:
            # real ops per document, then per device of the mesh
            per_doc = np.count_nonzero(real_lanes, axis=1).reshape(
                self._shards, -1)
            shard_real = per_doc.sum(axis=1)
            shard_active = np.count_nonzero(per_doc, axis=1)
        stats["shard_real_ops"] = [
            a + int(b) for a, b in zip(stats["shard_real_ops"], shard_real)]
        stats["shard_active_docs"] = [
            a + int(b)
            for a, b in zip(stats["shard_active_docs"], shard_active)]
        _M_ROUNDS.inc()
        _M_OPS.inc(real + pool_real)
        _M_PACK_MS.observe(pack_s * 1000.0)
        # host-side round record (timestamps + already-host scalars
        # only — nothing here may read the device)
        self.flight.record(
            "dispatch", round=rnd, real_ops=real,
            pool_ops=pool_real, pack_ms=round(pack_s * 1000.0, 3),
            capacity=self.capacity, walker_steps=walker,
        )
        if self.trace_ops and self._round_msgs:
            pack_t = time.time()
            for m in self._round_msgs:
                trace_stamp(m.traces, "sidecar", "pack",
                            timestamp=pack_t)
        # SYNC BOUNDARY — read the previous round's overflow flag
        # (recovery if set) before its snapshot is retired below.
        self._settle()
        dead = self._dead
        self._dead = None
        if dead is None and self.donate:
            # no retired buffer yet (first dispatch, or recovery just
            # voided the fodder): donate a fresh zero table so the
            # dispatch still runs the PING-PONG program — prewarm
            # compiles that one (per rung x bucket), and falling back
            # to the never-warmed plain program here would hit a
            # 20-40s serve-time compile on the real chip
            dead = self._new_table(self.capacity)
        # free pre-dispatch snapshot (immutable arrays): if this window
        # overflows, recovery pads THIS table and re-applies THIS
        # window instead of re-replaying history
        self._prev_table = self._table
        self._last_program = program
        self._unsettled = True
        self._inflight_round = rnd
        # commit this round's attribution snapshot now that the
        # previous round has been charged (in the _settle above)
        if self.heat is not None:
            self._attr_counts = attr_counts
            self._attr_t0 = attr_t0
        # _settle above closed the PREVIOUS round's trace window; this
        # round's messages are now the in-flight set
        if self.trace_ops:
            self._inflight_msgs = self._round_msgs
            self._round_msgs = []
        # the round's enqueue (jit call + transfer of the program's
        # arrays): sidecar:dispatch:r<n> when the device-trace switch
        # is on; either way it forces no host<->device sync (the
        # settle boundary stays the only one)
        e0 = time.perf_counter()
        with self._span("dispatch", rnd):
            self._table = self._apply_program(
                self._prev_table, program, dead if self.donate else None
            )
        stats["enqueue_s"] += time.perf_counter() - e0
        return real + pool_real

    def _settle(self) -> None:
        """The designated host<->device sync boundary of the dispatch
        pipeline: read the in-flight round's overflow flag, run
        recovery if set, flush the deferred pool dispatch, and retire
        the now-dead snapshot as donation fodder for the next round.
        Reads (text/signature/overflowed) and the next dispatch both
        funnel through here; nothing else in the apply loop may force
        a device->host transfer."""
        if self._unsettled:
            self._unsettled = False
            with self._span("settle", self._inflight_round):
                t0 = time.perf_counter()
                overflowed = self._overflow_set()
                settle_s = time.perf_counter() - t0
                self.stats["settle_s"] += settle_s
                _M_SETTLE_MS.observe(settle_s * 1000.0)
                # `overflowed` is a pre-fetched host bool by now — the
                # flight record costs no extra device read
                self.flight.record(
                    "settle", settle_ms=round(settle_s * 1000.0, 3),
                    overflow=overflowed,
                )
                if self.heat is not None and self._attr_counts:
                    # the round's wall-ms (dispatch start -> here) splits
                    # across its active documents proportional to ops —
                    # host math over pre-captured ints at the sanctioned
                    # sync boundary (obs/heat.py owns the formula and the
                    # conservation invariant)
                    round_ms = (self._attr_clock() - self._attr_t0) * 1000.0
                    attribute_round(
                        self.heat, self._attr_counts, round_ms,
                        usage=self.usage, tenant_of=self.tenant_of,
                    )
                    self._attr_counts = {}
                if self.trace_ops and self._inflight_msgs:
                    settle_t = time.time()
                    for m in self._inflight_msgs:
                        trace_stamp(m.traces, "sidecar", "settle",
                                    timestamp=settle_t)
                    self.last_settled_msgs = self._inflight_msgs
                    self._inflight_msgs = []
                if overflowed:
                    _M_RECOVER.inc()
                    # the automatic postmortem: what the dispatch loop did
                    # in the rounds leading up to the overflow
                    self.last_flight_dump = self.flight.dump_to(
                        reason="_settle found the overflow flag set "
                               "(recovery running)")
                    self._recover()
                    # recovery re-applied at a new capacity: retired
                    # buffers of the old shape are useless as fodder
                    self._dead = None
                elif self.donate:
                    self._dead = self._prev_table
                self._prev_table = None
                self._last_program = None
                if self._pool is not None and self._pool.members:
                    # pool tier: apply members' stream tails (the pool
                    # reads its overflow flags on the spot, which is why
                    # its dispatch lives at the sync boundary, not in
                    # _dispatch). Inside the _unsettled branch on purpose:
                    # the pool advances only when a flush is in flight, so
                    # reads stay side-effect-free (no per-read dispatch +
                    # compact) and tier-consistent — ingested-but-never-
                    # applied ops stay invisible on BOTH tiers until the
                    # next apply()
                    for slot in self._pool.dispatch_pending(self._streams):
                        self._evict(slot)  # beyond even pooled capacity
                        # (_evict rebuilds the pool for the survivors)

    # ------------------------------------------------------------------
    # overflow recovery: grow ladder, then seq-sharded pool, then
    # host eviction

    def _recover(self) -> None:
        while True:
            overflowed = np.nonzero(np.asarray(self._table.overflow))[0]
            if overflowed.size == 0:
                return
            if self.capacity * 2 <= self.max_capacity:
                self._grow(self.capacity * 2)
            elif self._pool is not None:
                slots = overflowed.tolist()
                failed = self._admit_to_pool(slots)
                for slot in failed:
                    self._evict(slot)
                return
            else:
                for slot in overflowed.tolist():
                    self._evict(slot)
                return

    def _grow(self, new_capacity: int) -> None:
        """Grow the slab 2x and retry the failed window: pad the
        pre-dispatch snapshot (content-preserving, one kernel) and
        re-apply the SAME window at the new capacity. O(window) rather
        than the old full-history re-replay — the failed dispatch
        never mutated the snapshot (the walker additionally PARKS
        overflowed docs pre-span, which this re-apply supersedes), so
        this is exact; with ``prewarm`` the
        new-capacity shapes are already compiled and a warm regrow
        costs about one steady apply."""
        self.grow_count += 1
        _M_GROW.inc()
        self.capacity = new_capacity
        _M_CAPACITY.set(new_capacity)
        self.flight.record("recover-grow", capacity=new_capacity)
        if self._prev_table is None:  # pragma: no cover - first flush
            self._prev_table = self._new_table(new_capacity)
        else:
            self._prev_table = self._pad(self._prev_table, new_capacity)
        # fresh fodder at the NEW capacity: the re-apply must ride the
        # same (prewarmed) ping-pong program the steady path uses
        self._table = self._apply_program(
            self._prev_table, self._last_program,
            self._new_table(new_capacity) if self.donate else None,
        )

    def _retire_rows(self, slots: list) -> None:
        """Zero the primary-table count/overflow of ``slots`` — the
        one definition every retirement path (pool admission, host
        eviction, straggler re-applies) uses: reads route elsewhere
        for these docs, and a stale overflow flag would re-trigger
        (or wedge) recovery."""
        if not slots:
            return
        count = np.asarray(self._table.count).copy()
        overflow = np.asarray(self._table.overflow).copy()
        for slot in slots:
            count[slot] = 0
            overflow[slot] = 0
        if self.doc_mesh is None:
            count, overflow = jnp.asarray(count), jnp.asarray(overflow)
        else:
            from ..parallel.mesh import doc_sharding

            count, overflow = jax.device_put(
                (count, overflow), doc_sharding(self.doc_mesh))
        self._table = self._table._replace(count=count, overflow=overflow)

    def _admit_to_pool(self, slots: list) -> list:
        """Move slots to the sequence-sharded pool; retire their
        primary rows. Returns slots the pool could not hold."""
        # Already-members can reappear here via the pipelined
        # straggler window: a round packed BEFORE their admission
        # settled re-applies their ops onto the retired primary row,
        # which can re-flag overflow. Their pool state is already
        # current (admission replayed the canonical stream, which had
        # these ops), so they need only the row retirement again —
        # not another O(pool-history) replay, and not another count.
        fresh = [s for s in slots if s not in self._pool.row_of]
        # (the admission's full-stream rebuild advances every member's
        # watermark, so nothing it subsumed can dispatch again)
        failed = self._admit_with_retry(fresh) if fresh else []
        admitted = [s for s in slots if s not in failed]
        newly = len([s for s in fresh if s not in failed])
        self.pool_admit_count += newly
        _M_POOL_ADMIT.inc(newly)
        _M_POOLED.set(len(self._pool.members))
        self.flight.record("recover-pool", admitted=newly,
                           failed=len(failed))
        self._retire_rows(admitted)
        for slot in admitted:
            self._queued[slot].clear()  # replayed from the stream
        return failed

    def _admit_with_retry(self, fresh: list) -> list:
        """Pool admission with the chaos seam in front: a transient
        admission fault (fired BEFORE the pool mutates anything)
        retries once; a second fault degrades the slots to host
        eviction — the last-resort tier that always exists — instead
        of wedging the settle boundary. Served text is identical on
        every tier, so the degradation is invisible to readers."""
        for _attempt in (0, 1):
            fault = _SITE_POOL_ADMIT.fire(slots=len(fresh))
            if fault is None:
                return self._pool.admit(fresh, self._streams)
            _M_POOL_FAULTS.labels(tier="seq", op="admit").inc()
        self.flight.record("recover-pool-admit-degraded",
                           slots=len(fresh))
        return list(fresh)

    def _evict(self, slot: int) -> None:
        """Move one document to a host-side scalar oracle replica —
        full fidelity (arbitrary props, unbounded length), off the
        device batch path."""
        # retire the slot's device state FIRST, and even for an
        # already-evicted doc: reads go to the host replica, and a
        # pipelined round that packed before a prior eviction settled
        # can re-apply window ops onto the retired row — its stale
        # overflow flag would otherwise wedge recovery in a loop
        self._retire_rows([slot])
        if slot in self._host:
            return
        from ..ops.host_bridge import decode_stream

        self.evict_count += 1
        _M_EVICT.inc()
        self.flight.record("recover-evict", slot=slot)
        if self._pool is not None and slot in self._pool.row_of:
            # remove() is bookkeeping only: rebuild HERE so every
            # eviction path (dispatch overflow, ingest's
            # tensor-inexpressible ValueError, pool-admission failure)
            # leaves the remaining members' rows consistent
            self._pool.remove(slot)
            self._pool.rebuild(self._streams)
        obs = MergeTreeClient(f"sidecar-host-{slot}")
        obs.start_collaboration(f"sidecar-host-{slot}")
        self._host[slot] = obs
        _M_HOSTED.set(len(self._host))
        if self._pool is not None:
            _M_POOLED.set(len(self._pool.members))
        self._queued[slot].clear()
        for msg in decode_stream(self._streams[slot]):
            obs.apply_msg(msg)

    # ------------------------------------------------------------------
    # reads (service-side summarization / validation)

    def _slot(self, document_id: str, datastore_id: str,
              channel_id: str) -> int:
        return self._slots[(document_id, datastore_id, channel_id)]

    def text(self, document_id: str, datastore_id: str,
             channel_id: str) -> str:
        self._settle()
        slot = self._slot(document_id, datastore_id, channel_id)
        if slot in self._host:
            return self._host[slot].get_text()
        if self._pool is not None and slot in self._pool.row_of:
            return extract_text(
                self._pool.fetch(), self._streams[slot],
                self._pool.row_of[slot],
            )
        return extract_text(fetch(self._table), self._streams[slot], slot)

    def signature(self, document_id: str, datastore_id: str,
                  channel_id: str) -> tuple:
        self._settle()
        slot = self._slot(document_id, datastore_id, channel_id)
        if slot in self._host:
            return self._host_signature(slot)
        if self._pool is not None and slot in self._pool.row_of:
            return extract_signature(
                self._pool.fetch(), self._streams[slot],
                self._pool.row_of[slot],
            )
        return extract_signature(
            fetch(self._table), self._streams[slot], slot
        )

    def _host_signature(self, slot: int) -> tuple:
        from ..ops.host_bridge import interned_signature

        return interned_signature(self._host[slot], self._streams[slot])

    def host_mode_docs(self) -> int:
        return len(self._host)

    def pooled_docs(self) -> int:
        return len(self._pool.members) if self._pool else 0

    def overflowed(self) -> bool:
        """True only if a document is CURRENTLY wrong (should never
        happen: recovery runs inside the settle boundary)."""
        self._settle()
        return self._overflow_set()

    def _overflow_set(self) -> bool:
        """Whether any document's overflow flag is set: the [docs]
        flags read from the one device, or on a mesh one flag reduced
        on the devices."""
        if self.doc_mesh is None:
            return bool(np.asarray(self._table.overflow).any())
        return bool(_overflow_any(self._table.overflow))
