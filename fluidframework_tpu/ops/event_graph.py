"""Event-graph merge executor — the vectorized Eg-walker route.

"Collaborative Text Editing with Eg-walker" (arXiv 2409.14252) avoids
re-transforming history by walking the CONCURRENT-OP EVENT GRAPH:
at a *critical version* — a version every later op has seen — the
prepared state collapses and ops apply directly to the document;
retreat/advance (re-preparing the state for an op's own version) is
paid only across genuinely concurrent spans. "On Coordinating
Collaborative Objects" (arXiv 1007.5093) frames why this is a legal
route swap: the sequencer fixes the total order, so ANY executor that
replays the sequenced stream to the same state is equivalent.

This module is that idea translated to the batched SoA table world:

1. EVENT GRAPH (:func:`build_event_graph`, host half, runs in the
   sidecar's ``_pack_rows`` pipeline stage): per-op parents/frontier
   arrays in the same [docs, window] SoA layout as the chunk state.
   In a sequenced stream with per-document consecutive seqs, an op's
   causal past is ``{seq <= refseq} ∪ {its own prior ops}``, so its
   frontier is AT MOST two heads: ``parent_seq`` (= refseq, the
   other-client head) and ``parent_own`` (window index of the same
   client's previous op, -1 at a chain start). CRITICAL-VERSION
   DETECTION is then one comparison per op: op *w* by client *c* is
   critical iff ``refseq[w] >= frontier_other[w]`` where
   ``frontier_other`` is the max seq of any prior op from ANOTHER
   client (tracked top-2-by-distinct-client in one pass; the
   pre-window history contributes conservatively through a per-row
   ``base_head`` watermark — the max seq already applied to the doc).
   Fully-sequential traffic — the overwhelming common case in real
   deployments — is critical at every op.

2. CRITICAL PREFIX / CONCURRENT SUFFIX SPLIT: each document's window
   splits at its FIRST non-critical op. The critical prefix takes the
   walker fast path below; the suffix (from the first genuinely
   concurrent op on) is applied by the per-op scan executor
   (``merge_kernel.apply_window_impl`` under :func:`apply_suffix`),
   whose masked visibility pass at each op's ``(refseq, client)`` IS
   the batched-table analogue of Eg-walker's retreat/advance: it
   re-prepares the op's view of the state instead of assuming the
   current one. Sequential docs pay no transform at all; concurrent
   docs pay it only from the point concurrency actually starts.

3. WALKER KERNEL (:func:`apply_window_egwalker`, device half): the
   critical prefix is composed on the host by ONE SHARED span chain
   (:class:`_Chain`, cross-client — every op in a critical span sees
   every earlier one, so an exact own-chain composition generalizes
   to the whole span with no cross-client breaks) and applied in
   macro-steps of up to ``EG_K`` ops. Because every op in the span is
   critical, its view of the span-base state S0 is THE SAME
   full-visibility view (``alive & ~removed``): one view pass + one
   prefix-sum per macro-step, shared by every lane, where a
   non-critical op would need a per-lane [D, K, C] view stack. The
   restructure replays ranks from host ``pred``, cuts boundary
   segments and orders rows by a stable multi-key order, but
   realises the order without a sort: the base rows are
   already in key order, so the ≤ 3K new rows MERGE into place
   (:func:`_merge_rows`, static lane shifts and selects — no
   row-axis gather); the remove/annotate stamp replay also collapses
   — every op sees every earlier in-span remove, so
   first-visible-remover-wins degenerates to first-remover-wins (an
   exclusive cumulative-or over lanes instead of a K-step replay
   loop).

Span breaks (``chunk_start``) happen only where host composition
stops being exact: an anchor strictly inside another in-span op's
text, the ``EG_K`` lane cap, or the narrow aging-collision residue
below. Breaks on min_seq aging are SPLIT instead of
taken (Eg-walker's internal-run splitting, arXiv 2409.14252): an
open-span tombstone that ages out of the stop set is split out of the
anchor walk by the chain itself (``_Chain._locate`` with the
exclusive ``ms`` watermark), and a committed tombstone crossing
min_seq mid-span is resolved exactly by the device's per-lane
``ms_pre`` stop mask — only when an earlier in-span insert shares the
exact anchor coordinate across the aging boundary does the span still
break (same-anchor rank groups would split). Absorbed breaks are
counted per row in ``program["span_splits"]``. Cross-client
visibility never breaks a
critical span: that is where the throughput comes from.

Semantics contract: bit-identical live slot state to the sequential
executor (tests/test_event_graph.py and the fuzz sweeps in
tests/test_walker_differential.py pin it differentially), except
after an overflow: a document whose span restructure would exceed
capacity is flagged and PARKED at its pre-span state, where the scan
keeps applying (the sidecar's snapshot re-apply recovery absorbs the
difference).
"""
from __future__ import annotations

from bisect import bisect_right
from typing import NamedTuple, Optional

import numpy as np

from .bucket_ladder import BucketLadder
from .segment_table import (
    KIND_ANNOTATE,
    KIND_INSERT,
    KIND_NOOP,
    KIND_REMOVE,
    OpBatch,
)

# extra per-op int32 arrays the span compiler emits alongside OpBatch
CHUNK_FIELDS = ("chunk_start", "pred", "ev_cover")

# the rows of the packed prefix: build_event_graph emits the critical
# prefix as ONE [len(PREFIX_FIELDS), D, P] int32 array, these fields
# in this order, so a round sends it in one host->device copy and the
# walker program splits it by static index
PREFIX_FIELDS = OpBatch._fields + CHUNK_FIELDS

# The sidecar's executor routes — ONE registry (the sidecar validates
# against it; docs/PERF.md "Executor routes"). The backend picks one
# (tpu_sidecar.default_executor); string pools always run the scan.
EXECUTOR_ROUTES = ("scan", "egwalker")

# Walker macro-step lane count. Must be <= 31 (the ev_cover bitmask is
# int32); 16 amortizes a step over many ops while keeping the
# [D, C+3K, K] stamp pass bounded. A static program-selection
# constant, not a per-dispatch shape (the LADDERED_CALLS discipline:
# prewarm walks it).
EG_K = 16


def validate_executor(route: Optional[str], source: str,
                      routes: tuple = EXECUTOR_ROUTES) -> None:
    """Loud-on-typo executor validation — the select_pool discipline:
    an emergency route change must never silently not happen.
    ``routes`` defaults to the merge plane's registry; the tree
    serving plane validates against its own
    (ops/tree_apply.TREE_EXECUTOR_ROUTES) through the same gate."""
    if route is not None and route not in routes:
        raise ValueError(
            f"{source}={route!r}: expected one of "
            f"{'|'.join(repr(r) for r in routes)}"
        )


class EventGraph(NamedTuple):
    """SoA event-graph of one dispatch window, all arrays
    [docs, window] (int32 unless noted) — the parents/frontier view
    the walker route is planned from."""

    parent_seq: np.ndarray      # other-client parent head (= refseq)
    parent_own: np.ndarray      # window index of own prior op, -1
    frontier_other: np.ndarray  # max prior other-client seq (+ history)
    critical: np.ndarray        # 1 iff the op saw everything before it
    prefix_len: np.ndarray      # [docs] critical-prefix length


# ======================================================================
# host half: graph construction + critical-span composition


def _graph_arrays(kind, seq, refseq, client, base_head):
    """One pass per active row: frontier/parents/criticality. Seqs
    ascend in stream order, so the max-other-client-seq frontier is a
    top-2-by-distinct-client running pair; ``base_head`` folds the
    pre-window history in conservatively (treated as another client's
    head: an op must have seen ALL applied history to stay critical —
    a same-client burst straddling a dispatch boundary re-qualifies
    one op later, which costs speed, never correctness)."""
    D, W = kind.shape
    parent_seq = np.array(refseq, np.int32)
    parent_own = np.full((D, W), -1, np.int32)
    frontier_other = np.zeros((D, W), np.int32)
    critical = np.ones((D, W), np.bool_)
    active = np.flatnonzero((kind != KIND_NOOP).any(axis=1))
    for d in active:
        top1_seq = int(base_head[d])
        top1_cli = -1
        top2_seq = int(base_head[d])
        last_own: dict[int, int] = {}
        for w in range(W):
            if kind[d, w] == KIND_NOOP:
                continue
            c = int(client[d, w])
            s = int(seq[d, w])
            other = top2_seq if c == top1_cli else top1_seq
            frontier_other[d, w] = other
            parent_own[d, w] = last_own.get(c, -1)
            critical[d, w] = int(refseq[d, w]) >= other
            if c == top1_cli:
                top1_seq = s
            else:
                top2_seq = top1_seq
                top1_seq = s
                top1_cli = c
            last_own[c] = w
    return parent_seq, parent_own, frontier_other, critical


class _Seg:
    """One segment of a client's own-view composition. ``base_len`` is
    the span's width in the client's FROZEN BASE VIEW (what the device
    resolves against — own-removed base text keeps counting there
    until the chunk materializes); ``view_len`` is its width in the
    client's CURRENT own view; ``ev_k`` >= 0 marks own in-chunk insert
    text (zero base width); ``rm_seq`` records the sequence number of
    the in-chunk remove that zeroed this segment's view (None = never
    removed) — the event-splitting walkers use it to age tombstone
    segments out of the anchor walk (``_locate`` with ``ms``)."""

    __slots__ = ("base_len", "view_len", "ev_k", "rm_seq")

    def __init__(self, base_len, view_len, ev_k=-1):
        self.base_len = base_len
        self.view_len = view_len
        self.ev_k = ev_k
        self.rm_seq = None


class _Chain:
    """A client's own-op composition within the open chunk."""

    def __init__(self, refseq: int):
        self.refseq = refseq
        self.segs: list[_Seg] = []  # implicit infinite base tail after

    def _locate(self, pos: int, ms=None):
        """Own-view pos -> (seg index, offset, base coord). The walk
        stops at the FIRST zero-view segment once the position is
        consumed (a sequenced insert tie-breaks BEFORE zero-width
        slots at its point — breakTie, seq > slot seq always on the
        sequenced path) — UNLESS ``ms`` is given and the segment is an
        in-chunk tombstone whose remove has aged at/below it
        (``rm_seq <= ms``): an aged tombstone leaves the stop set
        (fused_step's ``below`` mask), so the walk passes THROUGH it —
        this is the event split that lets the egwalker span survive
        min_seq aging. Index len(segs) = the infinite base tail."""
        base = 0
        rem = pos
        for i, s in enumerate(self.segs):
            if rem < s.view_len or (rem == 0 and s.view_len == 0):
                if ms is not None and s.view_len == 0 \
                        and s.rm_seq is not None and s.rm_seq <= ms:
                    base += s.base_len
                    continue
                return i, rem, base + (rem if s.ev_k < 0 else 0)
            rem -= s.view_len
            base += s.base_len
        return len(self.segs), rem, base + rem

    def map_insert(self, pos: int, length: int, k: int, ms=None):
        """Place own insert at own-view ``pos``. Returns
        (base_coord, pred, ok); ok False => the anchor falls strictly
        inside own event text (chunk must break). ``ms`` (the
        EXCLUSIVE min_seq watermark for this op — before its own
        min_seq applies, matching the device's ``ms_pre`` cummax)
        ages in-chunk tombstone segments out of the anchor walk."""
        i, off, base = self._locate(pos, ms)
        if off > 0:
            if i < len(self.segs):
                seg = self.segs[i]
                if seg.ev_k >= 0:
                    return 0, -1, False
                tail = _Seg(seg.base_len - off, seg.view_len - off)
                seg.base_len = off
                seg.view_len = off
                self.segs.insert(i + 1, tail)
            else:
                self.segs.append(_Seg(off, off))
            i += 1
        # pred: nearest preceding own event within the zero-base run
        # just before the insertion point (the walk lands right after
        # the own text it consumed)
        pred = -1
        q = i - 1
        while q >= 0 and self.segs[q].base_len == 0:
            if self.segs[q].ev_k >= 0:
                pred = self.segs[q].ev_k
                break
            q -= 1
        self.segs.insert(i, _Seg(0, length, ev_k=k))
        return base, pred, True

    def map_range(self, p1: int, p2: int):
        """Map own-view range [p1, p2) to base coords + fully-covered
        own events. Returns (b1, b2, cover_mask, ok)."""
        i1, o1, b1 = self._locate(p1)
        i2, o2, b2 = self._locate(p2)
        for idx, off in ((i1, o1), (i2, o2)):
            if idx < len(self.segs) and off > 0 \
                    and self.segs[idx].ev_k >= 0:
                return 0, 0, 0, False
        cover = 0
        i, off, _ = self._locate(p1)
        rem = p2 - p1
        while rem > 0 and i < len(self.segs):
            s = self.segs[i]
            avail = s.view_len - off
            if avail > 0:
                take = min(avail, rem)
                if s.ev_k >= 0 and off == 0 and take == s.view_len:
                    cover |= 1 << s.ev_k
                rem -= take
            off = 0
            i += 1
        return b1, b2, cover, True

    def apply_remove(self, p1: int, p2: int, seq=None) -> None:
        """Materialize own remove in the own view (base widths stay —
        the device counts the text until the chunk materializes).
        ``seq`` stamps the zeroed segments' ``rm_seq`` so a later
        ``_locate(..., ms)`` can age them out of the anchor walk."""
        for p in (p2, p1):  # split p2 first so indices stay valid
            i, off, _ = self._locate(p)
            if off > 0 and i < len(self.segs):
                seg = self.segs[i]
                assert seg.ev_k < 0, "event split rejected earlier"
                tail = _Seg(seg.base_len - off, seg.view_len - off)
                seg.base_len = off
                seg.view_len = off
                self.segs.insert(i + 1, tail)
            elif off > 0:
                self.segs.append(_Seg(off, off))
        i, off, _ = self._locate(p1)
        rem = p2 - p1
        while rem > 0 and i < len(self.segs):
            s = self.segs[i]
            if s.view_len:
                take = min(s.view_len - off, rem)
                if off == 0:
                    rem -= s.view_len if s.view_len <= rem else rem
                    s.view_len = max(0, s.view_len - take)
                    if s.view_len == 0:
                        s.rm_seq = seq
                else:  # pragma: no cover - boundaries were split
                    rem -= take
            off = 0
            i += 1


def _compile_span_row(out, chunk_start, pred, ev_cover, span_splits,
                      d: int, k_max: int) -> None:
    """Compose one document's critical prefix into spans with ONE
    shared chain (:class:`_Chain`'s own-op composition, applied
    span-wide: every op is critical, so every earlier in-span
    op is visible to it and the composition is exact cross-client).
    Rewrites positions into span-base coordinates in place and emits
    chunk_start/pred/ev_cover.

    EVENT SPLITTING (the Eg-walker internal-run split, arXiv
    2409.14252 §"splitting items", translated to the span chain):
    where a per-client composition would break on min_seq aging, this
    compiler SPLITS THE EVENT and keeps composing —

    - an OPEN-SPAN remove aging into ``below``: the aged tombstone
      segment is split out of the anchor walk by ``_Chain._locate``'s
      ``ms`` threading (the walk passes through it, exactly as the
      sequential executor's stop mask passes an aged tombstone), so
      no break is needed at all;
    - a COMMITTED (pre-span) tombstone aging before an insert: the
      device's per-lane ``ms_pre`` stop mask resolves the insert's
      anchor slot exactly, so the span survives UNLESS an earlier
      in-span insert shares the same anchor base coordinate — only
      then do the two inserts land in different same-anchor rank
      groups (pre-aging: the tombstone slot; post-aging: the next
      live row) and the device cannot replay their relative order, so
      the span breaks (the narrow residue of the seed-90007 class).

    Every absorbed would-be break counts into ``span_splits[d]`` (each
    one a walker step saved). Breaks that remain: the ``k_max`` lane cap, an
    anchor strictly inside another in-span op's text, and the
    same-coordinate aging collision above."""
    kind = out["kind"]
    W = kind.shape[1]
    chain = _Chain(0)
    chunk: list[int] = []
    base_w = 0
    ms_run = 0
    ms_global = 0
    ms_base = 0
    ms_counted = 0
    rm_committed: list[int] = []   # remove seqs of CLOSED spans
    rm_open: list[int] = []        # remove seqs in the open span
    ins_coords: set = set()        # base coords of in-span inserts

    def fresh(w: int) -> None:
        nonlocal chain, chunk, base_w, ms_run, ms_base, ms_counted
        chunk_start[d, w] = 1
        chain = _Chain(0)
        chunk = []
        base_w = w
        ms_run = 0
        ms_base = ms_global
        ms_counted = ms_global
        rm_committed.extend(rm_open)  # stays seq-sorted: stream order
        rm_open.clear()
        ins_coords.clear()

    def committed_aged(lo: int) -> bool:
        """Did min_seq cross a committed remove's seq since ``lo``?"""
        return ms_global > lo and \
            bisect_right(rm_committed, ms_global) > \
            bisect_right(rm_committed, lo)

    fresh(0)
    for w in range(W):
        kd = kind[d, w]
        if kd == KIND_NOOP:
            if len(chunk) >= k_max:
                fresh(w)
            chunk.append(w)
            ms_run = max(ms_run, int(out["min_seq"][d, w]))
            ms_global = max(ms_global, int(out["min_seq"][d, w]))
            continue
        ms_k = max(ms_run, int(out["min_seq"][d, w]))

        def must_break() -> bool:
            if len(chunk) >= k_max:
                return True
            # the aging-collision residue: a committed tombstone
            # crossed min_seq since the span opened AND an earlier
            # in-span insert anchors at the very coordinate this
            # insert would map to — their same-anchor rank groups
            # split across the aged tombstone, which the device
            # cannot order (probe is non-mutating; ms_run is the
            # exclusive watermark, matching the device's ms_pre)
            if kd == KIND_INSERT and committed_aged(ms_base):
                probe = chain._locate(
                    int(out["pos1"][d, w]), ms_run)[2]
                if probe in ins_coords:
                    return True
            return False

        if must_break():
            fresh(w)
        else:
            # count the span breaks event-splitting absorbed (each
            # would have been a fresh() under a plain per-client
            # composition's aging conditions): an open-span tombstone
            # aging out of the anchor walk, or a committed tombstone
            # crossing min_seq before an insert without a coordinate
            # collision
            if rm_open and rm_open[0] <= ms_k:
                span_splits[d] += 1
                while rm_open and rm_open[0] <= ms_k:
                    rm_committed.append(rm_open.pop(0))
                # one aging event = one absorbed break: the seqs just
                # moved must not re-count through the insert-crossing
                # branch below
                ms_counted = max(ms_counted, ms_k)
            if kd == KIND_INSERT and committed_aged(ms_counted):
                span_splits[d] += 1
                ms_counted = ms_global
        if kd == KIND_INSERT:
            b, pr, ok = chain.map_insert(
                int(out["pos1"][d, w]),
                int(out["length"][d, w]), w - base_w, ms_run)
            if not ok:
                fresh(w)
                b, pr, ok = chain.map_insert(
                    int(out["pos1"][d, w]),
                    int(out["length"][d, w]), 0, ms_run)
                assert ok
            out["pos1"][d, w] = b
            pred[d, w] = pr
            ins_coords.add(b)
        else:
            p1 = int(out["pos1"][d, w])
            p2 = int(out["pos2"][d, w])
            b1, b2, cover, ok = chain.map_range(p1, p2)
            if not ok:
                fresh(w)
                b1, b2, cover, ok = chain.map_range(p1, p2)
                assert ok
            out["pos1"][d, w] = b1
            out["pos2"][d, w] = b2
            ev_cover[d, w] = cover
            if kd == KIND_REMOVE:
                chain.apply_remove(p1, p2, int(out["seq"][d, w]))
                rm_open.append(int(out["seq"][d, w]))
        chunk.append(w)
        ms_run = ms_k
        ms_global = max(ms_global, int(out["min_seq"][d, w]))


def walker_steps(chunk_start: np.ndarray, K: int) -> int:
    """Trip count of the walker's ``while`` loop over a prefix program,
    from the host's ``chunk_start`` alone (no device read).

    The device's take rule (``_walker_step`` over ``_gather_ops``):
    from a row's cursor, take up to the first lane k >= 1 that starts
    a span, else K lanes; lanes past the window count as span starts.
    So a span of L lanes (from one start to the next, or to the
    window's end) costs its row ceil(L / K) steps, and the loop runs
    until the row with the most steps has passed the window. Exact
    unless a document overflows: an overflowed row parks at once, so
    the count is then an upper bound."""
    D, P = chunk_start.shape
    if D == 0 or P == 0:
        return 0
    starts = chunk_start > 0
    starts[:, 0] = True                 # every cursor starts at lane 0
    rows, cols = np.nonzero(starts)     # row-major: cols ascend per row
    ends = np.append(cols[1:], P)
    ends[np.append(rows[1:] != rows[:-1], True)] = P
    steps = np.zeros(D, np.int64)
    np.add.at(steps, rows, -(-(ends - cols) // K))
    return int(steps.max())


def build_event_graph(arrays: dict, base_head=None, k_max: int = EG_K,
                      window_floor: int = 16) -> dict:
    """[D, W] OpBatch field arrays -> the egwalker dispatch program.

    Returns ``{"egwalker": True, "k": k_max, "prefix": ..., "suffix":
    ..., "graph": EventGraph, "span_splits": ..., "walker_steps":
    ...}``: ``prefix`` holds every document's
    critical prefix as one packed [len(PREFIX_FIELDS), D, P] int32
    array (positions rewritten to span-base coordinates +
    CHUNK_FIELDS, window pow2-bucketed through the BucketLadder so
    compile counts stay laddered), ``suffix`` the raw remainder from
    each document's first non-critical op on, packed the same way as
    [len(OpBatch._fields), D, S] (left-aligned, bucketed; None when
    every op is critical — the common case). ``base_head``
    [D] is the max sequence number already applied per row (0 /
    omitted = a fresh table); it only gates the criticality of ops
    whose refseq predates the window, conservatively. ``walker_steps``
    is the prefix walker's ``while`` trip count (:func:`walker_steps`;
    0 without a prefix).
    """
    assert 1 <= k_max <= 31
    kind = np.array(arrays["kind"], np.int32)
    D, W = kind.shape
    raw = {f: np.array(arrays[f], np.int32) for f in OpBatch._fields}
    if base_head is None:
        base_head = np.zeros(D, np.int64)
    parent_seq, parent_own, frontier_other, critical = _graph_arrays(
        kind, raw["seq"], raw["refseq"], raw["client"], base_head)

    # split index per row: the first non-critical REAL op (noops are
    # trivially critical — they carry only a min_seq advance)
    lane = np.arange(W, dtype=np.int64)[None]
    bad = np.where(~critical & (kind != KIND_NOOP), lane, W)
    prefix_len = bad.min(axis=1).astype(np.int32) if W else \
        np.zeros(D, np.int32)
    graph = EventGraph(parent_seq, parent_own, frontier_other,
                       critical.astype(np.int32), prefix_len)
    ladder = BucketLadder(window_floor=window_floor)

    # per-row count of would-be span breaks event-splitting absorbed
    # (feeds egwalker_span_splits_total)
    span_splits = np.zeros(D, np.int32)
    program: dict = {"egwalker": True, "k": k_max, "graph": graph,
                     "prefix": None, "suffix": None,
                     "span_splits": span_splits, "walker_steps": 0}
    max_p = int(prefix_len.max()) if D else 0
    if max_p > 0:
        P = ladder.window_bucket(max_p)
        n = min(P, W)
        valid = lane[:, :n] < prefix_len[:, None]
        # every field is written into its row of the one packed buffer
        packed = np.empty((len(PREFIX_FIELDS), D, P), np.int32)
        pref = dict(zip(PREFIX_FIELDS, packed))     # [D, P] views
        for f in OpBatch._fields:
            pref[f].fill(KIND_NOOP if f == "kind" else 0)
            np.copyto(pref[f][:, :n], raw[f][:, :n], where=valid)
        chunk_start = pref["chunk_start"]
        pred = pref["pred"]
        ev_cover = pref["ev_cover"]
        chunk_start.fill(0)
        pred.fill(-1)
        ev_cover.fill(0)
        has_real = (pref["kind"] != KIND_NOOP).any(axis=1)
        # idle rows need no chain analysis: boundary every k_max lanes
        chunk_start[~has_real, ::k_max] = 1
        for d in np.flatnonzero(has_real):
            _compile_span_row(pref, chunk_start, pred, ev_cover,
                              span_splits, int(d), k_max)
        program["prefix"] = packed
        program["walker_steps"] = walker_steps(chunk_start, k_max)

    suf_len = (W - prefix_len).astype(np.int64)
    max_s = int(suf_len.max()) if D else 0
    if max_s > 0:
        S = ladder.window_bucket(max_s)
        suffix = np.zeros((len(OpBatch._fields), D, S), np.int32)
        suf = dict(zip(OpBatch._fields, suffix))    # [D, S] views
        suf["kind"].fill(KIND_NOOP)
        for d in np.flatnonzero(suf_len > 0):
            p = int(prefix_len[d])
            n = W - p
            for f in OpBatch._fields:
                suf[f][d, :n] = raw[f][d, p:W]
        program["suffix"] = suffix
    return program


# ======================================================================
# device half: the walker macro-step

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from .merge_kernel import apply_window_impl  # noqa: E402
from .merge_step import state_to_table, table_to_state  # noqa: E402
from .segment_table import (  # noqa: E402
    NOT_REMOVED,
    PROP_CHANNELS,
    SegmentTable,
)

BIG = jnp.int32(2**30)


def _gather_ops(ops_w: dict, cursor: jnp.ndarray, K: int) -> dict:
    """Slice the next K ops per doc from [D, W] arrays. Beyond-window
    lanes read as NOOP chunk starts (they stop the take)."""
    W = ops_w["kind"].shape[1]
    with jax.named_scope("gather_ops"):
        idx = cursor[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
        cidx = jnp.minimum(idx, W - 1)
        out = {
            f: jnp.take_along_axis(a, cidx, axis=1)
            for f, a in ops_w.items()
        }
        off_end = idx >= W
        out["kind"] = jnp.where(off_end, KIND_NOOP, out["kind"])
        out["chunk_start"] = jnp.where(
            off_end, 1, out["chunk_start"]
        )
    return out


def _chunk_state(table: SegmentTable) -> dict:
    st = table_to_state(table)
    # doc-scalar fields flat [D] in this executor
    for f in ("count", "min_seq", "overflow"):
        st[f] = st[f][..., 0]
    return st


def _chunk_unstate(st: dict) -> SegmentTable:
    for f in ("count", "min_seq", "overflow"):
        st[f] = st[f][..., None]
    return state_to_table(st, SegmentTable)


# the per-row fields a walker step rewrites (the rest are per document)
_ROW_FIELDS = ("length", "seq", "client", "removed_seq", "removers",
               "op_id", "op_off", "is_marker") + tuple(
                   f"prop{c}" for c in range(PROP_CHANNELS))


def _shift_right(x, s: int):
    """x[:, i - s] along the row axis, zero-filled: a static lane
    shift, no gather."""
    if s == 0:
        return x
    if s >= x.shape[1]:
        return jnp.zeros_like(x)
    return jnp.pad(x[:, :x.shape[1] - s], ((0, 0), (s, 0)))


def _merge_rows(base: list, extra: list, slot, minor, K: int) -> list:
    """The first C rows of the stable sort of ``base`` and ``extra``
    rows by (slot, minor), with neither a sort nor a row-axis gather.

    ``base`` holds [D, C] fields of rows already in key order (row j
    has the key (j, K)); ``extra`` the same fields for N more rows,
    [D, N], keyed by ``slot``/``minor`` [D, N]. An extra row's
    destination counts the rows that sort before it ([D, N, N] and
    [D, N] compares); base row j moves right by the number of extra
    rows that sort before it, a(j) — monotone in j, so a log-depth
    shifter moves all base rows by static lane shifts and selects, most
    significant bit first (after each stage the rows' positions still
    ascend, so no two rows ever meet); and each extra row lands through
    a one-hot select over a lane-dense [D, N, C] mask. Rows that sort
    past C (those of a document that overflows) are dropped, as the
    walker drops them."""
    D, C = base[0].shape
    N = slot.shape[1]
    pos = lax.broadcasted_iota(jnp.int32, (1, 1, C), 2)
    # base rows before extra row t: every j < slot, and row `slot`
    # itself when minor >= K (a tie would put base row `slot`, the lower
    # index, first). Walker keys never tie a base row's (j, K): a valid
    # tail's minor is (2 off + 1) K with off > 0, an event's 2 off K +
    # rank with rank < K, and an invalid row sits at slot C + 1.
    n_base = jnp.minimum(slot + (minor >= K).astype(jnp.int32), C)
    s_a, s_b = slot[:, :, None], slot[:, None, :]
    m_a, m_b = minor[:, :, None], minor[:, None, :]
    t = jnp.arange(N, dtype=jnp.int32)
    before = (s_a < s_b) | (s_a == s_b) & (
        (m_a < m_b) | (m_a == m_b) & (t[:, None] < t[None, :]))
    dest = n_base + jnp.sum(before, axis=1, dtype=jnp.int32)   # [D,N]
    at = dest[:, :, None] == pos                               # [D,N,C]
    hit = jnp.any(at, axis=1)                                  # [D,C]

    # the shift of base row j: extra rows whose base-row count is <= j
    amt = jnp.sum(n_base[:, :, None] <= pos, axis=1, dtype=jnp.int32)
    cols = list(base)
    for b in reversed(range(N.bit_length())):
        step = 1 << b
        go = (amt & step) != 0
        arrive = _shift_right(go, step)
        cols = [jnp.where(arrive, _shift_right(c, step), c) for c in cols]
        # a row that left must not move again from the slot it vacated
        amt = jnp.where(arrive, _shift_right(amt, step),
                        jnp.where(go, 0, amt))
    return [
        jnp.where(hit, jnp.sum(jnp.where(at, x[:, :, None], 0), axis=1,
                               dtype=x.dtype), c)
        for c, x in zip(cols, extra)
    ]


def _walker_step(st: dict, ops: dict, K: int):
    """Apply one critical span of up to K ops per document; what the
    criticality of every op saves is annotated inline. Returns
    (state', take, over)."""
    D, C = st["length"].shape
    kidx = jnp.arange(K, dtype=jnp.int32)[None]            # [1,K]

    with jax.named_scope("walker_take"):
        # ---- take: ops before the next span boundary --------------------
        take_upto = jnp.min(
            jnp.where((ops["chunk_start"] > 0) & (kidx > 0), kidx, K),
            axis=-1,
        )                                                      # [D]
        taken = kidx < take_upto[:, None]                      # [D,K]
        kind = jnp.where(taken, ops["kind"], KIND_NOOP)
        is_ins = kind == KIND_INSERT
        is_rem = kind == KIND_REMOVE
        is_ann = kind == KIND_ANNOTATE
        is_range = is_rem | is_ann

    with jax.named_scope("walker_view"):
        # ---- ONE shared view pass over S0 (the critical fast path) ------
        # Every op in a critical span has seen every seq in S0, so its
        # view is the full-visibility view: all inserts visible, all
        # removals visible => vis = alive & ~removed, identical across
        # lanes. One [D, C] pass + one cumsum replaces a per-lane
        # [D, K, C] view stack. `stop` (insert
        # tie-break eligibility) is the ONLY lane-dependent mask: a lane's
        # `below` watermark is the exclusive running max of earlier taken
        # lanes' min_seq (the ms_pre cummax — the
        # sequential executor applies an op's min_seq AFTER its view
        # pass), so a committed tombstone aging MID-SPAN resolves exactly
        # instead of forcing a span break (the event-splitting win). The
        # mask stays a 1-byte bool [D, K, C]; E/vis stay shared [D, C].
        j = lax.broadcasted_iota(jnp.int32, (D, C), 1)
        count = st["count"][:, None]                           # [D,1]
        alive = j < count
        removed = st["removed_seq"] != NOT_REMOVED
        ms0 = st["min_seq"][:, None]
        inc_ms = lax.cummax(
            jnp.where(taken, ops["min_seq"], 0), axis=1
        )
        ms_pre = jnp.maximum(
            ms0, jnp.concatenate(
                [jnp.zeros((D, 1), jnp.int32), inc_ms[:, :-1]], axis=1
            )
        )                                                      # [D,K]
        below_lane = removed[:, None, :] & (
            st["removed_seq"][:, None, :] <= ms_pre[..., None]
        )                                                      # [D,K,C]
        vis = alive & ~removed
        stop3 = alive[:, None, :] & ~below_lane
        vlen = jnp.where(vis, st["length"], 0)                 # [D,C]
        E = jnp.cumsum(vlen, axis=-1) - vlen
        incl = E + vlen
        total = incl[:, -1]                                    # [D]

        # ---- batched resolve of all K lanes against the shared view -----
        # All searches run on BOOLEAN [D, K, C] masks reduced by argmax
        # (first-True index — exactly a masked min-index,
        # since XLA argmax breaks ties toward the lowest index) and the
        # values at the found index come back through [D, K] gathers on
        # the shared [D, C] prefix sums; the wide intermediates stay 1-byte
        # bools, never int32 [D, K, C] `where` operands.
        E3 = E[:, None, :]                                     # [D,1,C]
        incl3 = incl[:, None, :]
        p1 = ops["pos1"][..., None]                            # [D,K,1]
        p2 = ops["pos2"][..., None]

        def first_true(mask, default):
            """[D,K,C] bool -> ([D,K] first-True index or default, any)."""
            any_ = jnp.any(mask, axis=-1)
            idx = jnp.argmax(mask, axis=-1).astype(jnp.int32)
            return jnp.where(any_, idx, default), any_

        def e_at(idx):
            """E[d, idx[d,k]] — callers gate on the found flag."""
            return jnp.take_along_axis(
                E, jnp.minimum(idx, C - 1), axis=1)

        inside = stop3 & (E3 <= p1) & (p1 < incl3)
        target = inside | (stop3 & (E3 == p1))
        idx_t, t_any = first_true(target, count)
        E_t = e_at(idx_t)
        t_found = t_any & (idx_t < count)
        valid_ins = is_ins & (ops["pos1"] <= total[:, None])
        a_slot = jnp.where(t_found, idx_t, count)              # [D,K]
        a_off = jnp.where(t_found, ops["pos1"] - E_t, 0)

        strict1 = (E3 < p1) & (p1 < incl3)
        i1, s1 = first_true(strict1, C)
        E1 = e_at(i1)
        strict2 = (E3 < p2) & (p2 < incl3)
        i2, s2 = first_true(strict2, C)
        E2 = e_at(i2)
        # junction fallback: first row with E >= p (count if none)
        jn1, _ = first_true(E3 >= p1, count)
        jn2, _ = first_true(E3 >= p2, count)
        r1s = jnp.where(s1, i1, jn1)
        r1o = jnp.where(s1, ops["pos1"] - E1, 0)
        r2s = jnp.where(s2, i2, jn2)
        r2o = jnp.where(s2, ops["pos2"] - E2, 0)

    with jax.named_scope("walker_rows"):
        # ---- event ranks: replay the walk's insertion order -------------
        # (pred comes from the shared span chain, so same-anchor ordering
        # composes across clients)
        ev_valid = valid_ins & taken
        rank = jnp.zeros((D, K), jnp.int32)
        pred = ops["pred"]
        same_anchor = (
            (a_slot[:, :, None] == a_slot[:, None, :])
            & (a_off[:, :, None] == a_off[:, None, :])
        )                                                      # [D,e,t]
        for t in range(K):
            pr = pred[:, t]
            pr_rank = jnp.where(
                pr >= 0,
                jnp.take_along_axis(
                    rank, jnp.maximum(pr, 0)[:, None], axis=1
                )[:, 0] + 1,
                0,
            )                                                  # [D]
            placing = ev_valid[:, t]
            bump = (
                same_anchor[:, :, t]
                & ev_valid
                & (jnp.arange(K)[None] < t)
                & (rank >= pr_rank[:, None])
                & placing[:, None]
            )
            rank = rank + bump.astype(jnp.int32)
            rank = rank.at[:, t].set(jnp.where(placing, pr_rank, 0))

        # ---- cuts (strictly-inside anchors) — verbatim ------------------
        ins_cut = ev_valid & (a_off > 0)
        r1_cut = is_range & taken & s1 & (r1o > 0)
        r2_cut = is_range & taken & s2 & (r2o > 0)
        cut_slot = jnp.concatenate([
            jnp.where(ins_cut, a_slot, jnp.where(r1_cut, r1s, C)),
            jnp.where(r2_cut, r2s, C),
        ], axis=-1)                                            # [D,2K]
        cut_off = jnp.concatenate([
            jnp.where(ins_cut, a_off, jnp.where(r1_cut, r1o, 0)),
            jnp.where(r2_cut, r2o, 0),
        ], axis=-1)
        cut_valid = jnp.concatenate(
            [ins_cut | r1_cut, r2_cut], axis=-1
        )
        twoK = 2 * K
        dup = (
            (cut_slot[:, :, None] == cut_slot[:, None, :])
            & (cut_off[:, :, None] == cut_off[:, None, :])
            & cut_valid[:, :, None] & cut_valid[:, None, :]
            & (jnp.arange(twoK)[None, :, None]
               < jnp.arange(twoK)[None, None, :])
        )                                                      # [D,i,j]
        cut_valid = cut_valid & ~jnp.any(dup, axis=1)
        cut_slot = jnp.where(cut_valid, cut_slot, C)
        cut_off = jnp.where(cut_valid, cut_off, 0)

        same_row = cut_slot[:, :, None] == cut_slot[:, None, :]
        higher = cut_off[:, None, :] > cut_off[:, :, None]
        next_off = jnp.min(
            jnp.where(
                same_row & higher & cut_valid[:, None, :],
                cut_off[:, None, :], BIG,
            ),
            axis=-1,
        )                                                      # [D,2K]
        # parent-row fields for tails: a plain batched gather (not
        # [D, 2K, C] masked reduces — this XLA-only kernel already
        # gathers for rank/win_val); invalid cuts read garbage
        # from a clamped row, which is fine: their keys (slot C+1) park
        # them past every live row.
        cut_clamped = jnp.minimum(cut_slot, C - 1)

        def row_at(field):
            return jnp.take_along_axis(field, cut_clamped, axis=1)

        par_len = row_at(st["length"])
        tail_len = jnp.minimum(next_off, par_len) - cut_off
        # head shortening: base row's new length = min cut offset in it —
        # a scatter-min (duplicate cut slots combine exactly like the
        # masked [D, C, 2K] min-reduce they replace)
        drow = jnp.arange(D, dtype=jnp.int32)[:, None]
        head_len = st["length"].at[drow, cut_clamped].min(
            jnp.where(cut_valid & (cut_slot < C), cut_off, BIG),
            mode="drop",
        )

        # ---- rows: C base rows + 2K tails + K events -------------------
        # The base rows stay where they are: row j keeps slot j and the
        # minor key K, so they are already in key order, and only the 3K
        # tail and event rows ("extra" rows, [D, 3K]) carry keys that
        # _merge_rows places them by. The minor key packs (off, is_base,
        # rank) into one int32 — all three bounded (off < OPOFF_BOUND =
        # 2^17, base 1 bit, rank < K) — lexicographically identical to
        # three separate keys.
        def extra(tail, event):
            return jnp.concatenate([tail, event], axis=-1)     # [D,3K]

        x_slot = extra(jnp.where(cut_valid, cut_slot, C + 1),
                       jnp.where(ev_valid, a_slot, C + 1))
        x_minor = extra((cut_off * 2 + 1) * K,
                        jnp.where(ev_valid, a_off, 0) * 2 * K + rank)

        zeros_k = jnp.zeros((D, K), jnp.int32)
        t_removed = jnp.where(cut_valid, row_at(st["removed_seq"]),
                              NOT_REMOVED)
        b_rows = {f: st[f] for f in _ROW_FIELDS}
        b_rows["length"] = head_len
        x_rows = {
            "length": extra(tail_len,
                            jnp.where(ev_valid, ops["length"], 0)),
            "seq": extra(row_at(st["seq"]), ops["seq"]),
            "client": extra(row_at(st["client"]), ops["client"]),
            "removed_seq": extra(t_removed,
                                 jnp.full((D, K), NOT_REMOVED, jnp.int32)),
            "removers": extra(row_at(st["removers"]),
                              jnp.zeros((D, K), jnp.uint32)),
            "op_id": extra(row_at(st["op_id"]), ops["op_id"]),
            "op_off": extra(row_at(st["op_off"]) + cut_off, zeros_k),
            "is_marker": extra(row_at(st["is_marker"]), ops["is_marker"]),
        }
        for c in range(PROP_CHANNELS):
            x_rows[f"prop{c}"] = extra(row_at(st[f"prop{c}"]), zeros_k)

    with jax.named_scope("walker_stamps"):
        # ---- stamps -----------------------------------------------------
        # A non-critical op would need per-(row, op) visibility and a
        # lexicographic (slot, offset) interval test here; in a critical
        # span every op sees every S0 row, so (a) a base/tail row is
        # stampable iff it is live, not already removed in S0 (an
        # always-visible removal), and non-empty — ONE mask per row
        # shared by every lane — and (b) the interval test collapses into
        # the SHARED view's E-space: a stampable row's absolute extent is
        # [E[slot]+frag_lo, E[slot]+frag_lo+length) and lane k stamps it
        # iff that extent lies inside [pos1, pos2) (positions are
        # span-base = this same E-space; visible extents partition
        # [0, total], so the interval compare is exactly the
        # six-comparison lexicographic test at two comparisons).
        # Event rows stamp only through the host cover bitmask. Masks
        # are [D, K, rows], rows on the minor axis; a base row's E is E
        # itself (its slot is j), a tail's is its parent row's.
        def in_interval(row_lo, length, stampable):
            lo = row_lo[:, None, :]                            # [D,1,n]
            return stampable[:, None, :] & \
                (lo >= ops["pos1"][:, :, None]) & \
                (lo + length[:, None, :] <= ops["pos2"][:, :, None])

        b_raw = in_interval(
            E, head_len,
            alive & (st["removed_seq"] == NOT_REMOVED) & (head_len > 0))
        t_raw = in_interval(
            row_at(E) + cut_off, tail_len,
            cut_valid & (t_removed == NOT_REMOVED) & (tail_len > 0))
        e_raw = (((ops["ev_cover"][:, :, None]
                   >> kidx[:, None, :].astype(jnp.uint32)) & 1) > 0) & \
            ev_valid[:, None, :]                               # [D,K,K]

        rm_lane = (is_rem & taken)[:, :, None]                 # [D,K,1]
        ann_lane = (is_ann & taken)[:, :, None]
        rm_bits = jnp.left_shift(jnp.uint32(1),
                                 ops["client"].astype(jnp.uint32))

        def lane_pick(mask, field, init):
            """[D,K,n] mask, [D,K] field -> [D,n]: ``field`` at the
            LAST True lane of each row, ``init`` where there is none —
            K lane-dense selects in lane (= sequenced) order."""
            out = init
            for k in range(K):
                out = jnp.where(mask[:, k], field[:, k, None], out)
            return out

        def stamp(raw_stamp, rows):
            """Apply the span's removes and annotates to ``rows``."""
            raw_stamp = raw_stamp & (is_range & taken)[:, :, None]
            # first-remover-wins: every op sees every earlier in-span
            # remove, so a K-iteration visibility replay
            # collapses to "the first remove lane to stamp a row owns it;
            # later range ops skip rows an earlier remove took" — one
            # exclusive cumulative-or over the lane axis. So at most ONE
            # lane of rm_eff is set per row, and only on a row not yet
            # removed (a stampable row, or an event row).
            rm_raw = (raw_stamp & rm_lane).astype(jnp.int32)
            prior_rm = jnp.cumsum(rm_raw, axis=1) - rm_raw > 0
            eff = raw_stamp & ~prior_rm
            rm_eff = eff & rm_lane
            ann_eff = eff & ann_lane
            rows["removed_seq"] = lane_pick(rm_eff, ops["seq"],
                                            rows["removed_seq"])
            rows["removers"] = rows["removers"] | lane_pick(
                rm_eff, rm_bits, jnp.uint32(0))
            # LWW per channel: the LAST candidate lane wins
            for c in range(PROP_CHANNELS):
                rows[f"prop{c}"] = lane_pick(
                    ann_eff & (ops["prop_key"][:, :, None] == c),
                    ops["prop_val"], rows[f"prop{c}"])

        stamp(b_raw, b_rows)
        stamp(extra(t_raw, e_raw), x_rows)

    with jax.named_scope("walker_overflow"):
        # ---- overflow ----------------------------------------------------
        adds = (
            ev_valid.astype(jnp.int32)
            + jnp.sum(
                cut_valid.reshape(D, 2, K).astype(jnp.int32), axis=1
            )
        )                                                      # [D,K]
        new_count = count[:, 0] + jnp.sum(adds, axis=-1)
        overflow_now = new_count > C
        keep = ~overflow_now

    with jax.named_scope("walker_merge"):
        merged = _merge_rows([b_rows[f] for f in _ROW_FIELDS],
                             [x_rows[f] for f in _ROW_FIELDS],
                             x_slot, x_minor, K)

    with jax.named_scope("walker_update"):
        out = {
            f: jnp.where(keep[:, None], new, st[f])
            for f, new in zip(_ROW_FIELDS, merged)
        }
        out.update(
            count=jnp.where(keep, new_count, st["count"]),
            min_seq=jnp.maximum(
                st["min_seq"],
                jnp.max(jnp.where(taken, ops["min_seq"], 0), axis=-1),
            ),
            overflow=jnp.where(overflow_now, 1, st["overflow"]),
        )
    return out, take_upto, overflow_now


def _walker_loop(st: dict, ops_w: dict, K: int) -> dict:
    """while_loop over span macro-steps until every doc's cursor
    passes its window (overflowed docs park immediately)."""
    D = st["length"].shape[0]
    W = ops_w["kind"].shape[1]
    cursor0 = jnp.zeros((D,), jnp.int32)

    def cond(carry):
        st_, cursor = carry
        return jnp.any(cursor < W)

    def body(carry):
        st_, cursor = carry
        span = _gather_ops(ops_w, cursor, K)
        st2, take, over = _walker_step(st_, span, K)
        cursor2 = jnp.where(over, W, cursor + take)
        return st2, jnp.minimum(cursor2, W)

    st, _ = lax.while_loop(cond, body, (st, cursor0))
    return st


def _walker_table(table: SegmentTable, prefix, K: int) -> SegmentTable:
    """The walker over a packed prefix, table in and table out: the
    state conversion runs inside the trace, where XLA fuses it into
    the walker program, and the prefix splits by static index."""
    ops_w = {f: prefix[i] for i, f in enumerate(PREFIX_FIELDS)}
    st = _walker_loop(_chunk_state(table), ops_w, K)
    return _chunk_unstate(dict(st))


_jit_cache: dict = {}


def _get_jit(K: int):
    """One cache-fill site per K (jitsan reads this cache for
    compile counting)."""
    if K not in _jit_cache:
        _jit_cache[K] = jax.jit(
            lambda table, prefix: _walker_table(table, prefix, K)
        )
    return _jit_cache[K]


_jit_pingpong_cache: dict = {}


def _get_jit_pingpong(K: int):
    if K not in _jit_pingpong_cache:

        def run(dead: SegmentTable, table: SegmentTable,
                prefix) -> SegmentTable:
            # ``dead`` is donation fodder (a retired table of the
            # output's shapes): all twelve of its buffers may back the
            # output. Never read.
            del dead
            return _walker_table(table, prefix, K)

        _jit_pingpong_cache[K] = jax.jit(run, donate_argnums=(0,))
    return _jit_pingpong_cache[K]


def apply_window_egwalker(table: SegmentTable, prefix: np.ndarray,
                          K: int = EG_K) -> SegmentTable:
    """Apply a compiled critical-prefix program (the packed ``prefix``
    of :func:`build_event_graph`'s output) to the table: one
    host->device copy and one program launch. ``K`` must equal the
    build k_max."""
    return _get_jit(K)(table, jax.device_put(prefix))


def apply_window_egwalker_pingpong(dead: SegmentTable | None,
                                   table: SegmentTable,
                                   prefix: np.ndarray,
                                   K: int = EG_K) -> SegmentTable:
    """Double-buffered twin of :func:`apply_window_egwalker`: DONATES
    ``dead`` (a retired table of the same shape) as the output buffer
    while ``table`` survives as the caller's pre-dispatch snapshot —
    the sidecar's O(window) overflow regrow depends on that snapshot.
    The caller must drop every reference to ``dead``. Degrades to the
    plain dispatch when ``dead`` is None or the backend (CPU) has no
    donation support. The concurrent SUFFIX of an egwalker program
    always dispatches the plain scan jit (its input is this stage's
    output — live, never donatable)."""
    if dead is None or jax.default_backend() == "cpu":
        return apply_window_egwalker(table, prefix, K=K)
    return _get_jit_pingpong(K)(dead, table, jax.device_put(prefix))


def _packed_scan(table: SegmentTable, suffix) -> SegmentTable:
    return apply_window_impl(table, OpBatch(*suffix))


# the concurrent suffix's scan over one packed buffer, a jit of this
# module so the scan route and the pools keep ``apply_window`` as is.
# Named as the scan it runs: XLA calls the program
# ``jit_apply_window_impl``, as it calls the scan route's window.
_packed_scan.__name__ = _packed_scan.__qualname__ = "apply_window_impl"
_jit_suffix = jax.jit(_packed_scan)


def apply_suffix(table: SegmentTable,
                 suffix: np.ndarray) -> SegmentTable:
    """Apply a program's packed concurrent ``suffix`` (the scan over
    each document's ops from its first non-critical one on): one
    host->device copy and one program launch. Never a donating form:
    its input is the walker stage's live output."""
    return _jit_suffix(table, jax.device_put(suffix))


def apply_batch_egwalker(table: SegmentTable, batch: OpBatch,
                         k_max: int = EG_K, base_head=None,
                         window_floor: int = 16) -> SegmentTable:
    """Kernel-level convenience (tests): build the event graph
    for one OpBatch and run the full route — walker over the critical
    prefix, scan over the concurrent suffix."""
    arrays = {f: np.array(getattr(batch, f), np.int32)
              for f in OpBatch._fields}
    program = build_event_graph(arrays, base_head=base_head,
                                k_max=k_max,
                                window_floor=window_floor)
    if program["prefix"] is not None:
        table = apply_window_egwalker(table, program["prefix"],
                                      K=k_max)
    if program["suffix"] is not None:
        table = apply_suffix(table, program["suffix"])
    return table


def compiled_window(table: SegmentTable, prefix: np.ndarray,
                    K: int = EG_K):
    """PUBLIC handle for AOT cost analysis of the walker: the SAME jit
    object ``apply_window_egwalker`` dispatches at this K, with the
    traced argument structure."""
    return _get_jit(K), (table, jnp.asarray(prefix))
