"""Chunked merge executor — up to K sequenced ops per kernel macro-step.

The sequential executor (merge_kernel.apply_window_impl) scans ONE op
per step: a 195-op window costs 195 dependent kernel rounds, so
where launches are costly that fixed overhead IS the runtime, and
the whole table streams through HBM once per op. This executor
applies a CHUNK of consecutive window ops per document in one
macro-step with a near-constant kernel count, so launches and HBM
traffic amortize over the chunk.

Semantics contract: BIT-IDENTICAL live slot state to the sequential
executor (tests/test_merge_chunk.py pins it differentially), except
after a capacity overflow (both executors flag `overflow`; the
sequential one keeps applying post-overflow ops while this one parks
the document — overflowed docs are eviction fodder either way, see
the sidecar's regrow/evict policy). The behavior reproduced is
fused_step's, i.e. the reference's sequenced path: mergeTree.ts
insertingWalk:1723 / breakTie:1705 / markRangeRemoved:1908 /
annotateRange:1864.

Two halves:

1. HOST CHUNK COMPILER (`compile_chunks`) — the observation that makes
   the device side flat (no iteration): within one chunk the only ops
   whose positions depend on other in-chunk ops are ops that can SEE
   them, and visible in-chunk dependencies are overwhelmingly
   SAME-CLIENT chains (a client typing a burst; backspacing over it).
   A client's own chain is pure metadata: its view = (frozen base view
   at its refseq) + its own ops at known own-view positions, so the
   host composes the chain EXACTLY — no table state needed — and
   rewrites each op's positions into frozen-base-view coordinates,
   emitting per op:
   - `pred`: for inserts, the chunk-local index of the own-chain
     insert this op lands immediately after (-1 = lands at its
     anchor's front) — the device replays the walk's insertion order
     from these;
   - `ev_cover`: for ranges, a bitmask of own in-chunk inserts the
     range covers entirely (backspace over one's own burst);
   - `chunk_start`: chunk boundary flags.
   The compiler CLOSES a chunk exactly where host arithmetic stops
   being exact: a cross-client dependency on an in-chunk
   insert/remove the later op can see, a same-client refseq advance
   mid-chain, an anchor strictly inside another op's text, or an
   in-chunk remove whose seq falls at/below a later op's min_seq
   (tie-break-relevant tombstone aging). After a break the offending
   op starts a fresh chunk against materialized state — progress is
   always exact, worst case one op per chunk (= sequential).

2. DEVICE MACRO-STEP (`apply_window_chunked`) — per chunk:
   one per-op view pass + prefix sum over the chunk-start state
   (refseq/min_seq differ per op), one batched position resolve with
   a single fused min-reduce layer (same monotonicity trick as
   fused_step), an unrolled elementwise walk-order replay for events
   sharing an anchor (later sequenced inserts land BEFORE zero-width
   slots at their position — breakTie, since a sequenced op's seq
   always exceeds a slot's), then the restructure as ONE stable
   multi-key `lax.sort` over C base rows + cut tails + insert events
   keyed (slot, offset, is_base, rank). Range stamps are lexicographic
   key-interval tests masked by per-row visibility, with
   first-visible-remover-wins combining replayed elementwise across
   the chunk's removes.
"""
from __future__ import annotations

from bisect import bisect_right

import numpy as np

from .segment_table import (
    KIND_ANNOTATE,
    KIND_INSERT,
    KIND_NOOP,
    KIND_REMOVE,
    OpBatch,
)

# extra per-op int32 arrays the chunk compiler emits alongside OpBatch
CHUNK_FIELDS = ("chunk_start", "pred", "ev_cover")

# Serving-side default chunk length (must be <= 31; 8 is the
# bench-proven sweet spot). Lived in service/tpu_sidecar.py as
# CHUNK_K through PR 7; owned here so the parallel layer's pool can
# route chunked without importing service (the sidecar re-exports).
CHUNK_K = 8


# ======================================================================
# host chunk compiler


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


def compile_chunks(arrays: dict, k_max: int = 8) -> dict:
    """Rewrite [D, W] OpBatch field arrays into chunked form (positions
    in frozen-base-view coordinates) + CHUNK_FIELDS. Pure numpy/host;
    runs at pack time. ``k_max`` caps chunk length (must match the
    device K; <= 31 so ev_cover bitmasks fit int32)."""
    assert 1 <= k_max <= 31
    kind = np.asarray(arrays["kind"])
    D, W = kind.shape
    out = {f: np.array(np.asarray(arrays[f]), np.int32, copy=True)
           for f in OpBatch._fields}
    chunk_start = np.zeros((D, W), np.int32)
    pred = np.full((D, W), -1, np.int32)
    ev_cover = np.zeros((D, W), np.int32)

    # All-NOOP rows (idle documents in a serving dispatch — the common
    # case for the sidecar's sparse windows) need no chain analysis:
    # their chunk pattern is a boundary every k_max lanes, emitted
    # vectorized. The Python compiler loop below then touches only the
    # rows that actually carry ops, so pack-time cost scales with real
    # traffic, not with the doc axis.
    active = np.flatnonzero((kind != KIND_NOOP).any(axis=1))
    idle_mask = np.ones(D, np.bool_)
    idle_mask[active] = False
    chunk_start[idle_mask, ::k_max] = 1

    for d in active:
        chains: dict[int, _Chain] = {}
        chunk: list[int] = []   # window indices of the open chunk
        base_w = 0              # chunk start window index
        ms_run = 0              # running max min_seq within chunk
        ms_global = 0           # max min_seq over ALL ops before w
        ms_base = 0             # ms_global when the chunk opened
        rm_committed: list[int] = []  # remove seqs of CLOSED chunks
        rm_open: list[int] = []       # remove seqs in the open chunk

        def fresh(w):
            nonlocal chains, chunk, base_w, ms_run, ms_base
            chunk_start[d, w] = 1
            chains = {}
            chunk = []
            base_w = w
            ms_run = 0
            ms_base = ms_global
            rm_committed.extend(rm_open)  # stays seq-sorted: stream order
            rm_open.clear()

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
            cli = int(out["client"][d, w])
            ref = int(out["refseq"][d, w])
            ms_k = max(ms_run, int(out["min_seq"][d, w]))

            def must_break():
                if len(chunk) >= k_max:
                    return True
                # Mid-chunk tombstone aging on COMMITTED tombstones:
                # if min_seq advanced past a pre-chunk remove's seq
                # since the chunk opened, this insert's `below` mask
                # (stop-slot eligibility, hence its anchor slot)
                # differs from earlier in-chunk events' — the device's
                # same-anchor breakTie rank group would split across
                # the aged tombstone (seed-90007 class divergence).
                # ms_global excludes op w's own min_seq: the sequential
                # step applies an op's min_seq AFTER its view pass, and
                # the device ms_pre cummax does the same.
                if kd == KIND_INSERT and ms_global > ms_base and \
                        bisect_right(rm_committed, ms_global) > \
                        bisect_right(rm_committed, ms_base):
                    return True
                for i in chunk:
                    ki = kind[d, i]
                    if ki == KIND_NOOP or ki == KIND_ANNOTATE:
                        continue
                    same = int(out["client"][d, i]) == cli
                    seen = same or int(out["seq"][d, i]) <= ref
                    if not same and seen:
                        return True  # cross-client visible ins/rm
                    if ki == KIND_REMOVE and \
                            int(out["seq"][d, i]) <= ms_k:
                        return True  # tombstone ages into "below"
                ch = chains.get(cli)
                if ch is not None and ch.segs and ch.refseq != ref:
                    return True  # frozen base view changed mid-chain
                return False

            if must_break():
                fresh(w)
            chain = chains.get(cli)
            if chain is None:
                chain = chains[cli] = _Chain(ref)
            chain.refseq = ref

            if kd == KIND_INSERT:
                b, pr, ok = chain.map_insert(
                    int(out["pos1"][d, w]),
                    int(out["length"][d, w]), w - base_w)
                if not ok:
                    fresh(w)
                    chain = chains[cli] = _Chain(ref)
                    b, pr, ok = chain.map_insert(
                        int(out["pos1"][d, w]),
                        int(out["length"][d, w]), 0)
                    assert ok
                out["pos1"][d, w] = b
                pred[d, w] = pr
            else:
                p1 = int(out["pos1"][d, w])
                p2 = int(out["pos2"][d, w])
                b1, b2, cover, ok = chain.map_range(p1, p2)
                if not ok:
                    fresh(w)
                    chain = chains[cli] = _Chain(ref)
                    b1, b2, cover, ok = chain.map_range(p1, p2)
                    assert ok
                out["pos1"][d, w] = b1
                out["pos2"][d, w] = b2
                ev_cover[d, w] = cover
                if kd == KIND_REMOVE:
                    chain.apply_remove(p1, p2)
                    rm_open.append(int(out["seq"][d, w]))
            chunk.append(w)
            ms_run = ms_k
            ms_global = max(ms_global, int(out["min_seq"][d, w]))

    out["chunk_start"] = chunk_start
    out["pred"] = pred
    out["ev_cover"] = ev_cover
    return out


# ======================================================================
# device macro-step

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from .segment_table import (  # noqa: E402
    NOT_REMOVED,
    PROP_CHANNELS,
    SegmentTable,
)
from .merge_step import (  # noqa: E402
    state_to_table,
    table_to_state,
)

BIG = jnp.int32(2**30)


def _gather_ops(ops_w: dict, cursor: jnp.ndarray, K: int) -> dict:
    """Slice the next K ops per doc from [D, W] arrays. Beyond-window
    lanes read as NOOP chunk starts (they stop the take)."""
    W = ops_w["kind"].shape[1]
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


def _macro_step(st: dict, ops: dict, K: int):
    """Apply one chunk of up to K ops per document. Returns the new
    state dict + per-doc consumed count [D]."""
    D, C = st["length"].shape
    kidx = jnp.arange(K, dtype=jnp.int32)[None]            # [1,K]

    # ---- take: ops before the next chunk boundary -------------------
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

    # ---- phase A: per-op view pass vs S0 ----------------------------
    j3 = lax.broadcasted_iota(jnp.int32, (D, 1, C), 2)
    count = st["count"][:, None]                           # [D,1]
    length3 = st["length"][:, None, :]
    seq3 = st["seq"][:, None, :]
    client3 = st["client"][:, None, :]
    rseq3 = st["removed_seq"][:, None, :]
    rmrs3 = st["removers"][:, None, :]

    refseq = ops["refseq"][..., None]                      # [D,K,1]
    client = ops["client"][..., None]
    ms0 = st["min_seq"][:, None]                           # [D,1]
    inc_ms = lax.cummax(
        jnp.where(taken, ops["min_seq"], 0), axis=1
    )
    ms_pre = jnp.maximum(
        ms0, jnp.concatenate(
            [jnp.zeros((D, 1), jnp.int32), inc_ms[:, :-1]], axis=1
        )
    )                                                      # [D,K]

    alive = j3 < count[..., None]
    removed = rseq3 != NOT_REMOVED
    below = removed & (rseq3 <= ms_pre[..., None])
    rm_by_viewer = (
        (rmrs3 >> client.astype(jnp.uint32)) & 1
    ).astype(jnp.bool_)
    removal_visible = removed & ((rseq3 <= refseq) | rm_by_viewer)
    insert_visible = (seq3 <= refseq) | (client3 == client)
    vis = alive & ~below & insert_visible & ~removal_visible
    stop = alive & ~below
    vlen = jnp.where(vis, length3, 0)                      # [D,K,C]
    E = jnp.cumsum(vlen, axis=-1) - vlen
    incl = E + vlen
    total = incl[..., -1]                                  # [D,K]

    # ---- batched resolve (single fused min-reduce layer) ------------
    p1 = ops["pos1"][..., None]
    p2 = ops["pos2"][..., None]

    inside = stop & (E <= p1) & (p1 < incl)
    target = inside | (stop & (E == p1))
    idx_t = jnp.min(jnp.where(target, j3, count[..., None]), axis=-1)
    E_t = jnp.min(jnp.where(target, E, BIG), axis=-1)
    t_found = idx_t < count
    valid_ins = is_ins & (ops["pos1"] <= total)
    a_slot = jnp.where(t_found, idx_t, count)              # [D,K]
    a_off = jnp.where(t_found, ops["pos1"] - E_t, 0)

    strict1 = (E < p1) & (p1 < incl)
    i1 = jnp.min(jnp.where(strict1, j3, C), axis=-1)
    E1 = jnp.min(jnp.where(strict1, E, BIG), axis=-1)
    s1 = i1 < C
    strict2 = (E < p2) & (p2 < incl)
    i2 = jnp.min(jnp.where(strict2, j3, C), axis=-1)
    E2 = jnp.min(jnp.where(strict2, E, BIG), axis=-1)
    s2 = i2 < C
    # junction fallback: first row with E >= p (count if none)
    jn1 = jnp.min(jnp.where(E >= p1, j3, count[..., None]), axis=-1)
    jn2 = jnp.min(jnp.where(E >= p2, j3, count[..., None]), axis=-1)
    r1s = jnp.where(s1, i1, jn1)
    r1o = jnp.where(s1, ops["pos1"] - E1, 0)
    r2s = jnp.where(s2, i2, jn2)
    r2o = jnp.where(s2, ops["pos2"] - E2, 0)

    # ---- event ranks: replay the walk's insertion order -------------
    # rank within (anchor) groups; event t lands right after its
    # own-chain pred (host-computed), else at its anchor's front.
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

    # ---- cuts (strictly-inside anchors) -----------------------------
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
    # dedupe identical (slot, off): keep the earliest entry
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

    # per-cut: next cut offset within the same row, and parent fields
    same_row = cut_slot[:, :, None] == cut_slot[:, None, :]
    higher = cut_off[:, None, :] > cut_off[:, :, None]
    next_off = jnp.min(
        jnp.where(
            same_row & higher & cut_valid[:, None, :],
            cut_off[:, None, :], BIG,
        ),
        axis=-1,
    )                                                      # [D,2K]
    # gather parent-row fields for tails (one masked reduce layer)
    cmask = (
        lax.broadcasted_iota(jnp.int32, (D, twoK, C), 2)
        == cut_slot[..., None]
    )

    def row_at(field):
        return jnp.sum(
            jnp.where(cmask, field[:, None, :], 0), axis=-1
        )

    par_len = row_at(st["length"])
    tail_len = jnp.minimum(next_off, par_len) - cut_off
    # head shortening: base row's new length = min cut offset in it
    mincut = jnp.min(
        jnp.where(
            (cut_slot[:, None, :] == j3[:, 0, :, None])
            & cut_valid[:, None, :],
            cut_off[:, None, :], BIG,
        ),
        axis=-1,
    )                                                      # [D,C]
    head_len = jnp.minimum(st["length"], mincut)

    # ---- row tables: C base + 2K tails + K events -------------------
    def rows(base, tail, event):
        return jnp.concatenate([base, tail, event], axis=-1)

    ev_row_valid = ev_valid
    inval_t = jnp.where(cut_valid, cut_slot, C + 1)
    inval_e = jnp.where(ev_row_valid, a_slot, C + 1)

    key_slot = rows(j3[:, 0], inval_t, inval_e)
    key_off = rows(jnp.zeros((D, C), jnp.int32), cut_off,
                   jnp.where(ev_row_valid, a_off, 0))
    key_base = rows(jnp.ones((D, C), jnp.int32),
                    jnp.ones((D, twoK), jnp.int32),
                    jnp.zeros((D, K), jnp.int32))
    key_rank = rows(jnp.zeros((D, C), jnp.int32),
                    jnp.zeros((D, twoK), jnp.int32), rank)

    r_length = rows(head_len, tail_len,
                    jnp.where(ev_row_valid, ops["length"], 0))
    r_seq = rows(st["seq"], row_at(st["seq"]), ops["seq"])
    r_client = rows(st["client"], row_at(st["client"]),
                    ops["client"])
    r_removed = rows(
        st["removed_seq"],
        jnp.where(cut_valid, row_at(st["removed_seq"]),
                  NOT_REMOVED),
        jnp.full((D, K), NOT_REMOVED, jnp.int32),
    )
    r_removers = rows(
        st["removers"].astype(jnp.int32),
        row_at(st["removers"].astype(jnp.int32)),
        jnp.zeros((D, K), jnp.int32),
    )
    r_op_id = rows(st["op_id"], row_at(st["op_id"]), ops["op_id"])
    r_op_off = rows(st["op_off"],
                    row_at(st["op_off"]) + cut_off,
                    jnp.zeros((D, K), jnp.int32))
    r_marker = rows(st["is_marker"], row_at(st["is_marker"]),
                    ops["is_marker"])
    r_props = [
        rows(st[f"prop{c}"], row_at(st[f"prop{c}"]),
             jnp.zeros((D, K), jnp.int32))
        for c in range(PROP_CHANNELS)
    ]
    # fragment extent [start, end) in parent-row offsets, for stamps
    r_frag_lo = rows(jnp.zeros((D, C), jnp.int32), cut_off,
                     jnp.zeros((D, K), jnp.int32))
    r_frag_hi = r_frag_lo + r_length
    r_is_event = rows(jnp.zeros((D, C), jnp.int32),
                      jnp.zeros((D, twoK), jnp.int32),
                      ev_row_valid.astype(jnp.int32))
    ev_bit = rows(jnp.zeros((D, C), jnp.int32),
                  jnp.zeros((D, twoK), jnp.int32),
                  kidx + jnp.zeros((D, K), jnp.int32))
    r_live = rows(
        (j3[:, 0] < count).astype(jnp.int32),
        cut_valid.astype(jnp.int32),
        ev_row_valid.astype(jnp.int32),
    )

    R = C + 3 * K

    # ---- stamps in key space ----------------------------------------
    # per (row, range-op): lexicographic containment of the fragment
    # in [ (r1s,r1o), (r2s,r2o) ), masked by the row's visibility to
    # the op and by first-visible-remover-wins replay.
    ks = key_slot[:, :, None]                              # [D,R,1]
    lo = r_frag_lo[:, :, None]
    hi = r_frag_hi[:, :, None]
    a1s = r1s[:, None, :]                                  # [D,1,K]
    a1o = r1o[:, None, :]
    a2s = r2s[:, None, :]
    a2o = r2o[:, None, :]
    ge_start = (ks > a1s) | ((ks == a1s) & (lo >= a1o))
    le_end = (ks < a2s) | ((ks == a2s) & (hi <= a2o))
    in_interval = ge_start & le_end & (r_is_event[:, :, None] == 0)

    refk = ops["refseq"][:, None, :]
    clik = ops["client"][:, None, :]
    msk = ms_pre[:, None, :]
    rr = r_removed[:, :, None]
    r_removed_f = rr != NOT_REMOVED
    row_below = r_removed_f & (rr <= msk)
    row_rm_vis = r_removed_f & (
        (rr <= refk)
        | (((r_removers[:, :, None]
             >> clik.astype(jnp.uint32)) & 1) > 0)
    )
    row_ins_vis = (r_seq[:, :, None] <= refk) | (
        r_client[:, :, None] == clik
    )
    row_vis = (r_live[:, :, None] > 0) & ~row_below & \
        row_ins_vis & ~row_rm_vis & (r_length[:, :, None] > 0)

    base_stamp = in_interval & row_vis & \
        (is_range & taken)[:, None, :]                     # [D,R,K]
    # event coverage from the host bitmask
    cover = (
        (ops["ev_cover"][:, None, :]
         >> ev_bit[:, :, None].astype(jnp.uint32)) & 1
    ) > 0
    ev_stamp = cover & (r_is_event[:, :, None] > 0) & \
        (is_range & taken)[:, None, :]
    raw_stamp = base_stamp | ev_stamp

    # first-visible-remover-wins replay across the chunk's removes:
    # a remove is suppressed on rows already taken by an earlier
    # unsuppressed remove it can SEE (visp); invisible overlaps both
    # stamp (reference rm_by_viewer/removers semantics).
    visp = (
        (ops["seq"][:, :, None] <= ops["refseq"][:, None, :])
        | (ops["client"][:, :, None] == ops["client"][:, None, :])
    )                                                      # [D,i,k]
    rm_taken = (is_rem & taken)
    eff = jnp.zeros((D, R, K), jnp.bool_)
    for t in range(K):
        stamped_before = jnp.einsum(
            "dri,di->dr",
            (eff & rm_taken[:, None, :]).astype(jnp.int32),
            (visp[:, :, t]
             & (jnp.arange(K)[None] < t)).astype(jnp.int32),
        ) > 0
        ok_t = raw_stamp[:, :, t] & ~stamped_before
        eff = eff.at[:, :, t].set(ok_t)
    rm_eff = eff & rm_taken[:, None, :]
    ann_eff = eff & (is_ann & taken)[:, None, :]

    any_rm = jnp.any(rm_eff, axis=-1)
    first_rm_seq = jnp.min(
        jnp.where(rm_eff, ops["seq"][:, None, :], BIG), axis=-1
    )
    new_removed = jnp.where(
        (r_removed == NOT_REMOVED) & any_rm, first_rm_seq,
        r_removed,
    )
    # per (row, client) at most ONE effective remove can stamp (a
    # same-client later remove always sees the earlier one and is
    # suppressed), so the bit union is a plain sum
    bits = jnp.where(
        rm_eff,
        jnp.left_shift(
            jnp.uint32(1),
            ops["client"][:, None, :].astype(jnp.uint32),
        ),
        jnp.uint32(0),
    )
    new_removers = r_removers.astype(jnp.uint32) | jnp.sum(
        bits, axis=-1, dtype=jnp.uint32
    )

    new_props = []
    for c in range(PROP_CHANNELS):
        cand = ann_eff & (ops["prop_key"][:, None, :] == c)
        # LWW winner = max window index: within a chunk, lane order IS
        # sequenced order (compile_chunks emits consecutive window
        # ops), so no seq*K composite is needed (and none can
        # overflow int32 — ADVICE r4)
        win_k = jnp.max(
            jnp.where(
                cand, jnp.arange(K, dtype=jnp.int32)[None, None, :],
                -1,
            ),
            axis=-1,
        )
        win_val = jnp.take_along_axis(
            jnp.broadcast_to(ops["prop_val"][:, None, :], (D, R, K)),
            jnp.maximum(win_k, 0)[..., None], axis=-1,
        )[..., 0]
        new_props.append(
            jnp.where(win_k >= 0, win_val, r_props[c])
        )

    # ---- overflow ---------------------------------------------------
    adds = (
        ev_valid.astype(jnp.int32)
        + jnp.sum(
            cut_valid.reshape(D, 2, K).astype(jnp.int32), axis=1
        )
    )                                                      # [D,K]
    new_count = count[:, 0] + jnp.sum(adds, axis=-1)
    overflow_now = new_count > C
    # overflowed docs: flag and park (consume the rest of the window)
    keep = ~overflow_now

    # ---- one stable multi-key sort ----------------------------------
    operands = [key_slot, key_off, key_base, key_rank,
                r_length, r_seq, r_client, new_removed,
                new_removers.astype(jnp.int32), r_op_id, r_op_off,
                r_marker] + new_props
    sorted_ops = jax.lax.sort(
        operands, dimension=-1, is_stable=True, num_keys=4
    )
    (s_len, s_seq, s_cli, s_rem, s_rrs, s_oid, s_ooff,
     s_mark) = sorted_ops[4:12]
    s_props = sorted_ops[12:]

    def upd(old, new):
        return jnp.where(keep[:, None], new[:, :C], old)

    out = {
        "length": upd(st["length"], s_len),
        "seq": upd(st["seq"], s_seq),
        "client": upd(st["client"], s_cli),
        "removed_seq": upd(st["removed_seq"], s_rem),
        "removers": jnp.where(
            keep[:, None], s_rrs[:, :C].astype(jnp.uint32),
            st["removers"],
        ),
        "op_id": upd(st["op_id"], s_oid),
        "op_off": upd(st["op_off"], s_ooff),
        "is_marker": upd(st["is_marker"], s_mark),
        "count": jnp.where(keep, new_count, st["count"]),
        "min_seq": jnp.maximum(
            st["min_seq"],
            jnp.max(jnp.where(taken, ops["min_seq"], 0), axis=-1),
        ),
        "overflow": jnp.where(overflow_now, 1, st["overflow"]),
    }
    for c in range(PROP_CHANNELS):
        out[f"prop{c}"] = upd(st[f"prop{c}"], s_props[c])
    return out, take_upto, overflow_now


# ======================================================================
# driver


def _window_loop(st: dict, ops_w: dict, K: int) -> dict:
    """while_loop over macro-steps until every doc's cursor passes its
    window (overflowed docs park at the end immediately)."""
    D = st["length"].shape[0]
    W = ops_w["kind"].shape[1]
    cursor0 = jnp.zeros((D,), jnp.int32)

    def cond(carry):
        st_, cursor = carry
        return jnp.any(cursor < W)

    def body(carry):
        st_, cursor = carry
        chunk = _gather_ops(ops_w, cursor, K)
        st2, take, over = _macro_step(st_, chunk, K)
        cursor2 = jnp.where(over, W, cursor + take)
        return st2, jnp.minimum(cursor2, W)

    st, _ = lax.while_loop(cond, body, (st, cursor0))
    return st


_jit_cache: dict = {}


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


def apply_window_chunked(table: SegmentTable, chunked: dict,
                         K: int = 8) -> SegmentTable:
    """Apply a compiled chunk program (``compile_chunks`` output, as
    jnp/np [D, W] arrays) to the table. ``K`` must equal the compile
    k_max."""
    st = _chunk_state(table)
    ops_w = {
        f: jnp.asarray(chunked[f])
        for f in OpBatch._fields + CHUNK_FIELDS
    }
    st = _get_jit(K)(st, ops_w)
    return _chunk_unstate(dict(st))


def build_chunked(batch: OpBatch, K: int = 8) -> dict:
    """OpBatch -> compiled chunk program (host pass)."""
    return compile_chunks(
        {f: np.asarray(getattr(batch, f)) for f in OpBatch._fields},
        k_max=K,
    )


def _get_jit(K: int):
    """One cache-fill site: ``apply_window_chunked`` and
    ``compiled_window`` must hand out the SAME jit object per K or
    the AOT cost-analysis path stops resolving from the compilation
    cache."""
    if K not in _jit_cache:
        _jit_cache[K] = jax.jit(
            lambda st, ops: _window_loop(st, ops, K)
        )
    return _jit_cache[K]


_jit_pingpong_cache: dict = {}


def _get_jit_pingpong(K: int):
    if K not in _jit_pingpong_cache:

        def run(dead: dict, st: dict, ops: dict) -> dict:
            # ``dead`` is donation fodder (a retired same-shape state):
            # its buffers may back this window's output. Never read.
            del dead
            return _window_loop(st, ops, K)

        _jit_pingpong_cache[K] = jax.jit(run, donate_argnums=(0,))
    return _jit_pingpong_cache[K]


def apply_window_chunked_pingpong(dead: SegmentTable | None,
                                  table: SegmentTable, chunked: dict,
                                  K: int = 8) -> SegmentTable:
    """Double-buffered twin of ``apply_window_chunked``: DONATES
    ``dead`` (a retired table of the same shape, e.g. the state two
    dispatches old) so XLA can reuse its buffers for the output while
    ``table`` survives as the caller's pre-dispatch snapshot — the
    sidecar's O(window) overflow regrow depends on that snapshot
    staying alive, which is why the live input is never the donated
    one. The caller must drop every reference to ``dead``. Degrades to
    the plain dispatch when ``dead`` is None or the backend (CPU) has
    no donation support."""
    if dead is None or jax.default_backend() == "cpu":
        return apply_window_chunked(table, chunked, K=K)
    st = _chunk_state(table)
    ops_w = {
        f: jnp.asarray(chunked[f])
        for f in OpBatch._fields + CHUNK_FIELDS
    }
    st = _get_jit_pingpong(K)(_chunk_state(dead), st, ops_w)
    return _chunk_unstate(dict(st))


def compiled_window(table: SegmentTable, chunked: dict, K: int = 8):
    """PUBLIC handle for AOT cost analysis / instrumentation of the
    chunked executor: returns (jitted, args) for the SAME jit object
    ``apply_window_chunked`` dispatches at this K, with the traced
    argument structure — bench's HBM accounting resolves it from the
    compilation cache instead of reaching into _jit_cache."""
    args = (
        _chunk_state(table),
        {f: jnp.asarray(chunked[f])
         for f in OpBatch._fields + CHUNK_FIELDS},
    )
    return _get_jit(K), args
