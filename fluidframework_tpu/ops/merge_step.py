"""Fused batched merge step — the one-pass-per-phase op apply shared by
the XLA scan executor and the VMEM-resident Pallas kernel.

This is v2 of the sequenced-path merge kernel (the vectorized
replacement for the reference's per-op B-tree walk: mergeTree.ts
``insertingWalk`` :1723, ``markRangeRemoved`` :1908, ``annotateRange``
:1864, ``PartialSequenceLengths`` partialLengths.ts:234). v1 applied
one op via FIVE full-table phases (3 view/cumsum passes + 2 structural
passes); this version fuses them into three:

  1. ONE view pass at (refseq, client) + exclusive prefix-sum, from
     which the insert target AND both range-boundary splits are all
     resolved (the p2 boundary is computed on the pre-op view and
     shifted into post-split coordinates, which is equivalent because
     splitting at p1 never changes visible lengths).
  2. ONE generalized restructure supporting two simultaneous slot
     insertions (split tails and/or the inserted segment), expressed as
     zero-fill static shifts + per-element selects — no gathers (which
     lower catastrophically inside lax.scan on TPU) and no data-
     dependent control flow.
  3. ONE stamp pass whose in-range mask is *derived* from the pre-op
     view (fully-contained slots shift along; the two boundary parts
     are stamped by position), avoiding a third view/cumsum pass.

The prefix-sum is a hand-rolled Hillis-Steele ladder of log2(capacity)
zero-fill shifts because Mosaic (Pallas TPU) has no ``cumsum``
lowering; the same code runs under plain XLA so both executors share
this exact function and agree bit-for-bit by construction.

Everything is expressed over a dict-of-arrays state with an explicit
leading doc axis ([D, C] slots, [D, 1] per-doc scalars): the same code
runs under vmap-free XLA (lax.scan over the window), inside a Pallas
kernel body (fori_loop over the window with the state resident in
VMEM), and under shard_map with the doc axis sharded over a mesh.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .segment_table import (
    KIND_ANNOTATE,
    KIND_INSERT,
    KIND_REMOVE,
    NOT_REMOVED,
    OPOFF_BOUND as _OPOFF_BOUND,
    PROP_CHANNELS,
)

# per-slot state arrays [D, C]; prop channels are split into separate
# arrays (a [D, C, 4] trailing dim would tile poorly in VMEM)
SLOT_FIELDS = (
    "length", "seq", "client", "removed_seq", "removers",
    "op_id", "op_off", "is_marker",
) + tuple(f"prop{c}" for c in range(PROP_CHANNELS))

# per-doc scalar arrays [D, 1]
DOC_FIELDS = ("count", "min_seq", "overflow")

STATE_FIELDS = SLOT_FIELDS + DOC_FIELDS

# op fields consumed per step, each [D, 1]
OP_COLS = (
    "kind", "pos1", "pos2", "seq", "refseq", "client",
    "op_id", "length", "is_marker", "prop_key", "prop_val", "min_seq",
)


def table_to_state(table) -> dict:
    """SegmentTable -> dict-of-arrays state (prop split per channel,
    per-doc scalars lifted to [D, 1])."""
    st = {
        f: getattr(table, f)
        for f in ("length", "seq", "client", "removed_seq", "removers",
                  "op_id", "op_off", "is_marker")
    }
    for c in range(PROP_CHANNELS):
        st[f"prop{c}"] = table.prop[..., c]
    for f in DOC_FIELDS:
        st[f] = getattr(table, f)[..., None]
    return st


def state_to_table(st: dict, table_cls):
    return table_cls(
        length=st["length"],
        seq=st["seq"],
        client=st["client"],
        removed_seq=st["removed_seq"],
        removers=st["removers"],
        op_id=st["op_id"],
        op_off=st["op_off"],
        is_marker=st["is_marker"],
        prop=jnp.stack(
            [st[f"prop{c}"] for c in range(PROP_CHANNELS)], axis=-1
        ),
        count=st["count"][..., 0],
        min_seq=st["min_seq"][..., 0],
        overflow=st["overflow"][..., 0],
    )


def _shift_right(arr, k: int):
    """arr[j-k] with zero fill — static pad+slice, Mosaic-safe."""
    pad = [(0, 0)] * (arr.ndim - 1) + [(k, 0)]
    return jnp.pad(arr, pad)[..., : arr.shape[-1]]


def _excl_cumsum_ladder(x):
    """Exclusive prefix sum along the last axis via a Hillis-Steele
    ladder of log2(C) zero-fill shifts — for the Pallas path, where
    Mosaic has no cumsum lowering and the ladder runs entirely in
    VMEM/VREGs."""
    C = x.shape[-1]
    s = x
    k = 1
    while k < C:
        s = s + _shift_right(s, k)
        k <<= 1
    return s - x


def _excl_cumsum_native(x):
    """Exclusive prefix sum for the XLA executor: the native cumsum
    lowers to one fused pass, where the ladder would stream the whole
    table through HBM log2(C) times per step (measured 7x slower)."""
    return jnp.cumsum(x, axis=-1) - x


def _first_true(mask, j, default):
    """Index of the first True along the last axis, else ``default``
    ([D,1]); implemented as a min-reduce (argmax is unavailable in
    Mosaic and data-dependent gathers are poison in scans)."""
    return jnp.min(
        jnp.where(mask, j, default), axis=-1, keepdims=True
    )


def _at(arr, idx, j):
    """arr[d, idx[d]] as a masked reduce ([D,1]); out-of-range idx
    yields 0 (callers gate on the found flag)."""
    return jnp.sum(
        jnp.where(j == idx, arr, 0), axis=-1, keepdims=True
    )


def _min_where(mask, arr, default):
    """min of ``arr`` over ``mask`` along the last axis ([D,1]).

    For a monotone non-decreasing ``arr`` this equals
    ``arr[first_true(mask)]`` — the trick that collapses the step's
    second (index-dependent gather) reduce layer into the first: E and
    incl are prefix sums, so every "value at the first masked slot"
    lookup is a plain masked min, and all of phase 1 becomes ONE
    fusable reduce layer instead of two dependent ones (each layer is
    a separate kernel launch, and every launch has a fixed cost)."""
    return jnp.min(
        jnp.where(mask, arr, default), axis=-1, keepdims=True
    )


# re-exported for executors; defined host-side in segment_table so the
# pure-numpy encoding path never imports the jax stack
OPOFF_BOUND = _OPOFF_BOUND


class AxisPrims:
    """The segment-axis primitives ``fused_step`` is generic over.

    Every slot-axis-global operation the step performs goes through
    this seam, so the same step function runs (a) single-device on a
    full table, (b) inside a Pallas kernel body (ladder cumsum), and
    (c) under ``shard_map`` with the SEGMENT axis sharded across
    devices — the long-document sequence-parallel path (SURVEY §5.7),
    where these become cross-device collectives
    (parallel/seq_shard.py).
    """

    def __init__(self, *, iota_j=None, excl_cumsum=None, shift_right=None,
                 shift_right_many=None, first_true=None, at=None,
                 min_where=None, total=None, global_capacity=None):
        self.iota_j = iota_j or (
            lambda D, C: lax.broadcasted_iota(jnp.int32, (D, C), 1))
        self.excl_cumsum = excl_cumsum or _excl_cumsum_native
        self.shift_right = shift_right or _shift_right
        # batched variant: shift a whole family of same-shape arrays at
        # once, so collective implementations pay ONE boundary exchange
        # per shift distance instead of one per field
        self.shift_right_many = shift_right_many or (
            lambda arrs, k: [self.shift_right(a, k) for a in arrs])
        self.first_true = first_true or _first_true
        self.at = at or _at
        self.min_where = min_where or _min_where
        # global visible-length total [D,1]; default = last inclusive
        # prefix (exact integer sum, == jnp.sum(vlen))
        self.total = total or (lambda vlen, incl: incl[..., -1:])
        # capacity of the FULL (logical) table; equals the local shape
        # except under sequence sharding
        self.global_capacity = global_capacity or (lambda C: C)


LOCAL_PRIMS = AxisPrims()


def batch_to_window(batch) -> dict:
    """OpBatch [docs, window] -> per-step op dict layout [window, docs,
    1] consumed by lax.scan over fused_step — the single definition of
    the op-window layout contract (shared by the XLA executor and the
    sequence-sharded path)."""
    return {
        f: jnp.swapaxes(getattr(batch, f), 0, 1)[..., None]
        for f in batch._fields
    }


def fused_step(st: dict, op: dict,
               prims: AxisPrims = LOCAL_PRIMS) -> dict:
    """Apply one sequenced op per document (batched over the leading
    doc axis) to the slot state. Pure jnp; runs under XLA, inside
    Pallas, and under a sequence-sharded shard_map identically (the
    AxisPrims implementation is the only knob, and every variant
    produces exact integer sums)."""
    _first_true = prims.first_true
    _min_where = prims.min_where
    C = st["length"].shape[-1]
    D = st["length"].shape[0]
    Cg = prims.global_capacity(C)
    j = prims.iota_j(D, C)

    count, min_seq = st["count"], st["min_seq"]
    kind = op["kind"]
    is_ins = kind == KIND_INSERT
    is_rem = kind == KIND_REMOVE
    is_ann = kind == KIND_ANNOTATE
    is_range = is_rem | is_ann
    refseq, client = op["refseq"], op["client"]
    p1, p2 = op["pos1"], op["pos2"]

    # ---- phase 1: one view pass at (refseq, client) ------------------
    alive = j < count
    removed = st["removed_seq"] != NOT_REMOVED
    below = removed & (st["removed_seq"] <= min_seq)
    rm_by_viewer = (
        (st["removers"] >> client.astype(jnp.uint32)) & 1
    ).astype(jnp.bool_)
    removal_visible = removed & (
        (st["removed_seq"] <= refseq) | rm_by_viewer
    )
    insert_visible = (st["seq"] <= refseq) | (st["client"] == client)
    vis = alive & ~below & insert_visible & ~removal_visible
    stop = alive & ~below
    vlen = jnp.where(vis, st["length"], 0)
    E = prims.excl_cumsum(vlen)
    incl = E + vlen
    total = prims.total(vlen, incl)

    # All "value at the first masked slot" lookups below ride the SAME
    # single reduce layer as the index searches: E and incl are
    # monotone non-decreasing (prefix sums), so value-at-first-true ==
    # masked min; op_off rides a j*OPOFF_BOUND+op_off composite whose
    # min is the first masked j's entry. One fused reduce layer
    # replaces the previous two dependent layers.
    BIG = jnp.int32(2**31 - 1)
    opoff_comp = j * OPOFF_BOUND + st["op_off"]

    # INSERT target: first stop slot with E==p1, or p1 strictly inside
    # (breakTie on the sequenced path: insert before the first
    # stop-eligible slot at the boundary — mergeTree.ts:1705)
    inside = stop & (E <= p1) & (p1 < incl)
    target = inside | (stop & (E == p1))
    idx_t = _first_true(target, j, count)
    E_t = _min_where(target, E, BIG)
    incl_t = _min_where(target, incl, BIG)
    opoff_t = _min_where(target, opoff_comp, BIG) % OPOFF_BOUND
    found_t = idx_t < count
    off_ins = jnp.where(found_t, p1 - E_t, 0)

    # RANGE boundary splits, both resolved on the PRE-op view; the p2
    # event is shifted into post-split-1 coordinates below (splitting
    # at p1 changes no visible lengths, so this matches resolving p2
    # after the first split)
    strict1 = (E < p1) & (p1 < incl)
    idx1 = _first_true(strict1, j, Cg)
    s1 = idx1 < Cg
    E_1 = _min_where(strict1, E, BIG)
    incl_1 = _min_where(strict1, incl, BIG)
    opoff_1 = _min_where(strict1, opoff_comp, BIG) % OPOFF_BOUND
    off1 = jnp.where(s1, p1 - E_1, 0)
    strict2 = (E < p2) & (p2 < incl)
    idx2 = _first_true(strict2, j, Cg)
    s2 = idx2 < Cg
    E_2 = _min_where(strict2, E, BIG)
    incl_2 = _min_where(strict2, incl, BIG)
    opoff_2 = _min_where(strict2, opoff_comp, BIG) % OPOFF_BOUND
    off2 = jnp.where(s2, p2 - E_2, 0)
    same = s1 & s2 & (idx1 == idx2)

    # ---- phase 2: unified two-insertion restructure ------------------
    valid_ins = is_ins & (p1 <= total)
    split_ins = valid_ins & (off_ins > 0)
    u1 = valid_ins | (is_range & s1)
    u2 = split_ins | (is_range & s2)
    added = u1.astype(jnp.int32) + u2.astype(jnp.int32)
    overflow_now = (added > 0) & (count + added > Cg)
    skip = overflow_now
    u1 = u1 & ~skip
    u2 = u2 & ~skip

    k1 = jnp.where(is_ins, idx_t, idx1)
    s1i = s1.astype(jnp.int32)
    # post-layout index of the first inserted slot (new segment, or the
    # tail of the p1 split) and of the second (insert-split tail, or
    # the tail of the p2 split); h2 = post index of the slot the p2
    # event splits (== A when both boundaries land in one slot)
    A = jnp.where(is_ins, idx_t + split_ins.astype(jnp.int32), idx1 + 1)
    h2 = idx2 + s1i
    B = jnp.where(is_ins, A + 1, h2 + 1)

    m = (u1 & (j >= A)).astype(jnp.int32) + (
        u2 & (j >= B)
    ).astype(jnp.int32)
    m1 = m == 1
    m2 = m == 2

    # the slot fields all restructure under the same m1/m2 selects, so
    # shift them as one family (one boundary-exchange collective per
    # shift distance under sequence sharding); the phase-3 stamp mask
    # rides along — it is derived from the PRE-op view (phase 1) but
    # must shift with the restructure like everything else
    fully_in = vis & (vlen > 0) & (E >= p1) & (incl <= p2)
    move_names = list(SLOT_FIELDS) + ["_stamp"]
    arrs = [st[f] for f in SLOT_FIELDS] + [fully_in.astype(jnp.int32)]
    sh1 = prims.shift_right_many(arrs, 1)
    sh2 = prims.shift_right_many(arrs, 2)
    mv = {
        n: jnp.where(m2, s2, jnp.where(m1, s1, a))
        for n, a, s1, s2 in zip(move_names, arrs, sh1, sh2)
    }

    def moved(arr_name):
        return mv[arr_name]

    at_A = u1 & (j == A)
    at_B = u2 & (j == B)
    new_at_A = at_A & is_ins

    # values at the split slots, all recovered from the single phase-1
    # reduce layer: incl-E == vlen == length there (every split slot is
    # visible: 'inside' and strictN imply E < incl, i.e. vlen > 0)
    len_k1 = jnp.where(is_ins, incl_t - E_t, incl_1 - E_1)
    len_k2 = incl_2 - E_2
    opoff_k1 = jnp.where(is_ins, opoff_t, opoff_1)
    opoff_k2 = opoff_2

    f_h1 = ~skip & (split_ins | (is_range & s1)) & (j == k1)
    f_h2 = ~skip & is_range & s2 & (j == h2)
    off1h = jnp.where(is_ins, off_ins, off1)
    len_h2 = off2 - jnp.where(same, off1, 0)

    length = moved("length")
    length = jnp.where(f_h1, off1h, length)
    length = jnp.where(
        at_A, jnp.where(is_ins, op["length"], len_k1 - off1), length
    )
    length = jnp.where(f_h2, len_h2, length)
    length = jnp.where(
        at_B,
        jnp.where(is_ins, len_k1 - off_ins, len_k2 - off2),
        length,
    )

    op_off = moved("op_off")
    op_off = jnp.where(
        at_A, jnp.where(is_ins, 0, opoff_k1 + off1), op_off
    )
    op_off = jnp.where(
        at_B,
        jnp.where(is_ins, opoff_k1 + off_ins, opoff_k2 + off2),
        op_off,
    )

    seq = moved("seq")
    seq = jnp.where(new_at_A, op["seq"], seq)
    cli = moved("client")
    cli = jnp.where(new_at_A, client, cli)
    removed_seq = moved("removed_seq")
    removed_seq = jnp.where(new_at_A, NOT_REMOVED, removed_seq)
    removers = moved("removers")
    removers = jnp.where(new_at_A, jnp.uint32(0), removers)
    op_id = moved("op_id")
    op_id = jnp.where(new_at_A, op["op_id"], op_id)
    is_marker = moved("is_marker")
    is_marker = jnp.where(new_at_A, op["is_marker"], is_marker)
    props = [moved(f"prop{c}") for c in range(PROP_CHANNELS)]
    props = [jnp.where(new_at_A, 0, p) for p in props]

    # ---- phase 3: stamps (mask derived from the pre-op view) ---------
    # mask shifted as int32: Mosaic cannot pad/select i1 vectors
    stamp = moved("_stamp") != 0
    stamp = stamp | (at_A & is_range) | (f_h2 & is_range)
    stamp = stamp & is_range & ~skip

    rmask = is_rem & stamp
    newly = rmask & (removed_seq == NOT_REMOVED)
    bit = jnp.uint32(1) << client.astype(jnp.uint32)
    removed_seq = jnp.where(newly, op["seq"], removed_seq)
    removers = jnp.where(rmask, removers | bit, removers)

    amask = is_ann & stamp
    props = [
        jnp.where(amask & (op["prop_key"] == c), op["prop_val"], p)
        for c, p in enumerate(props)
    ]

    out = {
        "length": length,
        "seq": seq,
        "client": cli,
        "removed_seq": removed_seq,
        "removers": removers,
        "op_id": op_id,
        "op_off": op_off,
        "is_marker": is_marker,
        "count": count + added * (1 - skip.astype(jnp.int32)),
        "min_seq": jnp.maximum(min_seq, op["min_seq"]),
        "overflow": jnp.where(overflow_now, 1, st["overflow"]),
    }
    for c in range(PROP_CHANNELS):
        out[f"prop{c}"] = props[c]
    return out
