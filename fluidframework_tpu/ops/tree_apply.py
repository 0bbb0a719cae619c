"""Batched forest-apply kernel — the tree serving plane's device half.

The merge sidecar serves flat sequence documents; this module is the
same discipline for SharedTree documents (ROADMAP item 6): forest
state lives on device as SoA arrays ``[docs, slots]`` and one dispatch
applies a whole window of sequenced tree changesets across every doc
at once. Per window step (one commit per doc):

1. **trunk-suffix rebase** — the incoming commit's atoms rebase over
   the per-doc RING of the last ``TRUNK_RING`` already-rebased trunk
   commits (``tree_kernel._rebase_one`` under a ``lax.scan`` over the
   ring, vmapped over docs). Ring entries outside the commit's
   concurrency window — sequenced at-or-before its ref, or from the
   commit's own session — are masked by muting their atoms (a fully
   muted ``over`` is a rebase no-op). Skipping own-session trunk
   commits is the batched form of the EditManager's inverse/trunk/
   rebased sandwich (editManager.ts:223): the inverses of the
   session's in-flight commits cancel its own trunk entries exactly
   when invert/rebase round-trips, which the scalar differential
   suite pins. TP1-valid tree transforms make the pairwise rebases
   commute without a central transform matrix (arXiv 1512.05949).
2. **forest apply** — the rebased atoms become attach/detach/set rows
   over the dense slot table and apply via one of two executor
   routes (``TREE_EXECUTOR_ROUTES``): ``atom``, a ``lax.scan`` over
   the 2A sorted rows (the parity reference — every row is a masked
   shift of the slot arrays), and ``macro``, a single stable-sort
   merge of surviving slots and attach rows (one sort per changeset,
   no sequential row walk). Both are bit-identical by construction
   and pinned by the service-level differential suite.

State model (the semidirect-product composition of arXiv 2004.04303:
tree structure x per-node registers in ONE changeset algebra):

- ``content[d, s]`` — host content-table index of the node in slot
  ``s`` (-1 empty). Live nodes occupy slots ``0..count-1`` in
  sequence order, so an atom's input position IS its slot index.
- ``value[d, s]`` — host value-table index of the node's latest SET
  (-1: the node's birth content stands).
- node payloads never cross the host->device boundary (the merge
  kernel's payload rule): INS/SET atoms carry host-table indices in
  the program's ``payload`` plane, MOV payloads are pre-captured from
  the source slot before any row applies (dense invariant: input
  position == slot), so destination-before-source moves need no
  ordering care.

Overflow: a step whose attaches could not all fit (``count +
attaches > slots``) PARKS the doc — state, ring and all later steps
of the window pass through untouched and ``overflow`` is flagged; the
sidecar's recovery re-applies the window from the pre-dispatch
snapshot at the next capacity rung, identical on both routes.
"""
from __future__ import annotations

import copy
import functools
from typing import Any, NamedTuple, Optional

import numpy as np

from .bucket_ladder import BucketLadder
from .event_graph import validate_executor
from .tree_atoms import (
    ATOM_DEL,
    ATOM_INS,
    ATOM_MOV,
    ATOM_NOP,
    ATOM_SET,
    DEFAULT_ATOMS,
    TreeAtoms,
    encode_changeset,
)

# The tree serving plane's executor routes — ONE registry, validated
# through the same gate as the merge plane's (event_graph.
# validate_executor(..., routes=TREE_EXECUTOR_ROUTES)).
TREE_EXECUTOR_ROUTES = ("atom", "macro")

# Trunk-rebase ring depth: how many already-rebased trunk commits each
# doc keeps on device for concurrency-window rebasing. A static
# program-selection constant (the EG_K discipline: one program per
# shape, prewarm walks it). A commit whose ref predates the ring's
# oldest entry is host-path (the sidecar evicts — ring_safe()).
TRUNK_RING = 16

_SORT_BIG = np.int32(1 << 30)


class TreeTable(NamedTuple):
    """Device forest state, docs-major SoA (int32 throughout)."""

    content: Any    # [docs, slots] host content-table index, -1 empty
    value: Any      # [docs, slots] host value-table index, -1 unset
    count: Any      # [docs] live node count
    overflow: Any   # [docs] 1 after a parked (overflowed) step
    ring: TreeAtoms  # [docs, ring, atoms] last rebased trunk commits
    ring_seq: Any   # [docs, ring] commit seq (0 = empty entry)
    ring_session: Any  # [docs, ring] session ordinal of the commit

    @property
    def docs(self) -> int:
        return self.content.shape[0]

    @property
    def slots(self) -> int:
        return self.content.shape[1]


class TreeProgram(NamedTuple):
    """One packed dispatch window, window-major for the outer scan."""

    atoms: TreeAtoms  # [window, docs, atoms]
    payload: Any      # [window, docs, atoms] host-table index or -1
    seq: Any          # [window, docs] commit seq (0 = padding)
    ref: Any          # [window, docs] commit ref seq
    session: Any      # [window, docs] session ordinal

    @property
    def window(self) -> int:
        return self.seq.shape[0]


def make_tree_table(docs: int, slots: int, ring: int = TRUNK_RING,
                    atoms: int = DEFAULT_ATOMS) -> TreeTable:
    """Fresh all-empty forest slab (host numpy; jax converts on first
    dispatch)."""
    z = functools.partial(np.zeros, dtype=np.int32)
    return TreeTable(
        content=np.full((docs, slots), -1, np.int32),
        value=np.full((docs, slots), -1, np.int32),
        count=z((docs,)),
        overflow=z((docs,)),
        ring=TreeAtoms(kind=z((docs, ring, atoms)),
                       pos=z((docs, ring, atoms)),
                       n=z((docs, ring, atoms)),
                       muted=z((docs, ring, atoms)),
                       pos2=z((docs, ring, atoms))),
        ring_seq=z((docs, ring)),
        ring_session=z((docs, ring)),
    )


def _pad_tree_impl(table: TreeTable, new_slots: int) -> TreeTable:
    import jax.numpy as jnp

    pad = new_slots - table.content.shape[1]

    def fill(a):
        return jnp.concatenate(
            [a, jnp.full((a.shape[0], pad), -1, jnp.int32)], axis=1)

    return table._replace(content=fill(table.content),
                          value=fill(table.value))


def _pad_tree():
    import jax

    return jax.jit(_pad_tree_impl, static_argnums=(1,))


pad_tree_capacity = None  # assigned below (import-light module head)


def ring_safe(history: list, ref: int, ring: int = TRUNK_RING) -> bool:
    """True iff every trunk commit a ref-``ref`` commit must rebase
    over is still inside a depth-``ring`` ring. ``history`` is the
    doc's packed-commit seqs, oldest first, trimmed to the last
    ``ring`` entries by the caller: safe when the ring is not yet full
    or when the oldest retained seq is at-or-under the ref (every
    commit older than the ring's head sequenced at-or-before it)."""
    if len(history) < ring:
        return True
    return ref >= history[0]


def noop_tree_commit(width: int = DEFAULT_ATOMS) -> dict:
    """The padding commit: all-NOP atoms, seq 0 (never pushed to the
    ring, rebases to itself, applies nothing)."""
    z = functools.partial(np.zeros, dtype=np.int32)
    return {"kind": z(width), "pos": z(width), "n": z(width),
            "muted": z(width), "pos2": z(width),
            "payload": np.full(width, -1, np.int32),
            "seq": 0, "ref": 0, "session": 0}


def encode_tree_commit(marks: list, content_table: list,
                       value_table: list, *, seq: int, ref: int,
                       session: int,
                       width: int = DEFAULT_ATOMS) -> dict:
    """Encode one sequenced changeset for the serving plane: the
    tree_atoms encoding re-granulated to UNIT inserts (each inserted
    node gets its own content-table row, so moves and decodes never
    split a width-n payload) with host-table payload indices
    assigned. Appends to ``content_table``/``value_table`` (append-
    only; a raised ``ValueError`` may leave unused tail entries —
    harmless, indices are only reachable from returned atoms).
    Raises ``ValueError`` for device-inexpressible changesets — the
    caller evicts to the scalar path, the merge-sidecar discipline."""
    enc, payloads = encode_changeset(marks, width=width)
    z = functools.partial(np.zeros, dtype=np.int32)
    kind, pos, n = z(width), z(width), z(width)
    muted, pos2 = z(width), z(width)
    payload = np.full(width, -1, np.int32)
    a = 0

    def put(k, at, mute, at2, pay):
        nonlocal a
        if a >= width:
            raise ValueError(f"changeset exceeds {width} atoms")
        kind[a], pos[a], n[a] = k, at, 1
        muted[a], pos2[a], payload[a] = mute, at2, pay
        a += 1

    for i in range(width):
        k = int(enc["kind"][i])
        if k == ATOM_NOP:
            continue
        if k == ATOM_INS:
            for node in payloads[i] or []:
                put(ATOM_INS, int(enc["pos"][i]),
                    int(enc["muted"][i]), 0, len(content_table))
                content_table.append(copy.deepcopy(node))
        elif k == ATOM_SET:
            put(k, int(enc["pos"][i]), int(enc["muted"][i]), 0,
                len(value_table))
            value_table.append(copy.deepcopy(payloads[i]))
        else:  # DEL / MOV
            put(k, int(enc["pos"][i]), int(enc["muted"][i]),
                int(enc["pos2"][i]), -1)
    return {"kind": kind, "pos": pos, "n": n, "muted": muted,
            "pos2": pos2, "payload": payload,
            "seq": seq, "ref": ref, "session": session}


def pack_tree_window(docs: int, queued: dict,
                     ladder: Optional[BucketLadder] = None,
                     bucket_floor: Optional[int] = None,
                     width: int = DEFAULT_ATOMS) -> TreeProgram:
    """Pack per-doc commit lists (``{doc_row: [encode_tree_commit
    dicts]}``) into one window-major TreeProgram, window depth
    bucketed via the BucketLadder (the _pack_rows contract: shapes
    reaching the jit come only from ladder rungs)."""
    lad = ladder or BucketLadder()
    if bucket_floor is not None:
        lad = BucketLadder(bucket_floor, lad.max_bucket)
    deepest = max((len(v) for v in queued.values()), default=0)
    window = lad.window_bucket(max(deepest, 1))
    z = functools.partial(np.zeros, dtype=np.int32)
    kind = z((window, docs, width))
    pos = z((window, docs, width))
    n = z((window, docs, width))
    muted = z((window, docs, width))
    pos2 = z((window, docs, width))
    payload = np.full((window, docs, width), -1, np.int32)
    seq, ref, session = z((window, docs)), z((window, docs)), \
        z((window, docs))
    for d, commits in queued.items():
        for w, c in enumerate(commits):
            kind[w, d] = c["kind"]
            pos[w, d] = c["pos"]
            n[w, d] = c["n"]
            muted[w, d] = c["muted"]
            pos2[w, d] = c["pos2"]
            payload[w, d] = c["payload"]
            seq[w, d] = c["seq"]
            ref[w, d] = c["ref"]
            session[w, d] = c["session"]
    return TreeProgram(
        atoms=TreeAtoms(kind=kind, pos=pos, n=n, muted=muted,
                        pos2=pos2),
        payload=payload, seq=seq, ref=ref, session=session,
    )


# ======================================================================
# device half


def _apply_atom_route(content, value, count, atoms, payload,
                      mov_content, mov_value):
    """Parity-reference executor: ``lax.scan`` over the changeset's
    2A rows in (position, attach-before-node-op, atom-index) order —
    the exact order ``tree_atoms.atoms_to_marks`` decodes — tracking
    the running attach-detach delta so every row applies at its
    effective (current-array) index as a masked shift."""
    import jax
    import jax.numpy as jnp

    a_width = atoms.kind.shape[0]
    slots_n = content.shape[0]
    live = atoms.muted == 0
    is_ins = (atoms.kind == ATOM_INS) & live
    is_mov = (atoms.kind == ATOM_MOV) & live
    is_det = ((atoms.kind == ATOM_DEL) | (atoms.kind == ATOM_MOV)) \
        & live
    is_set = (atoms.kind == ATOM_SET) & live
    aidx = jnp.arange(a_width, dtype=jnp.int32)

    node_kind = jnp.where(is_det, 2, jnp.where(is_set, 3, 0))
    att_kind = jnp.where(is_ins | is_mov, 1, 0)
    att_at = jnp.where(is_mov, atoms.pos2, atoms.pos)
    node_key = jnp.where(node_kind > 0,
                         (atoms.pos * 2 + 1) * a_width + aidx,
                         _SORT_BIG)
    att_key = jnp.where(att_kind > 0, (att_at * 2) * a_width + aidx,
                        _SORT_BIG)

    rkind = jnp.concatenate([node_kind, att_kind])
    rat = jnp.concatenate([atoms.pos, att_at])
    rpc = jnp.concatenate([
        jnp.full((a_width,), -1, jnp.int32),
        jnp.where(is_ins, payload, mov_content),
    ])
    rpv = jnp.concatenate([
        jnp.where(is_set, payload, -1),
        jnp.where(is_ins, -1, mov_value),
    ])
    order = jnp.argsort(jnp.concatenate([node_key, att_key]))
    rows = (rkind[order], rat[order], rpc[order], rpv[order])

    slot = jnp.arange(slots_n, dtype=jnp.int32)

    def row_step(carry, row):
        c, v, cnt, delta = carry
        k, at, pc, pv = row
        eff = at + delta
        att_c = jnp.where(slot < eff, c,
                          jnp.where(slot == eff, pc, jnp.roll(c, 1)))
        att_v = jnp.where(slot < eff, v,
                          jnp.where(slot == eff, pv, jnp.roll(v, 1)))
        det_c = jnp.where(
            slot >= eff,
            jnp.where(slot == slots_n - 1, -1, jnp.roll(c, -1)), c)
        det_v = jnp.where(
            slot >= eff,
            jnp.where(slot == slots_n - 1, -1, jnp.roll(v, -1)), v)
        is_a, is_d, is_s = k == 1, k == 2, k == 3
        nc = jnp.where(is_a, att_c, jnp.where(is_d, det_c, c))
        nv = jnp.where(is_a, att_v, jnp.where(is_d, det_v, v))
        nv = jnp.where(is_s & (slot == eff), pv, nv)
        step = is_a.astype(jnp.int32) - is_d.astype(jnp.int32)
        return (nc, nv, cnt + step, delta + step), None

    (nc, nv, ncnt, _), _ = jax.lax.scan(
        row_step, (content, value, count, jnp.int32(0)), rows)
    return nc, nv, ncnt


def _apply_macro_route(content, value, count, atoms, payload,
                       mov_content, mov_value):
    """Macro-step executor: value registers scatter in one LWW
    pre-pass on input coordinates, then ONE stable sort merges the
    surviving slots with the attach rows (attaches keyed just before
    the node at their anchor, ordered among themselves by atom
    index) — no sequential row walk."""
    import jax.numpy as jnp

    a_width = atoms.kind.shape[0]
    slots_n = content.shape[0]
    live = atoms.muted == 0
    is_ins = (atoms.kind == ATOM_INS) & live
    is_mov = (atoms.kind == ATOM_MOV) & live
    is_det = ((atoms.kind == ATOM_DEL) | (atoms.kind == ATOM_MOV)) \
        & live
    is_set = (atoms.kind == ATOM_SET) & live
    slot = jnp.arange(slots_n, dtype=jnp.int32)
    aidx = jnp.arange(a_width, dtype=jnp.int32)

    # value-register LWW pre-pass (last atom wins, deterministically)
    set_sel = is_set[None, :] & (atoms.pos[None, :] == slot[:, None])
    chosen = jnp.argmax(
        jnp.where(set_sel, aidx[None, :] + 1, 0), axis=1)
    value = jnp.where(jnp.any(set_sel, axis=1), payload[chosen], value)

    detached = jnp.any(
        is_det[None, :] & (atoms.pos[None, :] == slot[:, None]),
        axis=1)
    alive = (slot < count) & ~detached
    old_key = jnp.where(alive, slot * (a_width + 1) + a_width,
                        _SORT_BIG)

    att = is_ins | is_mov
    att_at = jnp.where(is_mov, atoms.pos2, atoms.pos)
    att_key = jnp.where(att, att_at * (a_width + 1) + aidx, _SORT_BIG)

    key = jnp.concatenate([old_key, att_key])
    cand_c = jnp.concatenate(
        [content, jnp.where(is_ins, payload, mov_content)])
    cand_v = jnp.concatenate(
        [value, jnp.where(is_ins, -1, mov_value)])
    order = jnp.argsort(key)[:slots_n]
    live_out = key[order] < _SORT_BIG
    nc = jnp.where(live_out, cand_c[order], -1)
    nv = jnp.where(live_out, cand_v[order], -1)
    ncnt = count + jnp.sum(att.astype(jnp.int32)) \
        - jnp.sum(is_det.astype(jnp.int32))
    return nc, nv, ncnt


def _tree_step(route: str, doc: TreeTable, xs):
    """One window step for one doc: ring rebase -> forest apply ->
    ring push. Parked docs (overflow) pass everything through."""
    import jax
    import jax.numpy as jnp

    from .tree_kernel import _rebase_one

    atoms, payload, seq, ref, session = xs
    slots_n = doc.content.shape[0]

    active = (doc.ring_seq > ref) & (doc.ring_seq < seq) \
        & (doc.ring_session != session) & (doc.ring_seq > 0)

    def rb(cur, over):
        o, act = over
        o = o._replace(muted=jnp.where(act, o.muted, 1))
        return _rebase_one(cur, o), None

    rebased, _ = jax.lax.scan(rb, atoms, (doc.ring, active))

    live = rebased.muted == 0
    is_mov = (rebased.kind == ATOM_MOV) & live
    att_n = jnp.sum(((rebased.kind == ATOM_INS) & live)
                    .astype(jnp.int32)) \
        + jnp.sum(is_mov.astype(jnp.int32))
    # conservative park bound: attaches may all land before any
    # detach frees a slot, so the transient peak is count + attaches
    overflowed = doc.count + att_n > slots_n
    park = (doc.overflow > 0) | overflowed

    src = jnp.clip(rebased.pos, 0, slots_n - 1)
    mov_content = jnp.where(is_mov, doc.content[src], -1)
    mov_value = jnp.where(is_mov, doc.value[src], -1)

    apply_route = _apply_atom_route if route == "atom" \
        else _apply_macro_route
    nc, nv, ncnt = apply_route(doc.content, doc.value, doc.count,
                               rebased, payload, mov_content,
                               mov_value)

    push = (seq > 0) & ~park
    shifted = jax.tree.map(
        lambda r, c: jnp.concatenate([r[1:], c[None]], axis=0),
        doc.ring, rebased)
    return doc._replace(
        content=jnp.where(park, doc.content, nc),
        value=jnp.where(park, doc.value, nv),
        count=jnp.where(park, doc.count, ncnt),
        overflow=jnp.maximum(doc.overflow,
                             overflowed.astype(jnp.int32)),
        ring=jax.tree.map(
            lambda new, old: jnp.where(push, new, old),
            shifted, doc.ring),
        ring_seq=jnp.where(
            push, jnp.concatenate([doc.ring_seq[1:], seq[None]]),
            doc.ring_seq),
        ring_session=jnp.where(
            push,
            jnp.concatenate([doc.ring_session[1:], session[None]]),
            doc.ring_session),
    )


def _apply_tree_window_impl(route: str, table: TreeTable,
                            program: TreeProgram) -> TreeTable:
    import jax

    def step(tab, xs):
        return jax.vmap(functools.partial(_tree_step, route))(
            tab, xs), None

    xs = (program.atoms, program.payload, program.seq, program.ref,
          program.session)
    out, _ = jax.lax.scan(step, table, xs)
    return out


# route -> jitted window program (the chunked-factory cache shape:
# jitsan reads compile counts from this dict — testing/jitsan.py
# _JIT_CACHES["tree_window"])
_jit_cache: dict = {}


def tree_window_fn(route: str):
    validate_executor(route, "tree_window_fn[route]",
                      routes=TREE_EXECUTOR_ROUTES)
    fn = _jit_cache.get(route)
    if fn is None:
        import jax

        fn = jax.jit(functools.partial(_apply_tree_window_impl, route))
        _jit_cache[route] = fn
    return fn


def apply_tree_window(table: TreeTable, program: TreeProgram,
                      route: str = "atom") -> TreeTable:
    """Dispatch one packed window on the chosen executor route."""
    return tree_window_fn(route)(table, program)


def decode_tree_row(content_row, value_row, count: int,
                    content_table: list, value_table: list) -> list:
    """Host read half: one settled doc row -> its node list. SET
    payloads are the algebra's ``{"new": v, "old": u}`` value dicts;
    the latest one overrides the birth content's value."""
    out = []
    for s in range(int(count)):
        node = copy.deepcopy(content_table[int(content_row[s])])
        v = int(value_row[s])
        if v >= 0:
            node["value"] = value_table[v]["new"]
        out.append(node)
    return out


pad_tree_capacity = _pad_tree()
