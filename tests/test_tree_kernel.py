"""Batched tree rebase kernel vs the scalar mark-list algebra.

Parity target: the APPLIED effect. For fuzzed changesets C and trunks
[O1..OK] over a shared base, applying the kernel-rebased atoms must
produce the same node sequence as applying the scalar-rebased marks —
the same end state the EditManager would hand the forest.
"""
import random

import numpy as np
import pytest

from fluidframework_tpu.models.tree import changeset as cs
from fluidframework_tpu.ops.tree_atoms import (
    DEFAULT_ATOMS,
    TreeAtoms,
    apply_atoms,
    atoms_to_marks,
    encode_changeset,
    stack_changesets,
)
from fluidframework_tpu.ops.tree_kernel import (
    rebase_atoms,
    rebase_over_trunk,
)

from fluidframework_tpu.testing.tree_fuzz import random_changeset, random_trunk

FIELD = "root"


def rand_marks(rng: random.Random, base_len: int, n_edits: int = 3):
    return random_changeset(rng, base_len, n_edits)


def base_seq(rng: random.Random, n: int):
    return [{"type": "n", "value": i} for i in range(n)]


def scalar_rebase_chain(c_marks, overs):
    change = {FIELD: c_marks}
    for o in overs:
        change = cs.rebase(change, {FIELD: o})
    return change.get(FIELD, [])


def apply_chain(seq, overs):
    for o in overs:
        seq = cs.walk_apply(seq, o)
    return seq


@pytest.mark.parametrize("seed", range(40))
def test_single_over_parity(seed):
    rng = random.Random(seed * 101 + 13)
    n = rng.randint(4, 16)
    base = base_seq(rng, n)
    c_marks = rand_marks(rng, n)
    o_marks = rand_marks(rng, n)

    after_o = cs.walk_apply(base, o_marks)
    scalar_marks = scalar_rebase_chain(c_marks, [o_marks])
    expect = cs.walk_apply(after_o, scalar_marks)

    enc_c, content = encode_changeset(c_marks)
    enc_o, _ = encode_changeset(o_marks)
    out = rebase_atoms(
        stack_changesets([enc_c]), stack_changesets([enc_o])
    )
    out_np = {f: np.asarray(getattr(out, f))[0] for f in out._fields}
    got = apply_atoms(after_o, out_np, content)
    assert got == expect, (
        f"seed {seed}: base={n}\nC={c_marks}\nO={o_marks}\n"
        f"scalar={scalar_marks}\nkernel={atoms_to_marks(out_np, content)}"
    )


@pytest.mark.parametrize("seed", range(25))
def test_trunk_scan_parity(seed):
    """Rebase over a K-deep trunk suffix: the scan must equal the
    scalar sequential rebase (the compose law)."""
    rng = random.Random(seed * 7 + 3)
    n = rng.randint(6, 14)
    k_trunk = rng.randint(2, 4)
    base = base_seq(rng, n)

    c_marks = rand_marks(rng, n)
    overs = []
    cur = list(base)
    for _ in range(k_trunk):
        o = rand_marks(rng, len(cur))
        overs.append(o)
        cur = cs.walk_apply(cur, o)

    scalar_marks = scalar_rebase_chain(c_marks, overs)
    expect = cs.walk_apply(cur, scalar_marks)

    enc_c, content = encode_changeset(c_marks)
    trunk_atoms = [encode_changeset(o, allow_moves=False)[0]
                   for o in overs]
    trunk = TreeAtoms(*[
        np.stack([np.stack([t[f] for t in trunk_atoms])])
        for f in ("kind", "pos", "n", "muted", "pos2")
    ])
    out = rebase_over_trunk(stack_changesets([enc_c]), trunk)
    out_np = {f: np.asarray(getattr(out, f))[0] for f in out._fields}
    got = apply_atoms(cur, out_np, content)
    assert got == expect, (
        f"seed {seed}: C={c_marks}\novers={overs}\n"
        f"scalar={scalar_marks}\nkernel={atoms_to_marks(out_np, content)}"
    )


def test_batched_docs_independent():
    """Docs rebase independently in one dispatch."""
    rng = random.Random(99)
    docs = 16
    cases = []
    for _ in range(docs):
        n = rng.randint(4, 12)
        base = base_seq(rng, n)
        c, o = rand_marks(rng, n), rand_marks(rng, n)
        cases.append((base, c, o))
    c_stack = stack_changesets(
        [encode_changeset(c)[0] for _, c, _ in cases]
    )
    o_stack = stack_changesets(
        [encode_changeset(o)[0] for _, _, o in cases]
    )
    out = rebase_atoms(c_stack, o_stack)
    for d, (base, c_marks, o_marks) in enumerate(cases):
        after_o = cs.walk_apply(base, o_marks)
        expect = cs.walk_apply(
            after_o, scalar_rebase_chain(c_marks, [o_marks])
        )
        out_np = {f: np.asarray(getattr(out, f))[d] for f in out._fields}
        content = encode_changeset(c_marks)[1]
        assert apply_atoms(after_o, out_np, content) == expect, d


def test_device_inexpressible_marks_raise():
    with pytest.raises(ValueError):
        encode_changeset([cs.rev(1, "uid", 0)])
    with pytest.raises(ValueError):
        encode_changeset(
            [cs.mod(fields={"x": [cs.dele(1)]})]
        )
    with pytest.raises(ValueError):
        encode_changeset([cs.dele(1)] * (DEFAULT_ATOMS + 1))


def test_valueless_mod_encodes_as_skip():
    """code-review r2: a valueless, fieldless mod is skip(1) after
    normalize; encoding must not emit a SET atom that decodes into a
    crash inside walk_apply."""
    enc, content = encode_changeset(
        [cs.skip(1), {"t": "mod"}, cs.dele(1)]
    )
    assert list(enc["kind"][:2]) == [2, 0]  # just the unit del
    assert enc["pos"][0] == 2
    got = apply_atoms(
        [{"v": 0}, {"v": 1}, {"v": 2}], enc, content
    )
    assert got == [{"v": 0}, {"v": 1}]


@pytest.mark.parametrize("seed", range(30))
def test_kernel_move_parity_fuzz(seed):
    """MOV atoms: a changeset containing a move rebased over a random
    ins/del/mod trunk must match the scalar algebra exactly —
    including delete-wins muting of both halves."""
    rng = random.Random(seed * 41 + 5)
    base = [{"type": "n", "value": i} for i in range(8)]
    src = rng.randint(0, len(base) - 1)
    choices = [d for d in range(len(base) + 1)
               if d <= src or d >= src + 1]
    dst = rng.choice(choices)
    c_marks = cs.stamp(
        {"root": cs.move(src, 1, dst)}, f"M{seed}"
    )["root"]
    overs, cur = random_trunk(rng, base, rng.randint(1, 4), 3)

    scalar_marks = scalar_rebase_chain(c_marks, overs)
    from fluidframework_tpu.models.tree.forest import Forest

    f = Forest({"root": [dict(x) for x in base]})
    for i, o in enumerate(overs):
        f.apply({"root": o}, f"o{i}")
    fs = f.clone()
    fs.apply({"root": scalar_marks}, "scalar")
    expect = fs.content()["root"]

    enc_c, content = encode_changeset(c_marks)
    trunk_atoms = [encode_changeset(o, allow_moves=False)[0]
                   for o in overs]
    trunk = TreeAtoms(*[
        np.stack([np.stack([t[f] for t in trunk_atoms])])
        for f in ("kind", "pos", "n", "muted", "pos2")
    ])
    out = rebase_over_trunk(stack_changesets([enc_c]), trunk)
    out_np = {f: np.asarray(getattr(out, f))[0] for f in out._fields}
    got = apply_atoms(cur, out_np, content)
    assert got == expect, (
        f"seed {seed}: C={c_marks}\novers={overs}\n"
        f"scalar={scalar_marks}\n"
        f"kernel={atoms_to_marks(out_np, content)}"
    )
