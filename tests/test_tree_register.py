"""Register field kind (modular-schema value/optional): per-kind compose/invert/rebase, cross-kind changesets,
algebra laws fuzzed per kind and mixed, and DDS-level LWW convergence
(two clients filling one optional field merge to ONE winner)."""
import copy
import random

import pytest

from fluidframework_tpu.models.tree import changeset as cs
from fluidframework_tpu.models.tree.forest import Forest, node
from fluidframework_tpu.models.tree.schema import (
    OPTIONAL,
    SEQUENCE,
    VALUE,
    FieldSchema,
    NodeSchema,
    SchemaViolation,
    StoredSchema,
)
from fluidframework_tpu.testing.runtime_mocks import ContainerSession


def apply_to(fields: dict, changes) -> dict:
    f = Forest()
    f.fields = copy.deepcopy(fields)
    f.apply(changes, revision=("t", 0))
    return f.fields


def n(v):
    return node("item", value=v)


# ---- unit: compose / invert / rebase per kind ------------------------

def test_reg_set_apply_and_invert():
    base = {"opt": [n(1)]}
    change = {"opt": cs.reg_set(n(2), n(1))}
    cs.stamp(change, "u1")
    after = apply_to(base, change)
    assert after["opt"][0]["value"] == 2
    inv = cs.invert(change, "inv1")
    restored = apply_to(after, inv)
    assert restored["opt"][0]["value"] == 1


def test_reg_clear_and_fill_optional():
    base = {"opt": [n(1)]}
    clear = {"opt": cs.reg_set(None, n(1))}
    cs.stamp(clear, "u1")
    after = apply_to(base, clear)
    assert after["opt"] == []
    fill = {"opt": cs.reg_set(n(9), None)}
    cs.stamp(fill, "u2")
    assert apply_to(after, fill)["opt"][0]["value"] == 9
    # inverse of clear restores the node
    assert apply_to(after, cs.invert(clear, "i"))["opt"][0]["value"] == 1


def test_reg_compose_set_set_keeps_oldest_old():
    a = {"opt": cs.reg_set(n(2), n(1))}
    b = {"opt": cs.reg_set(n(3), n(2))}
    cs.stamp(a, "ua")
    cs.stamp(b, "ub")
    comp = cs.compose([a, b])
    assert comp["opt"]["set"]["new"]["value"] == 3
    assert comp["opt"]["set"]["old"]["value"] == 1
    # inverse of the composite restores the original
    assert apply_to({"opt": [n(1)]}, comp)["opt"][0]["value"] == 3
    restored = apply_to(
        {"opt": [n(3)]}, cs.invert(comp, "i"))
    assert restored["opt"][0]["value"] == 1


def test_reg_nested_mods_compose_and_invert():
    child = node("obj")
    child["fields"] = {"kids": [n(5)]}
    base = {"opt": [child]}
    # modify the register node's nested sequence field
    mods = {"opt": cs.reg_mods(
        [cs.mod(fields={"kids": [cs.ins([n(6)])]})])}
    cs.stamp(mods, "u1")
    after = apply_to(base, mods)
    assert [x["value"] for x in after["opt"][0]["fields"]["kids"]] == \
        [6, 5]
    restored = apply_to(after, cs.invert(mods, "i1"))
    assert [x["value"] for x in restored["opt"][0]["fields"]["kids"]] \
        == [5]


def test_reg_rebase_concurrent_sets_lww():
    base = {"opt": [n(0)]}
    a = {"opt": cs.reg_set(n(1), n(0))}
    b = {"opt": cs.reg_set(n(2), n(0))}
    cs.stamp(a, "ua")
    cs.stamp(b, "ub")
    # a sequences first; b rebases over a and still applies (LWW)
    b2 = cs.rebase(copy.deepcopy(b), a)
    final = apply_to(apply_to(base, a), b2)
    assert final["opt"][0]["value"] == 2
    # the mirror order converges to the later-SEQUENCED writer
    a2 = cs.rebase(copy.deepcopy(a), b)
    final2 = apply_to(apply_to(base, b), a2)
    assert final2["opt"][0]["value"] == 1


def test_reg_rebase_mods_over_set_mute_and_unmute():
    """Nested mods whose node a concurrent set replaced mute; the
    set's inverse unmutes them (the sandwich property)."""
    child = node("obj")
    child["fields"] = {"kids": [n(5)]}
    base = {"opt": [child]}
    setter = {"opt": cs.reg_set(n(9), copy.deepcopy(child))}
    modder = {"opt": cs.reg_mods(
        [cs.mod(fields={"kids": [cs.ins([n(6)])]})])}
    cs.stamp(setter, "us")
    cs.stamp(modder, "um")
    # setter sequences first: modder's nested edit mutes
    m2 = cs.rebase(copy.deepcopy(modder), setter)
    assert "mods" not in m2["opt"]
    assert m2["opt"]["muted"][0]["by"] == setter["opt"]["set"]["sid"]
    after = apply_to(apply_to(base, setter), m2)
    assert after["opt"][0]["value"] == 9  # mods did not corrupt
    # the set's inverse restores the child; rebasing the muted change
    # over it unmutes
    inv = cs.invert(setter, "inv")
    m3 = cs.rebase(m2, inv)
    assert m3["opt"].get("mods")
    restored = apply_to(after, inv)
    final = apply_to(restored, m3)
    assert [x["value"] for x in final["opt"][0]["fields"]["kids"]] == \
        [6, 5]


def test_mixed_kind_changeset():
    """Sequence and register fields compose/rebase side by side in one
    changeset."""
    base = {"seq": [n(1), n(2)], "opt": [n(0)]}
    a = {"seq": [cs.ins([n(9)])], "opt": cs.reg_set(n(7), n(0))}
    b = {"seq": [cs.skip(2), cs.ins([n(8)])]}
    cs.stamp(a, "ua")
    cs.stamp(b, "ub")
    b2 = cs.rebase(copy.deepcopy(b), a)
    final = apply_to(apply_to(base, a), b2)
    assert [x["value"] for x in final["seq"]] == [9, 1, 2, 8]
    assert final["opt"][0]["value"] == 7
    comp = cs.compose([a, b2])
    assert apply_to(base, comp) == final


def test_mixed_kind_concurrent_edits_converge_not_crash():
    """One client edits a field through the sequence surface while
    another uses the register surface (an app modeling error): the
    register change lowers to delete+insert and the document CONVERGES
    instead of wedging every replica with a rebase exception
    (code-review r4 reproduced exactly this crash)."""
    s, (ta, tb) = make_session()
    ta.insert_nodes(("cfg",), 0, [n(0)])
    s.process_all()
    # concurrent: A sequence-inserts, B register-sets
    ta.insert_nodes(("cfg",), 0, [n(1)])
    tb.set_register(("cfg",), n(2))
    s.process_all()          # must not raise
    assert ta.signature() == tb.signature()
    # and the reverse order on a fresh doc
    s2, (tc, td) = make_session()
    tc.insert_nodes(("cfg",), 0, [n(0)])
    s2.process_all()
    td.set_register(("cfg",), n(5))
    tc.insert_nodes(("cfg",), 1, [n(6)])
    s2.process_all()
    assert tc.signature() == td.signature()


def test_mixed_kind_compose_lowers():
    a = {"f": cs.reg_set(n(1), None)}
    b = {"f": [cs.skip(1), cs.ins([n(2)])]}
    cs.stamp(a, "ua")
    cs.stamp(b, "ub")
    comp = cs.compose([a, b])
    assert isinstance(comp["f"], list)  # lowered to sequence marks
    out = apply_to({"f": []}, comp)
    assert [x["value"] for x in out["f"]] == [1, 2]


# ---- algebra laws fuzz -----------------------------------------------

def _rand_reg(rng, uid):
    """Random register change authored against base {"opt": [n(-1)]}
    (old values reflect the author's view, as real authoring does)."""
    roll = rng.random()
    if roll < 0.5:
        ch = {"opt": cs.reg_set(
            n(rng.randint(0, 99)) if rng.random() < 0.8 else None,
            n(-1))}
    else:
        ch = {"opt": cs.reg_mods([cs.mod(
            value={"new": rng.randint(0, 99), "old": -1})])}
    return cs.stamp(ch, uid)


@pytest.mark.parametrize("seed", range(15))
def test_reg_laws_fuzz(seed):
    """rebaser.ts:138 laws on register changes:
    rebase(a, compose([b, c])) == rebase(rebase(a, b), c);
    compose([a, invert(a)]) applies as identity."""
    rng = random.Random(seed)
    base = {"opt": [n(-1)]}
    a = _rand_reg(rng, "a")
    b = _rand_reg(rng, "b")
    c = _rand_reg(rng, "c")
    lhs = cs.rebase(copy.deepcopy(a), cs.compose(
        [copy.deepcopy(b), cs.rebase(copy.deepcopy(c), b)]))
    rhs = cs.rebase(
        cs.rebase(copy.deepcopy(a), b),
        cs.rebase(copy.deepcopy(c), b))
    state = apply_to(apply_to(base, b),
                     cs.rebase(copy.deepcopy(c), b))
    assert apply_to(state, lhs) == apply_to(state, rhs), seed

    inv = cs.invert(copy.deepcopy(a), "inv")
    after = apply_to(base, a)
    assert apply_to(after, inv) == base, seed


@pytest.mark.parametrize("seed", range(10))
def test_mixed_laws_fuzz(seed):
    """Convergence across mixed-kind changesets: both rebase orders of
    two concurrent edits produce the same final tree."""
    rng = random.Random(100 + seed)

    def rand_change(uid):
        ch = {}
        if rng.random() < 0.7:
            marks = []
            if rng.random() < 0.5:
                marks.append(cs.skip(rng.randint(0, 1)))
            marks.append(
                cs.ins([n(rng.randint(0, 9))])
                if rng.random() < 0.6 else cs.dele(1))
            ch["seq"] = marks
        if rng.random() < 0.7:
            ch["opt"] = cs.reg_set(
                n(rng.randint(10, 19)) if rng.random() < 0.8
                else None, None)
        if not ch:
            ch["seq"] = [cs.ins([n(0)])]
        return cs.stamp(ch, uid)

    base = {"seq": [n(1), n(2), n(3)], "opt": [n(0)]}
    a = rand_change("a")
    b = rand_change("b")
    # order 1: a then rebase(b, a); order 2 must converge at the state
    # level when sequencing picks the same total order — emulate the
    # sequenced order [a, b]
    fin = apply_to(apply_to(base, a),
                   cs.rebase(copy.deepcopy(b), a))
    comp = cs.compose([copy.deepcopy(a),
                       cs.rebase(copy.deepcopy(b), a)])
    assert apply_to(base, comp) == fin, seed


# ---- DDS-level: concurrent optional fill converges LWW ---------------

def make_session():
    s = ContainerSession(["A", "B"])
    for cid in ("A", "B"):
        s.runtime(cid).create_datastore("ds").create_channel(
            "sharedtree", "t")
    return s, [
        s.runtime(cid).get_datastore("ds").get_channel("t")
        for cid in ("A", "B")
    ]


def test_concurrent_optional_fill_single_winner():
    s, (ta, tb) = make_session()
    schema = StoredSchema(
        nodes={"item": NodeSchema("item", value="any")},
        root_fields={"cfg": FieldSchema(kind=OPTIONAL,
                                        allowed_types=("item",))},
    )
    ta.set_stored_schema(schema)
    s.process_all()
    ta.set_register(("cfg",), n(1))
    tb.set_register(("cfg",), n(2))
    s.process_all()
    assert ta.signature() == tb.signature()
    # ONE winner (the later-sequenced set), not two nodes
    assert len(ta.get_field(("cfg",))) == 1
    assert ta.get_field(("cfg",))[0]["value"] == 2


def test_register_undo_restores_previous_value():
    s, (ta, tb) = make_session()
    ta.set_register(("cfg",), n(1))
    s.process_all()
    tb.set_register(("cfg",), n(2))
    s.process_all()
    assert ta.get_field(("cfg",))[0]["value"] == 2
    # schema-free editable surface
    root = ta.editable()
    root.field("cfg").set(n(3))
    s.process_all()
    assert tb.get_field(("cfg",))[0]["value"] == 3
    root.field("cfg").clear()
    s.process_all()
    assert ta.get_field(("cfg",)) == []
    assert ta.signature() == tb.signature()


def test_value_field_cannot_clear():
    s, (ta, _) = make_session()
    schema = StoredSchema(
        nodes={"item": NodeSchema("item", value="any")},
        root_fields={"v": FieldSchema(kind=VALUE, allowed_types=("item",))},
    )
    # a value field must hold a node for the tree to conform; fill it
    # via register first (schema validates on set)
    ta.set_register(("v",), n(1))
    s.process_all()
    ta.set_stored_schema(schema)
    s.process_all()
    with pytest.raises(SchemaViolation, match="cleared"):
        ta.set_register(("v",), None)


def test_set_register_rejected_on_sequence_field():
    s, (ta, _) = make_session()
    schema = StoredSchema(
        nodes={"item": NodeSchema("item", value="any")},
        root_fields={"items": FieldSchema(kind=SEQUENCE,
                                          allowed_types=("item",))},
    )
    ta.set_stored_schema(schema)
    s.process_all()
    with pytest.raises(SchemaViolation, match="sequence"):
        ta.set_register(("items",), n(1))
