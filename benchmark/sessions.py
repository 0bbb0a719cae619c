"""Seeded co-authoring sessions, recorded in sequenced order.

A session is one document's op stream from ``n_clients`` writers. It
follows the bench corpus's model of concurrency (the program's
``testing.fuzz`` recorder, rebuilt here on the plain reference): every
client sees the same sequenced prefix, the first ``R`` ops; a local op
is sent with refSeq ``R`` and sequenced at once; a "process" step lets
the clients catch up on a random share of the ops they have not seen
(``R`` advances by 1 .. S - R). The op's positions are drawn in its own
view, so every op is valid where the reference applies it.

Two op mixes, named in a configuration's ``mixes``:

- ``fuzz``: generic editing, weights for insert / remove / annotate /
  process, inserts of 1..max_insert_len letters, annotates of a few
  keys with values or deletions;
- ``flow``: the webflow editor mix: words with class props, tag pairs
  (end marker, then begin marker), short removes, class annotates,
  paragraph and line-break markers. Unlike the program's recorder its
  removes are plain ranges (the pair-consistent walk is a document-level
  rule, not a merge rule).

Each recorded op is ``(client, csn, refseq, seq, msn, op)`` with ``op``
as ``reference.MergeDoc.apply`` takes it. ``seq`` and ``msn`` are what
the deli rule gives: seq after the ``n_clients`` joins; msn = the least
refSeq over the clients, a client's refSeq starting one below its join.
"""
from __future__ import annotations

import random
import string

from .reference import MergeDoc

WORDS = ("flow", "tensor", "lattice", "quorum", "spline", "glyph")
TAGS = ("em", "strong", "code", "span", "h1", "h2")


def record_session(seed: int, mix: dict, n_clients: int,
                   n_ops: int) -> list:
    rng = random.Random(seed)
    doc = MergeDoc()
    refs = [c for c in range(n_clients)]   # client c joined at seq c + 1
    csn = [0] * n_clients
    state = {"seq": n_clients, "ref": n_clients, "msn": 0, "pairs": 0}
    out: list = []

    def emit(client: int, op: tuple) -> None:
        doc.apply(op, state["seq"] + 1, state["ref"], client)
        state["seq"] += 1
        csn[client] += 1
        refs[client] = state["ref"]
        state["msn"] = max(state["msn"], min(refs))
        out.append((client, csn[client], state["ref"], state["seq"],
                    state["msn"], op))

    step = _STEPS[mix["kind"]]
    while len(out) < n_ops:
        pending = state["seq"] - state["ref"]
        if pending and rng.random() < mix["process"]:
            state["ref"] += rng.randint(1, pending)
            continue
        client = rng.randrange(n_clients)
        step(rng, mix, state, client,
             doc.view_length(state["ref"], client), emit)
    return out[:n_ops]


def _fuzz_step(rng, mix, state, client, n, emit) -> None:
    kinds = ["ins"] + (["rem", "ann"] if n else [])
    weights = [mix["insert"]] + ([mix["remove"], mix["annotate"]]
                                 if n else [])
    kind = rng.choices(kinds, weights=weights)[0]
    if kind == "ins":
        text = "".join(rng.choices(string.ascii_lowercase,
                                   k=rng.randint(1, mix["max_insert_len"])))
        emit(client, ("ins", rng.randint(0, n), 0, text, None))
        return
    start = rng.randint(0, n - 1)
    end = rng.randint(start + 1, n)
    if kind == "rem":
        emit(client, ("rem", start, end, None, None))
    else:
        emit(client, ("ann", start, end, None,
                      {rng.choice(mix["keys"]): rng.choice(mix["values"])}))


def _flow_step(rng, mix, state, client, n, emit) -> None:
    roll = rng.random()
    if roll < 0.34 or n < 4:
        props = ({"class": rng.choice(("hero", "note"))}
                 if rng.random() < 0.3 else None)
        emit(client, ("ins", rng.randint(0, n), 0, rng.choice(WORDS), props))
    elif roll < 0.50:
        a = rng.randrange(n - 2)
        b = rng.randint(a + 1, min(n, a + 9))
        state["pairs"] += 1
        pid = f"p{client}-{state['pairs']}"
        emit(client, ("ins", b, 0, None, {"pairId": pid}))
        emit(client, ("ins", a, 0, None,
                      {"tag": rng.choice(TAGS), "pairId": pid}))
    elif roll < 0.64:
        a = rng.randrange(n - 2)
        emit(client, ("rem", a, rng.randint(a + 1, min(n, a + 7)),
                      None, None))
    elif roll < 0.86:
        a = rng.randrange(n - 2)
        emit(client, ("ann", a, rng.randint(a + 1, min(n, a + 10)), None,
                      {"class": rng.choice(("hot", "cold", "muted",
                                            "alert", None))}))
    elif rng.random() < 0.5:
        emit(client, ("ins", rng.randint(0, n), 0, None,
                      {"heading": rng.choice((1, 2))}))
    else:
        emit(client, ("ins", rng.randint(0, n), 0, None, None))


_STEPS = {"fuzz": _fuzz_step, "flow": _flow_step}


def mix_of(mixes: list, index: int) -> dict:
    """The mix of session ``index``: ``mixes`` entries in turn, each
    taking ``every`` consecutive slots of a cycle (fuzz 3, flow 1 gives
    the bench corpus's 3/4 : 1/4)."""
    cycle = [m for m in mixes for _ in range(m["every"])]
    return cycle[index % len(cycle)]
