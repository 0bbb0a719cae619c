"""Closed-loop replay: every document's single-writer op log, already
sequenced (refSeq = the previous seq), read a batch at a time and fed
to the sidecar; the next batch is ingested as soon as ``apply``
returns.

Parameters (the workload file):
- ``batch_ops``: ops of every document per round (the top prewarmed
  window bucket, the replay driver's batch).

The configuration's ``typing`` block shapes the sessions
(``sequential.TypingSessions``), generated a round at a time so no run
runs out of them. The rate is the ops of every round dispatched in the
window over the time from its start to the settle of the last of them.
"""
from __future__ import annotations

import time

import numpy as np

from ..harness import build_sidecar, compare, read_served
from ..reference import SequentialDoc
from ..sequential import TypingSessions

LOOP = "closed"


class Traffic:
    def __init__(self, cfg: dict, wl: dict, sizes: dict, seed: int,
                 seconds: float, spans, tracer):
        self.cfg, self.wl, self.sizes = cfg, wl, sizes
        self.seed, self.seconds = seed, seconds
        self.spans, self.tracer = spans, tracer
        self.n_docs = sizes["docs"]
        self.k = wl["batch_ops"]

    def setup(self):
        from fluidframework_tpu.protocol.messages import ClientDetail
        from fluidframework_tpu.service.sequencer import DocumentSequencer

        self.gen = TypingSessions(self.seed, self.n_docs, self.cfg["typing"])
        self.log: list = []          # per round: (is_insert, pos, letter)
        self.next_seq = 2            # seq 1 is the writer's join
        self.sidecar = build_sidecar(self.sizes)
        self.sidecar.prewarm(max_bucket=self.sizes["max_bucket"])
        for d in range(self.n_docs):
            doc_id = f"doc-{d}"
            self.sidecar.track(doc_id, "d", "s")
            self.sidecar.ingest(doc_id, DocumentSequencer(doc_id).client_join(
                ClientDetail("w")))
        self.sidecar.apply()
        self.sidecar.sync()
        return self.sidecar

    def _read_round(self) -> list:
        """The next batch of every document's log as sequenced
        messages, the way a replay driver reads it."""
        from fluidframework_tpu.models.mergetree.ops import InsertOp, RemoveOp
        from fluidframework_tpu.protocol.messages import (
            MessageType,
            SequencedMessage,
        )

        ins, pos, letter = self.gen.round(self.k)
        self.log.append((ins, pos, letter))
        base = self.next_seq
        self.next_seq += self.k
        ins_l, pos_l, let_l = ins.tolist(), pos.tolist(), letter.tolist()
        out = []
        for d in range(self.n_docs):
            msgs = []
            for j in range(self.k):
                p = pos_l[d][j]
                op = (InsertOp(pos1=p, text=let_l[d][j]) if ins_l[d][j]
                      else RemoveOp(pos1=p, pos2=p + 1))
                s = base + j
                msgs.append(SequencedMessage(
                    client_id="w", sequence_number=s,
                    minimum_sequence_number=s - 1,
                    client_sequence_number=s - 1,
                    reference_sequence_number=s - 1,
                    type=MessageType.OPERATION,
                    contents={"kind": "op", "address": "d", "channel": "s",
                              "contents": op}))
            out.append((f"doc-{d}", msgs))
        return out

    def window(self) -> dict:
        sc = self.sidecar
        stats0 = dict(sc.stats)
        rounds = 0
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < self.seconds
               or self.tracer.wants_rounds):
            with self.spans("read"):
                batch = self._read_round()
            with self.spans("ingest"):
                for doc_id, msgs in batch:
                    for msg in msgs:
                        sc.ingest(doc_id, msg)
            with self.spans("apply"):
                sc.apply()
            rounds += 1
            self.tracer.note_round(self._round_bytes())
            self.tracer.at_round_boundary(sc, time.perf_counter() - t0,
                                          self.seconds)
        self.tracer.close(sc)
        with self.spans("sync"):
            sc.sync()
        window_s = time.perf_counter() - t0
        stats1 = dict(sc.stats)
        return {
            "loop": LOOP,
            "window_s": window_s,
            "rounds": rounds,
            "ops": rounds * self.k * self.n_docs,
            "ops_in_window": rounds * self.k * self.n_docs,
            "spans_s": dict(self.spans.seconds),
            "pack_s": stats1["pack_s"] - stats0["pack_s"],
            "settle_s": stats1["settle_s"] - stats0["settle_s"],
            "sidecar_rounds": stats1["rounds"] - stats0["rounds"],
        }

    def _round_bytes(self) -> dict | None:
        """Every document has ``batch_ops`` real ops in a round; its
        segment count from the op stream is its inserts so far."""
        if self.tracer.state != "open":
            return None
        inserts = sum(r[0].sum(axis=1) for r in self.log)
        return {d: (self.k, int(inserts[d])) for d in range(self.n_docs)}

    def check(self, control: str | None = None) -> tuple[dict, dict | None]:
        """The numbers compared for the served answers; with ``control``,
        also for the control's answers in their place."""
        sc = self.sidecar
        texts, sigs = read_served(sc, self.n_docs)
        checks = {"host_mode_docs": sc.host_mode_docs(),
                  "unapplied_ops": sc.queued_ops,
                  "overflowed_docs": int(sc.overflowed())}
        self.sidecar = None
        expected = self.reference()
        sound = {**checks, **compare(expected, texts, sigs)}
        if not control:
            return sound, None
        served = self.reference(control=control)
        return sound, {**checks, **compare(
            expected, [t for t, _ in served],
            [[(ch, (0, 0, 0, 0)) for ch, _ in sig] for _, sig in served])}

    def reference(self, control: str | None = None) -> list:
        """Per document, its log replayed on a string. ``control=
        "drop_op"`` breaks apply-exactly-once: the last op of each of a
        document's batches is left out."""
        out = []
        for d in range(self.n_docs):
            doc = SequentialDoc()
            for ins, pos, letter in self.log:
                for j in range(self.k):
                    if control == "drop_op" and j == self.k - 1:
                        continue
                    p = int(pos[d, j])
                    try:
                        doc.apply(("ins", p, 0, letter[d, j], None)
                                  if ins[d, j] else ("rem", p, p + 1,
                                                     None, None))
                    except ValueError:
                        pass      # a control op its text cannot hold
            out.append((doc.text(), doc.signature()))
        return out
