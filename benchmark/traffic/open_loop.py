"""Open-loop co-authoring traffic: seeded sessions of concurrent
writers, ops arriving on a fixed schedule, ticketed by the deli
sequencer, ingested and applied by the sidecar.

Parameters (the workload file):
- ``rate_ops_per_s``: offered ops per second over the whole cell;
- ``active_docs``: documents receiving ops at a time, each at an equal
  share of the rate; with ``swap_every_s`` and ``swap_count``, that many
  active documents are swapped for cold ones at each period;
- ``round_cap_merge_ops``: merge ops one document may bring into one
  round (the top prewarmed window bucket); later ones wait a round.

The schedule holds exactly ``round(rate * seconds)`` arrivals, uniform
on the window (a Poisson process given its count), each on an active
document drawn uniformly. A document's k-th arrival carries op k of its
session, with the session's client, csn and refSeq. Session ``d mod
sessions`` serves document d (the configuration's reuse factor).

Each op is timed from the instant it was due to the return of the
``apply`` whose settle read the overflow flag of the round that held it
(or the ``sync`` that did, when no op was due). Ops unmerged at the
window's end go through an untimed drain and keep their true latency.
"""
from __future__ import annotations

import time

import numpy as np

from ..harness import (
    build_sidecar,
    compare,
    envelope,
    intern_signature,
    merge_ops,
    read_served,
)
from ..reference import MergeDoc
from ..sessions import mix_of, record_session

LOOP = "open"


class Traffic:
    def __init__(self, cfg: dict, wl: dict, sizes: dict, seed: int,
                 seconds: float, spans, tracer):
        self.cfg, self.wl, self.sizes = cfg, wl, sizes
        self.seed, self.seconds = seed, seconds
        self.spans, self.tracer = spans, tracer
        self.n_docs = sizes["docs"]
        self.n_clients = cfg["clients"]

    # ------------------------------------------------------------------
    # set-up

    def schedule(self) -> None:
        rng = np.random.default_rng(self.seed)
        n = int(round(self.wl["rate_ops_per_s"] * self.seconds
                      * self.sizes.get("rate_scale", 1.0)))
        self.due = np.sort(rng.uniform(0.0, self.seconds, n))
        active = self.wl["active_docs"] * self.n_docs // self.cfg["docs"]
        every = self.wl.get("swap_every_s")
        if active >= self.n_docs or not every:
            self.doc = rng.integers(0, self.n_docs, n)
        else:
            hot = rng.choice(self.n_docs, active, replace=False)
            period = (self.due // every).astype(np.int64)
            swap = max(1, self.wl["swap_count"] * active
                       // self.wl["active_docs"])
            self.doc = np.empty(n, np.int64)
            pick = rng.integers(0, active, n)
            for p in range(int(period[-1]) + 1 if n else 0):
                at = period == p
                self.doc[at] = hot[pick[at]]
                cold = np.setdiff1d(np.arange(self.n_docs), hot)
                out = rng.choice(active, swap, replace=False)
                hot = hot.copy()
                hot[out] = rng.choice(cold, swap, replace=False)
        order = np.argsort(self.doc, kind="stable")
        counts = np.bincount(self.doc, minlength=self.n_docs)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        self.rank = np.empty(n, np.int64)
        self.rank[order] = np.arange(n) - starts[self.doc[order]]
        self.counts = counts

    def record_sessions(self) -> None:
        n_sess = self.sizes["sessions"]
        need = np.zeros(n_sess, np.int64)
        np.maximum.at(need, np.arange(self.n_docs) % n_sess, self.counts)
        self.sessions = [
            record_session(f"{self.seed}:{s}", mix_of(self.cfg["mixes"], s),
                           self.n_clients, int(need[s]))
            for s in range(n_sess)]
        self.envelopes = [[envelope(rec[5]) for rec in sess]
                          for sess in self.sessions]
        self.weights = [[merge_ops(rec[5]) for rec in sess]
                        for sess in self.sessions]
        self.inserts = [np.cumsum([rec[5][0] == "ins" for rec in sess])
                        for sess in self.sessions]

    def setup(self):
        from fluidframework_tpu.protocol.messages import ClientDetail
        from fluidframework_tpu.service.sequencer import DocumentSequencer

        self.schedule()
        self.record_sessions()
        self.sidecar = build_sidecar(self.sizes)
        self.sidecar.prewarm(max_bucket=self.sizes["max_bucket"])
        self.sequencers = []
        for d in range(self.n_docs):
            doc_id = f"doc-{d}"
            self.sidecar.track(doc_id, "d", "s")
            seq = DocumentSequencer(doc_id)
            for c in range(self.n_clients):
                self.sidecar.ingest(doc_id,
                                    seq.client_join(ClientDetail(f"c{c}")))
            self.sequencers.append(seq)
        self.sidecar.apply()
        self.sidecar.sync()
        return self.sidecar

    # ------------------------------------------------------------------
    # the window

    def _take(self, carry: list, lo: int, hi: int) -> tuple[list, list]:
        cap = self.wl["round_cap_merge_ops"]
        n_sess = self.sizes["sessions"]
        load: dict[int, int] = {}
        taken, left = [], []
        for a in carry + list(range(lo, hi)):
            d = int(self.doc[a])
            w = self.weights[d % n_sess][self.rank[a]]
            if load.get(d, 0) + w > cap:
                left.append(a)
                load[d] = cap + 1      # keep the doc's later ops behind
                continue
            load[d] = load.get(d, 0) + w
            taken.append(a)
        return taken, left

    def _serve(self, batch: list) -> None:
        from fluidframework_tpu.protocol.messages import (
            DocumentMessage,
            MessageType,
        )

        n_sess = self.sizes["sessions"]
        msgs = []
        with self.spans("ticket"):
            for a in batch:
                d = int(self.doc[a])
                r = int(self.rank[a])
                client, csn, ref = self.sessions[d % n_sess][r][:3]
                res = self.sequencers[d].ticket(f"c{client}", DocumentMessage(
                    client_sequence_number=csn,
                    reference_sequence_number=ref,
                    type=MessageType.OPERATION,
                    contents=self.envelopes[d % n_sess][r]))
                msg = res.message
                if msg is None:
                    self.nacked += 1
                    continue
                self.stamped[a] = (msg.sequence_number,
                                   msg.minimum_sequence_number)
                msgs.append((f"doc-{d}", msg))
        with self.spans("ingest"):
            for doc_id, msg in msgs:
                self.sidecar.ingest(doc_id, msg)
        self.ops_served += len(batch)
        with self.spans("apply"):
            self.sidecar.apply()

    def window(self) -> dict:
        sc = self.sidecar
        n = len(self.due)
        self.merged = np.full(n, np.nan)
        self.taken_at = np.full(n, np.nan)
        self.stamped = np.zeros((n, 2), np.int64)
        self.nacked = 0
        self.ops_served = 0
        stats0 = dict(sc.stats)
        inflight = None
        carry: list = []
        lo = 0
        rounds = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= self.seconds:
                break
            hi = int(np.searchsorted(self.due, now, side="right"))
            batch, carry = self._take(carry, lo, hi)
            lo = hi
            if batch:
                self.taken_at[batch] = now
                self._serve(batch)
                rounds += 1
                t = time.perf_counter() - t0
                if inflight is not None:
                    self.merged[inflight] = t
                inflight = batch
                if self.tracer.state == "open":
                    self.tracer.note_round(self._round_bytes(batch))
            elif inflight is not None:
                with self.spans("sync"):
                    sc.sync()
                self.merged[inflight] = time.perf_counter() - t0
                inflight = None
            else:
                nxt = self.due[lo] if lo < n else self.seconds
                with self.spans("idle"):
                    time.sleep(max(0.0, min(nxt, self.seconds) - now))
            if self.tracer.enabled:
                before = self.tracer.state
                self.tracer.at_round_boundary(
                    sc, time.perf_counter() - t0, self.seconds)
                if self.tracer.state != before and inflight is not None:
                    self.merged[inflight] = time.perf_counter() - t0
                    inflight = None
        window_s = time.perf_counter() - t0
        self.tracer.close(sc)
        queued_at_end = lo - int(np.count_nonzero(
            ~np.isnan(self.taken_at[:lo]))) + len(carry)
        unmerged_at_end = int(np.count_nonzero(np.isnan(self.merged)))
        served_in_window = self.ops_served
        stats1 = dict(sc.stats)
        spans_s = dict(self.spans.seconds)
        # untimed drain: everything due, then the last settle
        if inflight is not None or carry or lo < n:
            while carry or lo < n:
                batch, carry = self._take(carry, lo, n)
                lo = n
                self.taken_at[batch] = time.perf_counter() - t0
                self._serve(batch)
                t = time.perf_counter() - t0
                if inflight is not None:
                    self.merged[inflight] = t
                inflight = batch
            sc.sync()
            self.merged[inflight] = time.perf_counter() - t0
        lat_ms = (self.merged - self.due) * 1000.0
        late_ms = (self.taken_at - self.due) * 1000.0
        d_rounds = stats1["rounds"] - stats0["rounds"]
        return {
            "loop": LOOP,
            "window_s": window_s,
            "ops": n,
            "rounds": rounds,
            "latency_ms": lat_ms,
            "generator_late_ms_p95": float(np.percentile(late_ms, 95)),
            "ops_in_window": served_in_window,
            "intake_per_round": served_in_window / max(rounds, 1),
            "queued_at_end": queued_at_end,
            "unmerged_at_end": unmerged_at_end,
            "spans_s": spans_s,
            "pack_s": stats1["pack_s"] - stats0["pack_s"],
            "settle_s": stats1["settle_s"] - stats0["settle_s"],
            "sidecar_rounds": d_rounds,
            "nacked": self.nacked,
        }

    def _round_bytes(self, batch: list) -> dict:
        """Per document with ops in the round: its real merge ops and
        its segment count from the op stream (inserts so far)."""
        n_sess = self.sizes["sessions"]
        docs: dict[int, list] = {}
        for a in batch:
            d = int(self.doc[a])
            docs.setdefault(d, []).append(int(self.rank[a]))
        out = {}
        for d, ranks in docs.items():
            top = max(ranks)
            out[d] = (sum(self.weights[d % n_sess][r] for r in ranks),
                      int(self.inserts[d % n_sess][top]))
        return out

    # ------------------------------------------------------------------
    # the check

    def check(self, control: str | None = None) -> tuple[dict, dict | None]:
        """The numbers compared for the served answers; with ``control``,
        also for the control's answers in their place."""
        sc = self.sidecar
        checks = {}
        texts, sigs = read_served(sc, self.n_docs)
        checks["host_mode_docs"] = sc.host_mode_docs()
        checks["unapplied_ops"] = sc.queued_ops
        checks["overflowed_docs"] = int(sc.overflowed())
        checks["nacked_ops"] = self.nacked
        expect = np.array([(rec[3], rec[4]) for a in range(len(self.due))
                           for rec in [self.sessions[
                               int(self.doc[a]) % self.sizes["sessions"]][
                               int(self.rank[a])]]], np.int64).reshape(-1, 2)
        checks["seq_msn_drift"] = int(np.count_nonzero(
            (expect != self.stamped).any(axis=1)))
        self.sidecar = None
        expected = self.reference()
        sound = {**checks, **compare(expected, texts, sigs)}
        if not control:
            return sound, None
        served = self.reference(control=control)
        return sound, {**checks, **compare(
            expected, [t for t, _ in served],
            [intern_signature(sig) for _, sig in served])}

    def reference(self, control: str | None = None) -> list:
        """Per document: (text, signature) after its own prefix of its
        session, from one pass over each session. ``control="tip_view"``
        breaks the converged-text guarantee: every op resolves in the
        tip view of its client (refSeq = seq - 1), as a merge that
        ignored concurrency would."""
        n_sess = self.sizes["sessions"]
        out: list = [None] * self.n_docs
        for s, sess in enumerate(self.sessions):
            docs = sorted(range(s, self.n_docs, n_sess),
                          key=lambda d: self.counts[d])
            ref = MergeDoc()
            done = 0
            for d in docs:
                while done < self.counts[d]:
                    client, _csn, rseq, seq, _msn, op = sess[done]
                    if control == "tip_view":
                        rseq = seq - 1
                    try:
                        ref.apply(op, seq, rseq, client)
                    except ValueError:
                        pass      # a control op its view cannot hold
                    done += 1
                out[d] = (ref.text(), [(ch, dict(p))
                                       for ch, p in ref.signature()])
        return out

