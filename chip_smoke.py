"""Chip smoke: the merge-serving path, once, on the TPU, at serving size.

    python chip_smoke.py              # one chip, the driver's run
    python chip_smoke.py --chips 4    # the mesh pools on four chips
    python chip_smoke.py --cpu-rehearsal [--chips 4]   # tests only

One process drives the system through the entry points a service uses:
sequenced op streams from the deli sequencer (``DocumentSequencer``),
``TpuMergeSidecar.ingest`` / ``apply`` (pipelined, backend-default route,
donation on) / settle, and reads through ``text()``; then a few requests
through the real TCP ingress (``AlfredServer``). Every document's text is
compared with scalar oracles. Each phase prints one JSON line; the last
line is ``{"ok": true, "device": {...}}``. Any failure exits non-zero.

With no TPU the default mode exits non-zero before any phase runs:
``--cpu-rehearsal`` exists for tests only, runs at a tiny size on the
CPU, and never reports a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SIZES = {
    # the sidecar's own defaults (max_docs=1024, capacity=1024); the
    # long documents are insert-heavy and outgrow 1024 slots, so the
    # on-device ladder regrow runs
    "full": dict(docs=1024, capacity=1024, clients=3, steps=160,
                 long_docs=4, long_steps=1100, round_ops=8),
    "tiny": dict(docs=16, capacity=128, clients=3, steps=40,
                 long_docs=2, long_steps=160, round_ops=8),
}
# --chips 4: documents per sidecar, how many of them outgrow the slab
# into the pool, and the slab (= ladder top, so overflow admits to the
# pool at once)
MESH_SIZES = {
    "full": dict(docs=64, long_docs=5, capacity=1024, edits=40,
                 long_edits=1300, hot_rounds=6, hot_edits=24),
    "tiny": dict(docs=8, long_docs=5, capacity=64, edits=10,
                 long_edits=100, hot_rounds=6, hot_edits=8),
}


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class CompileMeter:
    """XLA compile requests (JAX's backend-compile event, which also
    wraps the ones the persistent cache serves) and persistent-cache
    hits, so each phase can say what it spent compiling."""

    def __init__(self):
        import jax

        self.requests = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.retrieval_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieval_s += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_requests": self.requests,
                "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "cache_retrieval_s": self.retrieval_s}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ----------------------------------------------------------------------
# corpus: recorded sessions, re-ticketed through a fresh sequencer


def build_corpus(sz: dict, seed: int) -> list:
    """Per document: (converged text of its MergeTreeClient replicas,
    recorded sequenced stream). The bench corpus mix, plus
    ``long_docs`` insert-heavy sessions that outgrow the slab."""
    from fluidframework_tpu.testing import (
        FuzzConfig,
        record_corpus_stream,
        record_op_stream,
    )

    corpus = []
    for i in range(sz["docs"]):
        if i < sz["long_docs"]:
            corpus.append(record_op_stream(FuzzConfig(
                n_clients=sz["clients"], n_steps=sz["long_steps"],
                seed=seed + 100_000 + i, insert_weight=0.9,
                remove_weight=0.0, annotate_weight=0.1,
                process_weight=0.15, max_insert_len=4,
            )))
        else:
            corpus.append(record_corpus_stream(
                i, seed, sz["clients"], sz["steps"]))
    return corpus


class DocFeed:
    """Re-tickets one recorded session through a fresh deli sequencer,
    wrapping each op in the channel envelope the sidecar tracks. The
    re-ticketed sequence and msn must equal the recording's."""

    def __init__(self, doc_id: str, recorded: list):
        from fluidframework_tpu.service.sequencer import DocumentSequencer

        self.doc_id = doc_id
        self.sequencer = DocumentSequencer(doc_id)
        self.recorded = recorded
        self.pos = 0

    @property
    def done(self) -> bool:
        return self.pos >= len(self.recorded)

    def next_round(self, n_ops: int) -> list:
        from fluidframework_tpu.protocol.messages import (
            DocumentMessage,
            MessageType,
        )

        out = []
        ops = 0
        while not self.done and ops < n_ops:
            rec = self.recorded[self.pos]
            self.pos += 1
            if rec.type == MessageType.CLIENT_JOIN:
                msg = self.sequencer.client_join(rec.contents)
            else:
                check(rec.type == MessageType.OPERATION,
                      f"unexpected recorded {rec.type}")
                res = self.sequencer.ticket(rec.client_id, DocumentMessage(
                    client_sequence_number=rec.client_sequence_number,
                    reference_sequence_number=rec.reference_sequence_number,
                    type=MessageType.OPERATION,
                    contents={"kind": "op", "address": "d",
                              "channel": "s", "contents": rec.contents},
                ))
                check(res.ok, f"{self.doc_id}: nack {res.nack}")
                msg = res.message
                ops += 1
            check(msg.sequence_number == rec.sequence_number
                  and msg.minimum_sequence_number
                  == rec.minimum_sequence_number,
                  f"{self.doc_id}: re-ticketed seq/msn drifted")
            out.append(msg)
        return out


def native_texts(recorded: list) -> list:
    """Every document's text from the C++ scalar replayer, fed its own
    encoding of the recorded streams (not the sidecar's)."""
    import numpy as np

    from fluidframework_tpu.native.replay_baseline import MergeHostSession
    from fluidframework_tpu.ops import encode_stream
    from fluidframework_tpu.ops.host_bridge import OP_FIELDS

    streams = [encode_stream(stream) for stream in recorded]
    session = MergeHostSession(len(streams))
    try:
        rows = np.array([[op[f] for f in OP_FIELDS]
                         for s in streams for op in s.ops], np.int32)
        doc_of_row = np.repeat(np.arange(len(streams), dtype=np.int32),
                               [len(s.ops) for s in streams])
        session.apply(rows, doc_of_row)
        return [session.text(d, s) for d, s in enumerate(streams)]
    finally:
        session.close()


# ----------------------------------------------------------------------
# phases


def phase_compile(sz: dict, meter: CompileMeter) -> dict:
    """Ahead-of-time compiles of the two executor routes' window
    programs at the serving shape (docs x capacity, first window
    bucket): cold, then again after dropping JAX's in-memory caches so
    the persistent cache serves it."""
    import jax

    from fluidframework_tpu.ops import event_graph, make_table, merge_kernel
    from fluidframework_tpu.ops.host_bridge import pack_rows
    from fluidframework_tpu.ops.segment_table import KIND_NOOP, OpBatch

    noop = dict(kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0,
                client=0, op_id=0, length=0, is_marker=0, prop_key=0,
                prop_val=0, min_seq=0)
    table = make_table(sz["docs"], sz["capacity"])
    arrays = pack_rows(sz["docs"], {0: [noop]}, bucket_floor=16)
    programs = {
        "scan": (merge_kernel.compiled_window(),
                 (table, OpBatch(**{f: arrays[f]
                                    for f in OpBatch._fields}))),
        "egwalker": event_graph.compiled_window(
            table, event_graph.build_event_graph(
                arrays, k_max=event_graph.EG_K)["prefix"]),
    }
    out = {"phase": "compile", "docs": sz["docs"],
           "capacity": sz["capacity"], "window": 16}
    for name, (jitted, args) in programs.items():
        for label in ("cold", "cached"):
            if label == "cached":
                jax.clear_caches()
            before = meter.snapshot()
            t0 = time.perf_counter()
            jitted.lower(*args).compile()
            out[f"{name}_{label}_s"] = time.perf_counter() - t0
            out[f"{name}_{label}_cache_hits"] = \
                meter.since(before)["cache_hits"]
    return out


def phase_serving(sz: dict, seed: int, meter: CompileMeter,
                  device) -> dict:
    from fluidframework_tpu.qos.breaker import CircuitBreaker
    from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar

    t0 = time.perf_counter()
    corpus = build_corpus(sz, seed)
    feeds = [DocFeed(f"doc-{d}", stream)
             for d, (_, stream) in enumerate(corpus)]
    gen_s = time.perf_counter() - t0

    opened = []
    breaker = CircuitBreaker("smoke-dispatch",
                             on_open=lambda b: opened.append(
                                 repr(b.last_error)))
    sidecar = TpuMergeSidecar(max_docs=sz["docs"],
                              capacity=sz["capacity"], breaker=breaker)
    for feed in feeds:
        sidecar.track(feed.doc_id, "d", "s")

    before = meter.snapshot()
    rounds = applied = 0
    ticket_s = apply_s = 0.0
    first_round_s = None
    t_serve = time.perf_counter()
    while not all(f.done for f in feeds):
        tr = time.perf_counter()
        for feed in feeds:
            for msg in feed.next_round(sz["round_ops"]):
                sidecar.ingest(feed.doc_id, msg)
        ta = time.perf_counter()
        ticket_s += ta - tr
        applied += sidecar.apply()
        apply_s += time.perf_counter() - ta
        rounds += 1
        if first_round_s is None:
            sidecar.sync()
            first_round_s = time.perf_counter() - tr
    sidecar.sync()
    serve_s = time.perf_counter() - t_serve
    compiles = meter.since(before)

    t0 = time.perf_counter()
    served = [sidecar.text(f.doc_id, "d", "s") for f in feeds]
    read_s = time.perf_counter() - t0
    native = native_texts([stream for _, stream in corpus])
    oracle_mismatch = [d for d, (want, _) in enumerate(corpus)
                       if served[d] != want]
    native_mismatch = [d for d in range(len(feeds))
                       if served[d] != native[d]]
    record = {
        "phase": "serving",
        "docs": sz["docs"], "capacity_start": sz["capacity"],
        "capacity_end": sidecar.capacity,
        "clients_per_doc": sz["clients"], "steps_per_stream": sz["steps"],
        "long_docs": sz["long_docs"], "round_ops_per_doc": sz["round_ops"],
        "executor": sidecar.executor, "donate": sidecar.donate,
        "pipeline": sidecar.pipeline,
        "rounds": rounds, "ops_applied": applied,
        "corpus_gen_s": gen_s,
        "first_round_s": first_round_s,
        "serve_wall_s": serve_s,
        "ticket_ingest_s": ticket_s, "apply_call_s": apply_s,
        "host_pack_s": sidecar.stats["pack_s"],
        "settle_wait_s": sidecar.stats["settle_s"],
        "read_s": read_s,
        **{f"serve_{k}": v for k, v in compiles.items()},
        "regrows": sidecar.grow_count,
        "host_mode_docs": sidecar.host_mode_docs(),
        "pooled_docs": sidecar.pooled_docs(),
        "breaker_opened": len(opened),
        "oracle_mismatches": len(oracle_mismatch),
        "native_mismatches": len(native_mismatch),
        "peak_bytes_in_use": peak_bytes(device),
    }
    emit(record)
    check(not oracle_mismatch,
          f"text differs from the MergeTreeClient oracle on docs "
          f"{oracle_mismatch[:8]}")
    check(not native_mismatch,
          f"text differs from the C++ replayer on docs "
          f"{native_mismatch[:8]}")
    check(sidecar.host_mode_docs() == 0,
          "documents left the device path")
    check(sidecar.grow_count >= 1, "no on-device ladder regrow ran")
    check(not opened, f"circuit breaker opened: {opened}")
    check(not sidecar.overflowed(), "a document is still overflowed")
    return record


def start_alfred():
    import asyncio
    import threading

    from fluidframework_tpu.service.ingress import AlfredServer

    server = AlfredServer()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(server.start())
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    check(started.wait(30), "AlfredServer did not start")

    def on_loop(fn):
        """Run ``fn`` on the server's event loop, where the broadcaster
        calls ``ingest``: the sidecar is not shared across threads."""
        async def call():
            return fn()

        return asyncio.run_coroutine_threadsafe(call(), loop).result(60)

    def stop():
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(30)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(30)
        check(not thread.is_alive(), "AlfredServer thread did not stop")

    return server, on_loop, stop


def phase_requests(seed: int, meter: CompileMeter, device) -> dict:
    """Two socket clients edit one document through the TCP ingress; a
    sidecar subscribed on the server side must serve their text."""
    import random

    from fluidframework_tpu.drivers.socket_driver import (
        SocketDocumentService,
    )
    from fluidframework_tpu.loader import Container
    from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar

    doc = "smoke-requests"
    rng = random.Random(seed)
    before = meter.snapshot()
    t0 = time.perf_counter()
    server, on_loop, stop = start_alfred()
    services = []
    try:
        sidecar = TpuMergeSidecar(max_docs=8, capacity=256)
        sidecar.subscribe(server.local, doc, "app", "s")
        svc_a = SocketDocumentService("127.0.0.1", server.port, doc)
        services.append(svc_a)
        with svc_a.lock:
            ca = Container.load(svc_a, client_id="ana")
            sa = ca.runtime.create_datastore("app").create_channel(
                "sharedstring", "s")
            ca.flush()
        pump(svc_a, ca)
        svc_b = SocketDocumentService("127.0.0.1", server.port, doc)
        services.append(svc_b)
        with svc_b.lock:
            cb = Container.load(svc_b, client_id="ben")
            sb = cb.runtime.get_datastore("app").get_channel("s")
        submitted = 0
        for _ in range(6):
            for svc, c, s, tag in ((svc_a, ca, sa, "a"),
                                   (svc_b, cb, sb, "b")):
                with svc.lock:
                    for _ in range(3):
                        n = s.get_length()
                        if n > 4 and rng.random() < 0.25:
                            lo = rng.randrange(n - 2)
                            s.remove_text(lo, lo + 2)
                        else:
                            s.insert_text(rng.randint(0, n), tag * 3)
                        submitted += 1
                    c.flush()
            on_loop(sidecar.apply)
        pump(svc_a, ca)
        pump(svc_b, cb)
        wait_converged(svc_a, sa, svc_b, sb)
        on_loop(sidecar.apply)
        with svc_a.lock:
            text_a = sa.get_text()
        with svc_b.lock:
            text_b = sb.get_text()
        text_sc = on_loop(lambda: sidecar.text(doc, "app", "s"))
        for svc, c in ((svc_a, ca), (svc_b, cb)):
            with svc.lock:
                c.close()
    finally:
        for svc in services:
            svc.close()
        stop()
    record = {
        "phase": "requests", "clients": 2, "ops_submitted": submitted,
        "executor": sidecar.executor, "text_len": len(text_sc),
        "wall_s": time.perf_counter() - t0,
        **{f"requests_{k}": v for k, v in meter.since(before).items()},
        "clients_agree": text_a == text_b,
        "sidecar_agrees": text_sc == text_a,
        "host_mode_docs": sidecar.host_mode_docs(),
        "peak_bytes_in_use": peak_bytes(device),
    }
    emit(record)
    check(text_a == text_b, "the two socket clients diverged")
    check(text_sc == text_a, "sidecar text differs from the clients'")
    return record


def pump(svc, container, timeout: float = 30.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        with svc.lock:
            if container.runtime.pending.count == 0:
                return
        time.sleep(0.02)
    raise SmokeFailure("ops never acked")


def wait_converged(svc_a, sa, svc_b, sb, timeout: float = 30.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        with svc_a.lock:
            ta = sa.get_text()
        with svc_b.lock:
            tb = sb.get_text()
        if ta == tb:
            return
        time.sleep(0.02)
    raise SmokeFailure("socket clients never converged")


def phase_mesh(msz: dict, seed: int, meter: CompileMeter,
               devices: list) -> dict:
    """The two pools that exist only across chips: documents that
    outgrow the slab are admitted to a docs-sharded ``MeshShardedPool``
    and to a seq-sharded ``SeqShardedPool`` (collective prefix sums)
    over four devices; one hot pooled document forces a migration.
    Every document is compared with the same ops applied on device 0."""
    import random

    from fluidframework_tpu.drivers import LocalDocumentServiceFactory
    from fluidframework_tpu.loader import Container
    from fluidframework_tpu.parallel import (
        MeshShardedPool,
        make_mesh,
        make_seq_mesh,
    )
    from fluidframework_tpu.service import LocalServer
    from fluidframework_tpu.service.tpu_sidecar import (
        SeqShardedPool,
        TpuMergeSidecar,
    )

    check(len(devices) >= 4, f"--chips 4 needs 4 devices, "
                             f"found {len(devices)}")
    quad = devices[:4]
    cap = msz["capacity"]
    mesh_sc = TpuMergeSidecar(max_docs=msz["docs"], capacity=cap,
                              max_capacity=cap, seq_mesh=make_mesh(quad))
    seq_sc = TpuMergeSidecar(max_docs=msz["docs"], capacity=cap,
                             max_capacity=cap,
                             seq_mesh=make_seq_mesh(quad))
    # the comparison: no pool, the ladder grows on device 0 instead
    ref_sc = TpuMergeSidecar(max_docs=msz["docs"], capacity=cap,
                             max_capacity=cap * 4)
    check(isinstance(mesh_sc._pool, MeshShardedPool), "no mesh pool")
    check(isinstance(seq_sc._pool, SeqShardedPool), "no seq pool")
    sidecars = {"mesh": mesh_sc, "seq": seq_sc, "device0": ref_sc}

    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    rng = random.Random(seed)
    docs, strings, containers = [], {}, {}
    for d in range(msz["docs"]):
        doc = f"mdoc-{d}"
        for sc in sidecars.values():
            sc.subscribe(server, doc, "d", "s")
        c = Container.load(factory.create_document_service(doc),
                           client_id=f"{doc}-w")
        strings[doc] = c.runtime.create_datastore("d").create_channel(
            "sharedstring", "s")
        containers[doc] = c
        docs.append(doc)

    def edit(doc: str, n: int) -> None:
        s = strings[doc]
        for _ in range(n):
            length = s.get_length()
            if length > 8 and rng.random() < 0.1:
                lo = rng.randrange(length - 3)
                s.remove_text(lo, lo + 3)
            else:
                s.insert_text(rng.randint(0, length), "ab")
        containers[doc].flush()

    def apply_all() -> None:
        for sc in sidecars.values():
            sc.apply()
            sc.sync()

    before = meter.snapshot()
    t0 = time.perf_counter()
    long_docs = docs[:msz["long_docs"]]
    # grow in slices so the pool admits documents over several
    # settles, with ordinary traffic on the rest interleaved
    slices = 10
    for i in range(slices):
        for doc in docs:
            n = msz["long_edits"] if doc in long_docs else msz["edits"]
            edit(doc, max(1, n // slices))
        apply_all()
    admitted = {k: sc.pooled_docs() for k, sc in sidecars.items()}
    # hot spot: a pooled document that shares its shard takes nearly
    # all the traffic, so the mesh pool moves one of the two away
    pool = mesh_sc._pool
    shared = [m for m in pool.shard_members if len(m) >= 2]
    check(bool(shared), f"no shard holds two pooled documents: "
                        f"{pool.shard_members}")
    hot = mesh_sc._slot_doc[shared[0][0]]
    for _ in range(msz["hot_rounds"]):
        edit(hot, msz["hot_edits"])
        for doc in long_docs:
            if doc != hot:
                edit(doc, 1)
        apply_all()
    wall_s = time.perf_counter() - t0

    mismatches = {k: 0 for k in sidecars}
    for doc in docs:
        want = strings[doc].get_text()
        ref_sig = ref_sc.signature(doc, "d", "s")
        for name, sc in sidecars.items():
            if sc.text(doc, "d", "s") != want or \
                    sc.signature(doc, "d", "s") != ref_sig:
                mismatches[name] += 1
    spans, slot_shards = {}, {}
    for name in ("mesh", "seq"):
        table = sidecars[name]._pool._table
        spans[name] = sorted({len(leaf.sharding.device_set)
                              for leaf in table})
        slot_shards[name] = list(
            table.length.sharding.shard_shape(table.length.shape))
    ref_devices = {d for leaf in ref_sc._table for d in
                   leaf.sharding.device_set}
    record = {
        "phase": "mesh4", "devices": len(quad), "docs": msz["docs"],
        "long_docs": msz["long_docs"], "capacity": cap,
        "pool_capacity": {"mesh": mesh_sc._pool.capacity,
                          "seq": seq_sc._pool.capacity},
        "pooled_docs": admitted,
        "pool_admits": {k: sc.pool_admit_count
                        for k, sc in sidecars.items()},
        "migrations": mesh_sc._pool.migration_count,
        "pool_dispatches": {"mesh": mesh_sc._pool.dispatch_count,
                            "seq": seq_sc._pool.dispatch_count},
        "device0_regrows": ref_sc.grow_count,
        "host_mode_docs": {k: sc.host_mode_docs()
                           for k, sc in sidecars.items()},
        "mismatches_vs_device0_and_oracle": mismatches,
        "pool_table_device_counts": spans,
        "pool_length_shard_shape": slot_shards,
        "device0_table_devices": len(ref_devices),
        "executor": ref_sc.executor,
        "wall_s": wall_s,
        **{f"mesh4_{k}": v for k, v in meter.since(before).items()},
        "peak_bytes_in_use": [peak_bytes(d) for d in quad],
    }
    emit(record)
    check(not any(mismatches.values()),
          f"text/signature mismatches: {mismatches}")
    for name in ("mesh", "seq"):
        check(admitted[name] == msz["long_docs"],
              f"{name} pool admitted {admitted[name]} of "
              f"{msz['long_docs']} long documents")
        check(spans[name] == [4],
              f"{name} pool table spans {spans[name]} devices, not 4")
    check(mesh_sc._pool.migration_count >= 1, "no hot-doc migration")
    check(ref_devices == {quad[0]}, "reference left device 0")
    check(not any(sc.host_mode_docs() for sc in sidecars.values()),
          "documents left the device path")
    return record


# ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=2026)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tests only: tiny sizes on the CPU backend")
    args = ap.parse_args(argv)

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.cpu_rehearsal:
        if platform != "cpu":
            print("chip_smoke: --cpu-rehearsal must run on the CPU",
                  file=sys.stderr)
            return 2
    elif platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform!r}); nothing "
              "was run", file=sys.stderr)
        return 2

    from fluidframework_tpu.native import (
        load_merge_replay,
        merge_replay_error,
        native_build_status,
    )
    from fluidframework_tpu.utils.compile_cache import enable_compile_cache

    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    cache_entries = (len(os.listdir(cache_dir))
                     if os.path.isdir(cache_dir) else 0)
    if load_merge_replay() is None:
        print(f"chip_smoke: the C++ replayer oracle is missing: "
              f"{merge_replay_error()}", file=sys.stderr)
        return 1
    meter = CompileMeter()
    size = "tiny" if args.cpu_rehearsal else "full"
    emit({"phase": "env", "platform": platform,
          "device_kind": devices[0].device_kind,
          "device_count": len(devices), "jax": jax.__version__,
          "rehearsal": args.cpu_rehearsal, "size": size,
          "seed": args.seed, "native": native_build_status(),
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_at_start": cache_entries})
    try:
        if args.chips == 4:
            phase_mesh(MESH_SIZES[size], args.seed, meter, devices)
        else:
            emit(phase_compile(SIZES[size], meter))
            phase_serving(SIZES[size], args.seed, meter, devices[0])
            phase_requests(args.seed, meter, devices[0])
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit({"phase": "done", "wall_s": time.perf_counter() - t_start,
          **meter.snapshot()})
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
