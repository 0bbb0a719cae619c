"""Round telemetry of the merge sidecar: the host's walker trip count
against the device's, the always-on ``stats`` counters of the sidecar
and the sequencer, the ``sidecar:<stage>:r<n>`` profiler spans behind
the device-trace switch, and the named scopes in the compiled walker.
"""
import builtins
import functools
import glob
import random
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from fluidframework_tpu.models.mergetree.ops import (
    AnnotateOp,
    InsertOp,
    RemoveOp,
)
from fluidframework_tpu.obs import profiler
from fluidframework_tpu.obs.profiler import SELF_TIME_EVERY
from fluidframework_tpu.ops import OpBatch, make_table
from fluidframework_tpu.ops.bucket_ladder import BucketLadder
from fluidframework_tpu.ops.event_graph import (
    EG_K,
    PREFIX_FIELDS,
    _chunk_state,
    _gather_ops,
    _walker_step,
    build_event_graph,
    compiled_window,
    walker_steps,
)
from fluidframework_tpu.ops.host_bridge import pack_rows
from fluidframework_tpu.ops.merge_kernel import compact
from fluidframework_tpu.ops.merge_kernel import (
    compiled_window as scan_window,
)
from fluidframework_tpu.ops.segment_table import KIND_INSERT, KIND_NOOP
from fluidframework_tpu.protocol.messages import (
    ClientDetail,
    DocumentMessage,
    MessageType,
)
from fluidframework_tpu.service.sequencer import DocumentSequencer
from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar


@functools.partial(jax.jit, static_argnums=(2,))
def _counted_walker(st, ops_w, K):
    """``event_graph._walker_loop`` with a trip counter in its carry
    (test-only: the served program carries none)."""
    D = st["length"].shape[0]
    W = ops_w["kind"].shape[1]

    def cond(carry):
        return jnp.any(carry[1] < W)

    def body(carry):
        st_, cursor, n = carry
        span = _gather_ops(ops_w, cursor, K)
        st2, take, over = _walker_step(st_, span, K)
        cursor2 = jnp.where(over, W, cursor + take)
        return st2, jnp.minimum(cursor2, W), n + 1

    _, _, n = lax.while_loop(
        cond, body, (st, jnp.zeros((D,), jnp.int32), jnp.int32(0)))
    return n


class _Writers:
    """Documents with deli sequencers and joined writers, feeding one
    sidecar; ops carry a chosen refSeq (the tip unless stated)."""

    def __init__(self, sc, n_docs: int, n_clients: int, seed: int):
        self.sc = sc
        self.rng = random.Random(seed)
        self.seqs = []
        self.length = [0] * n_docs
        self.csn: dict = {}
        self.ingested = 0
        for d in range(n_docs):
            sc.track(f"doc-{d}", "d", "s")
            seq = DocumentSequencer(f"doc-{d}")
            for c in range(n_clients):
                self.ingest(d, seq.client_join(ClientDetail(f"c{c}")))
            self.seqs.append(seq)

    def ingest(self, d: int, msg) -> None:
        self.sc.ingest(f"doc-{d}", msg)
        self.ingested += 1

    def op(self, d: int, c: int, inner, ref=None) -> int:
        seq = self.seqs[d]
        self.csn[d, c] = self.csn.get((d, c), 0) + 1
        res = seq.ticket(f"c{c}", DocumentMessage(
            client_sequence_number=self.csn[d, c],
            reference_sequence_number=(seq.sequence_number if ref is None
                                       else ref),
            type=MessageType.OPERATION,
            contents={"kind": "op", "address": "d", "channel": "s",
                      "contents": inner}))
        assert res.message is not None
        self.ingest(d, res.message)
        return res.message.sequence_number

    def typed(self, d: int, n: int, clients: int) -> None:
        """n tip-view ops on doc d: inserts, removes, annotates."""
        for _ in range(n):
            c = self.rng.randrange(clients)
            size = self.length[d]
            roll = self.rng.random()
            if size > 4 and roll < 0.25:
                a = self.rng.randrange(size - 1)
                b = self.rng.randint(a + 1, min(size, a + 3))
                self.op(d, c, RemoveOp(pos1=a, pos2=b))
                self.length[d] -= b - a
            elif size > 4 and roll < 0.35:
                a = self.rng.randrange(size - 1)
                self.op(d, c, AnnotateOp(pos1=a, pos2=a + 1,
                                         props={"bold": 1}))
            else:
                text = "xyz"[: self.rng.randint(1, 3)]
                self.op(d, c, InsertOp(pos1=self.rng.randint(0, size),
                                       text=text))
                self.length[d] += len(text)

    def concurrent(self, d: int) -> None:
        """Two writers at the same refSeq: the second is not critical,
        so the window from it on rides the scan suffix."""
        ref = self.seqs[d].sequence_number
        for c in (0, 1):
            self.op(d, c, InsertOp(pos1=0, text="ab"), ref=ref)
            self.length[d] += 2


def _recorded(sc):
    """Wrap the sidecar's dispatch halves: every round's compiled
    program, and the table each program was applied to."""
    programs, applied = [], []
    compile_program = sc._compile_program
    apply_program = sc._apply_program

    def _compile(arrays, base_head=None):
        program = compile_program(arrays, base_head=base_head)
        programs.append(program)
        return program

    def _apply(table, program, dead=None):
        applied.append((table, program))
        return apply_program(table, program, dead)

    sc._compile_program = _compile
    sc._apply_program = _apply
    return programs, applied


def _egwalker(**kw):
    return TpuMergeSidecar(max_docs=8, executor="egwalker",
                           ladder=BucketLadder(window_floor=16,
                                               max_bucket=64), **kw)


def _rounds(w):
    """Seeded rounds: idle rows, a K-cap run, a concurrent suffix, and
    a round with no real op."""
    yield lambda: [w.typed(d, 5, 3) for d in (0, 2, 5)]
    yield lambda: w.typed(1, EG_K + 9, 3)              # past the lane cap
    yield lambda: (w.typed(3, 6, 3), w.concurrent(3), w.typed(3, 4, 3),
                   w.typed(6, 3, 2))
    yield lambda: w.ingest(7, w.seqs[7].client_join(ClientDetail("late")))
    yield lambda: [w.typed(d, w.rng.randint(1, 2 * EG_K), 3)
                   for d in range(8) if w.rng.random() < 0.6]


@pytest.mark.parametrize("seed", [11, 29])
def test_host_walker_steps_equal_the_device_trip_count(seed):
    sc = _egwalker(capacity=256, max_capacity=256)
    w = _Writers(sc, 8, 3, seed)
    programs, applied = _recorded(sc)
    for step in _rounds(w):
        step()
        sc.apply()
    sc.sync()
    assert not sc.overflowed() and sc.grow_count == 0
    assert len(programs) == len(applied) == sc.stats["rounds"] == 5
    suffixes = 0
    for table, program in applied:
        assert program["egwalker"]
        suffixes += program["suffix"] is not None
        prefix = program["prefix"]
        if prefix is None:
            assert program["walker_steps"] == 0
            continue
        ops_w = {f: jnp.asarray(a) for f, a in zip(PREFIX_FIELDS, prefix)}
        trips = int(_counted_walker(_chunk_state(table), ops_w, EG_K))
        assert program["walker_steps"] == trips
    assert suffixes >= 1                    # the concurrent round split
    assert sc.stats["walker_steps"] == sum(
        p["walker_steps"] for p in programs)


def test_walker_steps_on_a_hand_built_program():
    K = 4
    cs = np.zeros((3, 12), np.int32)
    cs[0, ::K] = 1                  # an idle row: a start every K lanes
    cs[1, [0, 2, 3, 9]] = 1         # spans of 2, 1, 6 and 3 lanes
    # row 2: no start marked at all: spans run to the window's end
    assert walker_steps(cs, K) == max(3, 1 + 1 + 2 + 1, 3)
    assert walker_steps(np.zeros((0, 8), np.int32), K) == 0
    assert walker_steps(np.zeros((2, 0), np.int32), K) == 0


def test_sidecar_stats_advance_through_every_stage():
    sc = _egwalker(capacity=32, max_capacity=256, compact_every=2)
    w = _Writers(sc, 8, 3, 5)
    programs, applied = _recorded(sc)
    assert sc.stats["ingest_msgs"] == w.ingested == 24
    assert sc.stats["ingest_s"] > 0.0
    dup = w.seqs[0].client_join(ClientDetail("c9"))
    w.ingest(0, dup)
    w.ingest(0, dup)                        # dropped, still counted
    assert sc.stats["ingest_msgs"] == w.ingested == 26
    real, active = 0, 0
    steps = list(_rounds(w)) + [lambda: w.typed(4, 60, 3)]  # a regrow
    for i, step in enumerate(steps):
        step()
        active += sum(any(op["kind"] != KIND_NOOP for op in q)
                      for q in sc._queued)
        real += sc.apply()
        if i == 0:
            assert sc.stats["compact_s"] == 0.0
    settle0 = sc.stats["settle_s"]
    sc.sync()
    st = sc.stats
    assert sc.grow_count >= 1 and not sc.overflowed()
    assert st["settle_s"] > settle0
    assert st["rounds"] == len(programs) == 6
    assert len(applied) > st["rounds"]      # the regrow re-applied
    assert st["compact_s"] > 0.0
    assert 0.0 < st["graph_s"] <= st["pack_s"]
    assert st["enqueue_s"] > 0.0
    assert st["real_ops"] == real and st["active_docs"] == active
    walker = sum(p["walker_steps"] for p in programs)
    scan = sum(p["suffix"].shape[2] for p in programs
               if p["suffix"] is not None)
    assert st["walker_steps"] == walker > 0
    assert st["scan_steps"] == scan > 0
    assert st["lane_slots"] == 8 * (walker * EG_K + scan)
    recorded = [f["walker_steps"] for _i, _t, kind, f in sc.flight.events()
                if kind == "dispatch"]
    assert recorded == [p["walker_steps"] for p in programs]


def test_launch_counters_count_the_programs_a_round_enqueues():
    """One walker program per round with a critical prefix, one scan
    program per concurrent suffix, one more walker program per regrow
    re-apply, which sends the round's own packed prefix again."""
    sc = _egwalker(capacity=32, max_capacity=256)
    w = _Writers(sc, 8, 3, 13)
    programs, applied = _recorded(sc)
    steps = list(_rounds(w)) + [lambda: w.typed(4, 60, 3)]  # a regrow
    per_round = []
    for step in steps:
        step()
        before = dict(sc.stats)
        sc.apply()
        sc.sync()
        per_round.append(tuple(sc.stats[k] - before[k] for k in (
            "walker_launches", "suffix_launches")))
    assert sc.grow_count >= 1
    walker = [int(p["prefix"] is not None) for p in programs]
    suffix = [int(p["suffix"] is not None) for p in programs]
    regrow = [sum(1 for _t, p in applied if p is program) - 1
              for program in programs]
    assert per_round == [(wl * (1 + g), sf * (1 + g))
                         for wl, sf, g in zip(walker, suffix, regrow)]
    assert sum(suffix) >= 1 and sum(walker) >= 4 and sum(regrow) >= 1
    # each re-apply sent the very buffer its round packed
    for program, g in zip(programs, regrow):
        sent = [p["prefix"] for _t, p in applied if p is program]
        assert all(x is program["prefix"] for x in sent)
        assert len(sent) == 1 + g


def _compiled_names(fn) -> list:
    """The jit programs XLA compiles while ``fn`` runs: the
    backend-compile event, as the benchmark's CompileMeter counts it."""
    names = []

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    return names


def _fresh_program(sc, concurrent: bool) -> dict:
    """A round's program for ``sc``: a few critical inserts, and with
    ``concurrent`` two writers at one refSeq (a suffix)."""
    rows = {0: [], 1: []}
    for k in range(3):
        rows[0].append(dict(kind=KIND_INSERT, pos1=0, pos2=0, seq=k + 1,
                            refseq=k, client=0, op_id=k, length=1,
                            is_marker=0, prop_key=0, prop_val=0,
                            min_seq=0))
    if concurrent:
        for c in (0, 1):
            rows[1].append(dict(rows[0][0], seq=c + 1, refseq=0,
                                client=c))
    arrays = pack_rows(sc.max_docs, rows, bucket_floor=16)
    program = sc._compile_program(arrays)
    assert (program["suffix"] is not None) == concurrent
    return program


@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("form", ["plain", "pingpong"])
@pytest.mark.parametrize("concurrent", [False, True])
def test_first_apply_at_a_fresh_shape_compiles_only_its_programs(
        monkeypatch, form, concurrent, sharded):
    """No eager array op around the walker: the first ``_apply_program``
    at a table shape nothing else uses compiles the walker program and,
    with a suffix, the suffix's scan (named as the scan route's window,
    so traces and the roofline read it as the scan); no slice, squeeze,
    broadcast or concatenate program. ``pingpong`` takes the chip's
    path (the donated dead table) with the backend reported as a TPU.
    ``sharded``: the plane split by document over 4 devices compiles
    the same programs under the same names, and no resharding one."""
    docs = {("plain", False): 3, ("plain", True): 5,
            ("pingpong", False): 6, ("pingpong", True): 7}[form, concurrent]
    mesh = None
    if sharded:
        from fluidframework_tpu.parallel.mesh import make_mesh

        docs *= 4
        mesh = make_mesh(jax.devices()[:4])
    sc = TpuMergeSidecar(max_docs=docs, capacity=40, executor="egwalker",
                         donate=False, doc_mesh=mesh)
    program = _fresh_program(sc, concurrent)
    table = sc._new_table(40)
    dead = sc._new_table(40) if form == "pingpong" else None
    jax.block_until_ready((table, dead))
    if form == "pingpong":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", "Some donated buffers")
        names = _compiled_names(
            lambda: jax.block_until_ready(
                sc._apply_program(table, program, dead)))
    walker = "jit(run)" if form == "pingpong" else "jit(<lambda>)"
    scan = ["jit(apply_window_impl)"] if concurrent else []
    assert names == [walker] + scan


@pytest.mark.parametrize("form", ["plain", "pingpong"])
def test_prefix_stage_makes_no_implicit_transfer(monkeypatch, form):
    """The walker stage sends its prefix with one explicit copy: under
    ``jax.transfer_guard("disallow")`` it runs, and gives the table the
    unguarded call gives."""
    sc = TpuMergeSidecar(max_docs=4, capacity=32, executor="egwalker",
                         donate=False)
    program = _fresh_program(sc, concurrent=False)
    table = make_table(4, 32)
    want = sc._apply_program(table, program)
    if form == "pingpong":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dead = make_table(4, 32) if form == "pingpong" else None
    with warnings.catch_warnings(), jax.transfer_guard("disallow"):
        warnings.filterwarnings("ignore", "Some donated buffers")
        got = sc._apply_program(table, program, dead)
        jax.block_until_ready(got)
    for f in want._fields:
        assert np.array_equal(np.asarray(getattr(want, f)),
                              np.asarray(getattr(got, f))), f


def test_suffix_stage_makes_no_implicit_transfer():
    """A round with a concurrent suffix sends the packed suffix with one
    explicit copy too: the whole ``_apply_program`` runs under
    ``jax.transfer_guard("disallow")`` and gives the unguarded table."""
    sc = TpuMergeSidecar(max_docs=4, capacity=32, executor="egwalker",
                         donate=False)
    program = _fresh_program(sc, concurrent=True)
    table = make_table(4, 32)
    want = sc._apply_program(table, program)
    with jax.transfer_guard("disallow"):
        got = sc._apply_program(table, program)
        jax.block_until_ready(got)
    for f in want._fields:
        assert np.array_equal(np.asarray(getattr(want, f)),
                              np.asarray(getattr(got, f))), f


def test_suffix_program_is_named_as_the_scan_window():
    """The egwalker suffix's own jit lowers to a module of the same
    name as the scan route's window, so a device trace files the
    suffix's time under the scan."""
    from fluidframework_tpu.ops.event_graph import _jit_suffix
    from fluidframework_tpu.ops.merge_kernel import _apply_window_xla

    table = make_table(2, 16)
    suffix = np.zeros((len(OpBatch._fields), 2, 16), np.int32)
    suffix[OpBatch._fields.index("kind")] = KIND_NOOP

    def module(lowered) -> str:
        return lowered.compiler_ir("hlo").as_hlo_module().name

    scan = module(_apply_window_xla.lower(table, OpBatch(*suffix)))
    assert module(_jit_suffix.lower(table, suffix)) == scan
    assert scan == "jit_apply_window_impl"


def test_scan_route_counts_its_window_as_lanes():
    sc = TpuMergeSidecar(max_docs=4, capacity=64, executor="scan")
    w = _Writers(sc, 4, 2, 3)
    w.typed(0, 5, 2)
    w.typed(2, 20, 2)
    assert sc.apply() == 25
    st = sc.stats
    assert st["walker_steps"] == 0 and st["active_docs"] == 2
    assert st["scan_steps"] == 32           # 20 ops + joins, bucketed
    assert st["lane_slots"] == 4 * 32 and st["real_ops"] == 25


def test_sequencer_stats_count_every_ticket_call():
    seq = DocumentSequencer("doc")
    seq.client_join(ClientDetail("a"))
    assert seq.stats == {"ticket_s": 0.0, "tickets": 0}

    def msg(csn, ref=0):
        return DocumentMessage(client_sequence_number=csn,
                               reference_sequence_number=ref,
                               type=MessageType.OPERATION, contents=None)
    assert seq.ticket("a", msg(1)).ok
    assert not seq.ticket("a", msg(1)).ok          # duplicate: dropped
    assert seq.ticket("b", msg(1)).nack is not None  # not joined
    assert seq.ticket("a", msg(5)).nack is not None  # csn gap
    assert seq.stats == {"ticket_s": 0.0, "tickets": 4}
    # one call in SELF_TIME_EVERY is timed, its seconds scaled up
    last = SELF_TIME_EVERY - 3               # csn of the 16th call
    for csn in range(2, last):
        assert seq.ticket("a", msg(csn)).ok
    assert seq.stats["tickets"] == SELF_TIME_EVERY - 1
    assert seq.stats["ticket_s"] == 0.0
    assert seq.ticket("a", msg(last)).ok
    assert seq.stats["tickets"] == SELF_TIME_EVERY
    assert seq.stats["ticket_s"] > 0.0


def _host_events(logdir) -> list:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    return [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_round_spans_reach_the_profiler_behind_the_switch(
        tmp_path, monkeypatch):
    monkeypatch.delenv(profiler.DEVICE_TRACE_ENV, raising=False)
    sc = _egwalker(capacity=256, max_capacity=256, compact_every=2)
    w = _Writers(sc, 8, 3, 7)
    profiler.set_device_trace(True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        for d in (1, 4):
            w.typed(d, 6, 3)
            sc.apply()
        sc.sync()
        jax.profiler.stop_trace()
    finally:
        profiler.set_device_trace(False)
    names = set(_host_events(tmp_path))
    for stage in ("pack", "graph", "dispatch", "settle"):
        assert {f"sidecar:{stage}:r1", f"sidecar:{stage}:r2"} <= names
    assert "sidecar:compact:r2" in names
    # off again: the next round adds no span and the switch reads off
    assert not profiler.device_trace_enabled()
    w.typed(2, 3, 3)
    sc.apply()
    assert sc._span("pack", 3) is sc._span("settle", 3)


def test_device_trace_off_imports_nothing(monkeypatch):
    monkeypatch.delenv(profiler.DEVICE_TRACE_ENV, raising=False)
    profiler.set_device_trace(False)
    imported = []
    real_import = builtins.__import__

    def spy(name, *args, **kw):
        imported.append(name)
        return real_import(name, *args, **kw)
    builtins.__import__ = spy
    try:
        with profiler.device_trace("sidecar:pack:r1"):
            pass
    finally:
        builtins.__import__ = real_import
    assert imported == []


def test_compiled_programs_carry_the_stage_scopes():
    noop = dict(kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0, client=0,
                op_id=0, length=0, is_marker=0, prop_key=0, prop_val=0,
                min_seq=0)
    arrays = pack_rows(4, {0: [noop]}, bucket_floor=16)
    table = make_table(4, 64)
    program = build_event_graph(arrays, k_max=EG_K)
    fn, args = compiled_window(table, program["prefix"], K=EG_K)
    walker = fn.lower(*args).compile().as_text()
    for scope in ("gather_ops", "walker_take", "walker_view", "walker_rows",
                  "walker_stamps", "walker_overflow", "walker_merge",
                  "walker_update"):
        assert f"/{scope}/" in walker, scope
    batch = OpBatch(**{f: jnp.asarray(arrays[f]) for f in OpBatch._fields})
    scan = scan_window().lower(table, batch).compile().as_text()
    assert "/scan_step/" in scan
    assert "jit(compact)/compact/" in compact.lower(table).compile().as_text()
    # the module names the benchmark selects by stay as they were
    assert walker.startswith("HloModule jit__lambda")
    assert scan.startswith("HloModule jit_apply_window_impl")
