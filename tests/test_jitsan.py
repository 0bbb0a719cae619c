"""jitsan (testing/jitsan.py) — the runtime half of shapecheck — and
THE two differentials that pin the static analyzer to reality:

(a) observed XLA compile counts per jit root must stay <= the
    per-root bounds ``shapecheck.ladder_bounds`` derives from the
    BucketLadder (one extra = an unladdered shape reached a kernel);
(b) ``shapecheck.infer_kernel_output``'s abstract output signatures
    must EQUAL ``jax.eval_shape`` for every real kernel root across
    every ladder rung — an abstract-interpreter gap fails here by
    name, never silently.

Plus the donation read-traps (the runtime form of
``donated-buffer-reuse``) and the prewarm-coverage runtime pin:
after ``prewarm()``, in-ladder serving traffic — including grow
recovery and pool admission — compiles NOTHING new.
"""
import numpy as np
import pytest

import jax

from fluidframework_tpu.analysis.shapecheck import (
    _pow2_span,
    infer_kernel_output,
    ladder_bounds,
)
from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Container
from fluidframework_tpu.obs import metrics as obs_metrics
from fluidframework_tpu.ops import make_table
from fluidframework_tpu.ops.bucket_ladder import BucketLadder
from fluidframework_tpu.ops.segment_table import KIND_NOOP, OpBatch
from fluidframework_tpu.service import LocalServer, TpuMergeSidecar
from fluidframework_tpu.service.tpu_sidecar import _pack_rows
from fluidframework_tpu.testing import jitsan

NOOP = dict(
    kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0, client=0,
    op_id=0, length=0, is_marker=0, prop_key=0, prop_val=0, min_seq=0,
)


@pytest.fixture()
def sanitizer():
    jitsan.install()
    jitsan.reset()
    yield jitsan
    # deliberate trips belong to the test that made them, not to the
    # session-wide conftest guard
    jitsan.reset()
    jitsan.uninstall()


@pytest.fixture()
def cold_mesh_caches(monkeypatch):
    """Fresh jit caches for the mesh-pool roots: any earlier test in
    the process that drove the same (mesh, ladder) shapes leaves the
    module caches warm, and a warm-cache run observes ZERO new
    compiles — which would make the non-vacuity asserts below fail
    (and the bound differential vacuous) depending on suite order."""
    from fluidframework_tpu.ops import shard_moves
    from fluidframework_tpu.parallel import mesh_pool as mp

    # jit caches key on FUNCTION IDENTITY: re-jitting the same impl
    # inherits the warm signatures, so each replacement jit wraps a
    # fresh function object to start genuinely cold
    def _fresh_take(table, idx):
        return shard_moves._take_rows_impl(table, idx)

    def _fresh_migrate(table, idx):
        return shard_moves._take_rows_impl(table, idx)

    monkeypatch.setattr(mp, "_compiled_cache", {})
    monkeypatch.setattr(
        shard_moves, "_take_rows_jit", jax.jit(_fresh_take))
    monkeypatch.setattr(
        shard_moves, "_migrate_rows_donating",
        jax.jit(_fresh_migrate, donate_argnums=(0,)))
    jitsan.reset()  # baseline the fresh (empty) caches


def _batch(docs: int, bucket: int) -> OpBatch:
    return OpBatch(**_pack_rows(docs, {0: [NOOP]}, bucket_floor=bucket))


def _drive(server, sidecar, doc: str, n: int = 24,
           chunk: str = "abcdefgh"):
    """Frequent-flush writer traffic: windows stay under the ladder's
    max_bucket (one flush per apply), segments churn via removes."""
    factory = LocalDocumentServiceFactory(server)
    sidecar.subscribe(server, doc, "d", "s")
    c = Container.load(factory.create_document_service(doc),
                       client_id=f"{doc}-writer")
    s = c.runtime.create_datastore("d").create_channel(
        "sharedstring", "s")
    for i in range(n):
        s.insert_text(0, chunk)
        c.flush()
        if i % 3 == 2 and s.get_length() > 6:
            s.remove_text(2, 5)
            c.flush()
        sidecar.apply()
    sidecar.sync()
    return c, s


# ======================================================================
# differential (a): compile counts <= the static ladder bounds


def test_compile_counts_within_ladder_bounds_scan_route(sanitizer):
    """A prewarmed sidecar driven through real traffic — including an
    overflow regrow up the capacity ladder — compiles at most the
    shapes shapecheck derives from the BucketLadder, per root."""
    ladder = BucketLadder(window_floor=16, max_bucket=32)
    sidecar = TpuMergeSidecar(
        max_docs=2, capacity=16, max_capacity=64, executor="scan",
        donate=False, ladder=ladder,
    )
    sidecar.prewarm()
    server = LocalServer()
    _drive(server, sidecar, "doc")
    assert sidecar.grow_count >= 1, "traffic must exercise a regrow"
    counts = sanitizer.compile_counts()
    bounds = ladder_bounds(16, 32, 16, 64, executor="scan",
                           donate=False)
    for root, bound in bounds.items():
        assert counts[root] <= bound, (
            f"{root}: {counts[root]} compiles > static ladder bound "
            f"{bound} — an unladdered shape reached the kernel"
        )
    assert counts["apply_window"] > 0  # the bound check is not vacuous


def test_compile_counts_within_ladder_bounds_egwalker_route(sanitizer):
    """The TPU's executor route: a prewarmed egwalker sidecar driven
    through real traffic — including an overflow regrow — compiles at
    most the shapes shapecheck derives per root. The walker jits AND
    the route's own scan jit (the concurrent-suffix program) both stay
    within bounds."""
    ladder = BucketLadder(window_floor=16, max_bucket=32)
    sidecar = TpuMergeSidecar(
        max_docs=2, capacity=16, max_capacity=64, executor="egwalker",
        donate=False, ladder=ladder,
    )
    sidecar.prewarm()
    server = LocalServer()
    _drive(server, sidecar, "doc")
    assert sidecar.grow_count >= 1, "traffic must exercise a regrow"
    counts = sanitizer.compile_counts()
    bounds = ladder_bounds(16, 32, 16, 64, executor="egwalker",
                           donate=False)
    for root, bound in bounds.items():
        assert counts[root] <= bound, (
            f"{root}: {counts[root]} compiles > static ladder bound "
            f"{bound} — an unladdered shape reached the kernel"
        )
    assert counts["egwalker"] > 0  # the bound check is not vacuous


def test_prewarm_covers_egwalker_serving_compiles(sanitizer):
    """After prewarm, in-ladder egwalker traffic (incl. grow
    recovery) pays ZERO mid-serve compiles — including the scan
    SUFFIX program a concurrent window would dispatch, which an
    all-noop prewarm window can never reach through the graph (the
    prewarm walk compiles it explicitly)."""
    ladder = BucketLadder(window_floor=16, max_bucket=32)
    sidecar = TpuMergeSidecar(
        max_docs=2, capacity=16, max_capacity=64, executor="egwalker",
        donate=False, ladder=ladder,
    )
    sidecar.prewarm()
    jitsan.reset()
    server = LocalServer()
    _drive(server, sidecar, "doc")
    assert sidecar.grow_count >= 1
    # a genuinely CONCURRENT window (two blind writers) exercises the
    # suffix route too — prewarm must already have compiled it
    from fluidframework_tpu.models.mergetree.ops import InsertOp
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    sidecar.track("conc", "d", "s")
    for seq, refseq, cli in [(1, 0, "a"), (2, 0, "b"), (3, 0, "c")]:
        sidecar.ingest("conc", SequencedMessage(
            client_id=cli, sequence_number=seq,
            minimum_sequence_number=0, client_sequence_number=1,
            reference_sequence_number=refseq,
            type=MessageType.OPERATION,
            contents={"kind": "op", "address": "d", "channel": "s",
                      "contents": InsertOp(pos1=0, text="zz")},
        ))
    sidecar.apply()
    sidecar.sync()
    counts = sanitizer.compile_counts()
    assert all(n == 0 for n in counts.values()), (
        f"mid-serve compiles after prewarm: "
        f"{ {r: n for r, n in counts.items() if n} }"
    )


def test_egwalker_bounds_arithmetic():
    """The egwalker route's bound shape: walker roots get the full
    (window bucket x capacity rung) ladder, the suffix rides the
    route's own plain scan root (never a donating form, never the
    scan route's apply_window) — and the scan route pins the egwalker
    roots to zero."""
    b = ladder_bounds(16, 32, 16, 64, executor="egwalker")
    shapes = b["egwalker"]
    assert shapes > 0
    assert b["egwalker_suffix"] == shapes
    assert b["apply_window"] == b["apply_window_pingpong"] == 0
    assert b["egwalker_pingpong"] == 0  # donate off
    donating = ladder_bounds(16, 32, 16, 64, executor="egwalker",
                             donate=True)
    assert donating["egwalker_pingpong"] == shapes
    assert donating["apply_window_pingpong"] == 0  # suffix stays plain
    cold = ladder_bounds(16, 32, 16, 64, executor="scan")
    assert cold["egwalker"] == cold["egwalker_pingpong"] == 0
    assert cold["egwalker_suffix"] == 0 and cold["apply_window"] == shapes
    # a POOLED sidecar's pool applies the plain scan on a degenerate
    # mesh whatever the route — the bound must grant the pool's scan
    # programs instead of reading a correctly laddered sidecar as a
    # recompile storm; the walker roots get nothing from the pool
    for route in ("scan", "egwalker"):
        alone = ladder_bounds(16, 32, 16, 64, executor=route)
        pooled = ladder_bounds(16, 32, 16, 64, executor=route,
                               pool_capacity=64, pool_rows=1)
        assert pooled["apply_window"] - alone["apply_window"] == \
            pooled["mesh_pool"] > 0
        assert pooled["egwalker"] == alone["egwalker"]


@pytest.fixture
def cold_route_caches(monkeypatch):
    """Fresh egwalker factory caches and a fresh scan jit: the
    factories fill with FRESH lambdas on miss, so an emptied dict
    yields genuinely cold compiles, and the scan jit wraps a fresh
    function object — suite-order warm caches otherwise make
    cache-delta non-vacuity asserts flaky (the cold_mesh_caches
    precedent)."""
    from fluidframework_tpu.ops import event_graph, merge_kernel

    def _fresh_scan(table, batch):
        return merge_kernel.apply_window_impl(table, batch)

    monkeypatch.setattr(
        merge_kernel, "_apply_window_xla", jax.jit(_fresh_scan))
    monkeypatch.setattr(event_graph, "_jit_cache", {})
    monkeypatch.setattr(event_graph, "_jit_pingpong_cache", {})
    jitsan.reset()  # baseline the fresh (empty) caches


def test_pooled_egwalker_compile_counts_within_ladder_bounds(
        sanitizer, cold_route_caches):
    """The runtime half of the pooled-route bound: an egwalker
    sidecar whose documents overflow into a degenerate seq pool
    compiles the pool's plain scan programs (pools run the scan
    whatever the route) and still stays within ladder_bounds per
    root."""
    from fluidframework_tpu.parallel.seq_shard import make_seq_mesh

    mesh = make_seq_mesh(jax.devices()[:1], doc_shards=1)
    sidecar = TpuMergeSidecar(
        max_docs=2, capacity=16, max_capacity=16, executor="egwalker",
        donate=False, seq_mesh=mesh, pool_capacity=64,
        ladder=BucketLadder(16, 16),
    )
    sidecar.prewarm()
    server = LocalServer()
    _, s = _drive(server, sidecar, "doc", n=24)
    assert sidecar.pooled_docs() == 1, "traffic must exercise the pool"
    assert sidecar.text("doc", "d", "s") == s.get_text()
    counts = sanitizer.compile_counts()
    bounds = ladder_bounds(16, 16, 16, 16, executor="egwalker",
                           donate=False, pool_capacity=64,
                           pool_rows=1)
    for root, bound in bounds.items():
        assert counts[root] <= bound, (root, counts[root], bound)
    # non-vacuity (cold caches): the pool's scan programs (more than
    # the primary route alone may compile) AND the primary window's
    # walker programs both actually compiled
    assert counts["apply_window"] > ladder_bounds(
        16, 16, 16, 16, executor="egwalker")["apply_window"]
    assert counts["egwalker"] > 0


def test_ladder_arithmetic_matches_the_real_enumeration():
    """shapecheck keeps the ladder arithmetic import-free
    (_pow2_span); this pins it to the real BucketLadder enumeration
    so the two can never drift."""
    for floor, top in ((16, 16), (16, 64), (16, 128), (8, 64)):
        assert _pow2_span(floor, top) == len(
            BucketLadder(floor, top).window_buckets())
    for base, top in ((16, 16), (16, 512), (32, 64)):
        assert _pow2_span(base, top) == len(
            BucketLadder.capacity_rungs(base, top))
    # a non-positive floor never doubles past the top: raise instead
    # of spinning forever (a misread config used to hang the caller)
    with pytest.raises(ValueError, match="positive floor"):
        _pow2_span(0, 64)
    with pytest.raises(ValueError, match="positive floor"):
        ladder_bounds(16, 64, 0, 64)


def test_mesh_pool_compile_counts_within_ladder_bounds(
        sanitizer, cold_mesh_caches):
    """The mesh-pool route under differential (a): an UN-prewarmed
    2-shard mesh-pool sidecar driven through admission, incremental
    dispatch, and a live migration compiles at most the shapes
    ladder_bounds derives for the mesh_pool/mesh_move roots (per-
    shard row-bucket ladder x window buckets x sharding signatures),
    and the pool's compact signatures stay inside the extended
    compact bound."""
    from fluidframework_tpu.parallel import make_mesh

    ladder = BucketLadder(window_floor=16, max_bucket=32)
    sidecar = TpuMergeSidecar(
        max_docs=6, capacity=16, max_capacity=16, executor="scan",
        donate=False, ladder=ladder,
        seq_mesh=make_mesh(jax.devices()[:2]), pool_capacity=256,
    )
    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    docs = {}
    for i in range(3):
        doc = f"doc-{i}"
        sidecar.subscribe(server, doc, "d", "s")
        c = Container.load(factory.create_document_service(doc),
                           client_id=f"{doc}-w")
        s = c.runtime.create_datastore("d").create_channel(
            "sharedstring", "s")
        for _ in range(20):
            s.insert_text(0, "abcdefgh")
            c.flush()
        docs[doc] = (c, s)
    sidecar.apply()
    sidecar.sync()
    assert sidecar.pooled_docs() == 3
    # hot-spot traffic (windows stay under max_bucket per settle)
    for _ in range(5):
        for doc, (c, s) in docs.items():
            n = 10 if doc == "doc-0" else 1
            for _ in range(n):
                s.insert_text(0, "XY")
            c.flush()
        sidecar.apply()
        sidecar.sync()
    assert sidecar._pool.migration_count > 0, (
        "traffic must exercise a migration")
    counts = sanitizer.compile_counts()
    bounds = ladder_bounds(
        16, 32, 16, 16, executor="scan", donate=False,
        pool_capacity=256, pool_rows=sidecar._pool.rows_per_shard,
    )
    for root in ("mesh_pool", "mesh_move", "mesh_move_pingpong",
                 "compact"):
        assert counts[root] <= bounds[root], (
            f"{root}: {counts[root]} compiles > static ladder bound "
            f"{bounds[root]} — an unladdered shape reached the "
            "mesh-pool route"
        )
    assert counts["mesh_pool"] > 0    # the bound check is not vacuous
    assert counts["mesh_move"] > 0    # the migration gather ran


def test_mesh_pool_bounds_arithmetic():
    """The mesh-pool bound formula pinned: row buckets x window
    buckets (+ the replay chunk rung when outside the ladder span) x
    the two input-sharding signatures."""
    bounds = ladder_bounds(16, 32, 16, 64, executor="scan",
                           donate=False, pool_capacity=256,
                           pool_rows=2)
    rb = _pow2_span(1, 2)             # 2
    n_windows = _pow2_span(16, 32) + 1  # chunk=64 outside [16, 32]
    assert bounds["mesh_pool"] == rb * n_windows * 2
    assert bounds["mesh_move"] == rb * 2
    # the migration handoff donates by BACKEND, not by the sidecar
    # donate flag (shard_moves.migrate_rows), so the donating form's
    # bound holds even with donate=False — on TPU every migration
    # compiles it while CPU CI leaves it cold
    assert bounds["mesh_move_pingpong"] == rb * 2
    assert bounds["compact"] == _pow2_span(16, 64) + rb * 2
    donating = ladder_bounds(16, 32, 16, 64, donate=True,
                             pool_capacity=256, pool_rows=2)
    assert donating["mesh_move_pingpong"] == rb * 2
    # no pool attached -> no mesh roots in the bound map
    assert "mesh_pool" not in ladder_bounds(16, 32, 16, 64)


def test_prewarm_covers_mesh_pool_admission_compiles(
        sanitizer, cold_mesh_caches):
    """With a docs mesh attached, prewarm walks the mesh pool's
    dispatch programs (both window floors x both sharding signatures
    + the migration gather), so the FIRST pool admission and its
    incremental tails compile NOTHING mid-serve."""
    from fluidframework_tpu.parallel import make_mesh

    sidecar = TpuMergeSidecar(
        max_docs=2, capacity=16, max_capacity=16, executor="scan",
        donate=False, seq_mesh=make_mesh(jax.devices()[:2]),
        pool_capacity=64, ladder=BucketLadder(16, 16),
    )
    sidecar.prewarm()
    jitsan.reset()
    server = LocalServer()
    _, s = _drive(server, sidecar, "doc", n=24)
    assert sidecar.pooled_docs() == 1, "traffic must exercise the pool"
    assert sidecar.text("doc", "d", "s") == s.get_text()
    counts = sanitizer.compile_counts()
    assert all(n == 0 for n in counts.values()), (
        f"mid-serve compiles after prewarm: "
        f"{ {r: n for r, n in counts.items() if n} }"
    )


# ======================================================================
# differential (b): abstract output signatures == jax.eval_shape


def _sig_of(tree) -> dict:
    if hasattr(tree, "_fields"):
        items = zip(tree._fields, tree)
    else:
        items = tree.items()
    return {f: (tuple(a.shape), str(a.dtype)) for f, a in items}


RUNGS = (32, 64, 128)
BUCKETS = (16, 32)


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("bucket", BUCKETS)
def test_static_signatures_match_eval_shape_scan(rung, bucket):
    from fluidframework_tpu.ops.merge_kernel import (
        apply_window_impl,
        compact,
    )

    table = make_table(4, rung)
    spec = _sig_of(table)
    batch = _batch(4, bucket)
    out = jax.eval_shape(apply_window_impl, table, batch)
    assert infer_kernel_output("apply_window", spec) == _sig_of(out)
    out = jax.eval_shape(compact, table)
    assert infer_kernel_output("compact", spec) == _sig_of(out)


@pytest.mark.parametrize("rung", RUNGS[:-1])
def test_static_signatures_match_eval_shape_pad_capacity(rung):
    from fluidframework_tpu.ops.merge_kernel import pad_capacity

    table = make_table(4, rung)
    spec = _sig_of(table)
    out = jax.eval_shape(lambda t: pad_capacity(t, rung * 2), table)
    assert infer_kernel_output(
        "pad_capacity", spec, new_capacity=rung * 2) == _sig_of(out)


def _egwalker_signatures(root: str, rung: int, bucket: int) -> None:
    """Differential (b) for a walker root: shapecheck's abstract
    (shape, dtype) signature == jax.eval_shape for the egwalker
    program, table in and table out."""
    from fluidframework_tpu.ops.event_graph import (
        EG_K,
        _get_jit,
        _get_jit_pingpong,
        build_event_graph,
    )

    table = make_table(4, rung)
    spec = _sig_of(table)
    arrays = {f: np.array(getattr(_batch(4, bucket), f), np.int32)
              for f in OpBatch._fields}
    prefix = build_event_graph(arrays)["prefix"]
    if root == "egwalker":
        out = jax.eval_shape(_get_jit(EG_K), table, prefix)
    else:
        out = jax.eval_shape(_get_jit_pingpong(EG_K), table, table,
                             prefix)
    assert infer_kernel_output(root, spec) == _sig_of(out)


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("bucket", BUCKETS)
def test_static_signatures_match_eval_shape_egwalker(rung, bucket):
    """The plain walker program across every rung x bucket."""
    _egwalker_signatures("egwalker", rung, bucket)


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("bucket", BUCKETS)
def test_static_signatures_match_eval_shape_egwalker_pingpong(rung,
                                                              bucket):
    """The ping-pong walker program: the donated dead table has the
    output's signature."""
    _egwalker_signatures("egwalker_pingpong", rung, bucket)


@pytest.mark.parametrize("rung", RUNGS)
def test_static_signatures_match_eval_shape_seq_shard(rung):
    from fluidframework_tpu.parallel.seq_shard import (
        apply_window_seq_sharded,
        make_seq_mesh,
    )

    mesh = make_seq_mesh(jax.devices()[:2], doc_shards=1)
    table = make_table(4, rung)
    spec = _sig_of(table)
    out = jax.eval_shape(
        lambda t, b: apply_window_seq_sharded(t, b, mesh),
        table, _batch(4, 16),
    )
    assert infer_kernel_output("seq_shard", spec) == _sig_of(out)


def test_infer_kernel_output_rejects_unknown_root():
    with pytest.raises(ValueError, match="unknown kernel root"):
        infer_kernel_output("warp_drive", {})
    with pytest.raises(ValueError, match="new_capacity"):
        infer_kernel_output("pad_capacity", {})


# ======================================================================
# donation read-traps


def test_donated_table_reads_trap_on_any_backend(sanitizer):
    """apply_window_pingpong consumes its ``dead`` argument; jitsan
    makes a later read raise even on CPU, where XLA would silently
    ignore the donation and the bug would only detonate on-chip."""
    from fluidframework_tpu.ops.merge_kernel import (
        apply_window_pingpong,
    )

    table = make_table(2, 32)
    dead = make_table(2, 32)
    out = apply_window_pingpong(dead, table, _batch(2, 16))
    assert [e.root for e in sanitizer.donation_events()] == [
        "apply_window_pingpong"]
    with pytest.raises(RuntimeError, match="deleted"):
        # the deliberate post-donation read the trap exists to catch
        np.asarray(dead.length)  # fluidlint: disable=donated-buffer-reuse
    # the live input and the output stay readable
    np.asarray(table.length)
    np.asarray(out.length)
    assert sanitizer.trips() == []


def test_donated_egwalker_fodder_reads_trap(sanitizer):
    """The walker route's double-buffer contract: fodder donated to
    apply_window_egwalker_pingpong becomes a read-trap on ANY
    backend (CPU ignores donation; on-chip it is consumed)."""
    import numpy as onp

    from fluidframework_tpu.ops.event_graph import (
        apply_window_egwalker_pingpong,
        build_event_graph,
    )

    arrays = {f: onp.array(getattr(_batch(2, 16), f), onp.int32)
              for f in OpBatch._fields}
    prefix = build_event_graph(arrays)["prefix"]
    table = make_table(2, 32)
    dead = make_table(2, 32)
    out = apply_window_egwalker_pingpong(dead, table, prefix)
    assert [e.root for e in sanitizer.donation_events()] == [
        "egwalker_pingpong"]
    with pytest.raises(RuntimeError, match="deleted"):
        # the deliberate post-donation read the trap exists to catch
        np.asarray(dead.seq)  # fluidlint: disable=donated-buffer-reuse
    np.asarray(out.length)
    # dead=None is the explicit plain-dispatch opt-out: no trap
    jitsan.reset()
    apply_window_egwalker_pingpong(None, table, prefix)
    assert sanitizer.donation_events() == []


def test_donating_the_live_input_records_a_trip(sanitizer):
    """The aliasing form of donated-buffer-reuse: one table passed
    both donated and live. jitsan records a trip (and refuses to
    delete the shared buffers — the live input must stay readable so
    the test can report instead of crash)."""
    from fluidframework_tpu.ops.merge_kernel import (
        apply_window_pingpong,
    )

    table = make_table(2, 32)
    # the deliberate aliasing dispatch the trip exists to catch
    apply_window_pingpong(table, table, _batch(2, 16))  # fluidlint: disable=donated-buffer-reuse
    trips = sanitizer.trips()
    assert trips and all(
        t.root == "apply_window_pingpong" for t in trips)
    assert "aliases a live input" in trips[0].describe()
    np.asarray(table.length)  # not deleted
    jitsan.reset()  # the trip was deliberate; clear it for the guard


def test_keyword_live_args_alias_check_and_survive(sanitizer):
    """Live inputs passed BY KEYWORD are part of the aliasing check:
    donating a table that also rides in as ``table=`` records a trip
    and the shared buffers are NOT deleted (deleting them would
    corrupt the live input the kernel still reads)."""
    from fluidframework_tpu.ops.merge_kernel import (
        apply_window_pingpong,
    )

    table = make_table(2, 32)
    # the deliberate keyword-aliasing dispatch the trip exists to catch
    apply_window_pingpong(table, table=table, batch=_batch(2, 16))  # fluidlint: disable=donated-buffer-reuse
    trips = sanitizer.trips()
    assert trips and trips[0].root == "apply_window_pingpong"
    np.asarray(table.length)  # still readable: not deleted
    jitsan.reset()  # the trip was deliberate; clear it for the guard


def test_migration_handoff_source_reads_trap(sanitizer):
    """The migration handoff (ops/shard_moves.migrate_rows) consumes
    its SOURCE table; jitsan makes a later read raise on any backend
    — a migration that kept reading the pre-move table would pass on
    CPU (donation ignored) and detonate on-chip."""
    from fluidframework_tpu.ops.shard_moves import (
        migrate_rows,
        take_rows,
    )

    table = make_table(4, 32)
    perm = np.arange(4, dtype=np.int32)[::-1].copy()
    out = migrate_rows(table, perm)
    assert [e.root for e in sanitizer.donation_events()] == [
        "mesh_move_pingpong"]
    with pytest.raises(RuntimeError, match="deleted"):
        # the deliberate post-handoff read the trap exists to catch
        np.asarray(table.length)  # fluidlint: disable=donated-buffer-reuse
    np.asarray(out.length)  # the permuted output stays readable
    assert sanitizer.trips() == []
    # the PLAIN gather is the non-consuming form: source stays live
    jitsan.reset()
    kept = take_rows(out, np.arange(4, dtype=np.int32))
    np.asarray(out.length)
    np.asarray(kept.length)
    assert sanitizer.donation_events() == []


def test_mesh_pool_migration_under_sanitizer_never_rereads(sanitizer):
    """The pool's own migration discipline end to end under the
    sanitizer: a driven hot-spot migration consumes the pre-move
    table (a mesh_move donation event fires), no aliasing trip
    fires, and every member's text stays bit-correct afterwards —
    the runtime half of the 'migration handoff buffers must not
    read-after-donate' contract."""
    from fluidframework_tpu.parallel import make_mesh

    sidecar = TpuMergeSidecar(
        max_docs=4, capacity=16, max_capacity=16, executor="scan",
        donate=False, seq_mesh=make_mesh(jax.devices()[:2]),
        pool_capacity=256, ladder=BucketLadder(16, 32),
    )
    server = LocalServer()
    factory = LocalDocumentServiceFactory(server)
    docs = {}
    for i in range(3):
        doc = f"doc-{i}"
        sidecar.subscribe(server, doc, "d", "s")
        c = Container.load(factory.create_document_service(doc),
                           client_id=f"{doc}-w")
        s = c.runtime.create_datastore("d").create_channel(
            "sharedstring", "s")
        for _ in range(20):
            s.insert_text(0, "abcdefgh")
            c.flush()
        docs[doc] = (c, s)
    sidecar.apply()
    sidecar.sync()
    for _ in range(5):
        for doc, (c, s) in docs.items():
            n = 10 if doc == "doc-0" else 1
            for _ in range(n):
                s.insert_text(0, "XY")
            c.flush()
        sidecar.apply()
        sidecar.sync()
    assert sidecar._pool.migration_count > 0
    assert sanitizer.trips() == []
    assert any(
        e.root == "mesh_move_pingpong"
        for e in sanitizer.donation_events()
    ), "the migration handoff must consume the pre-move table"
    for doc, (c, s) in docs.items():
        assert sidecar.text(doc, "d", "s") == s.get_text(), doc


def test_sidecar_donate_path_retires_fodder_loudly(sanitizer):
    """The sidecar's double-buffer discipline under the sanitizer:
    with donation forced on (CPU falls back to the plain dispatch but
    the CONTRACT is identical), every retired fodder table is
    consumed, no trip fires, and serving stays correct — the
    ping-pong invariant from PR2, machine-checked end to end."""
    sidecar = TpuMergeSidecar(
        max_docs=2, capacity=64, max_capacity=64, donate=True,
        ladder=BucketLadder(16, 16),
    )
    server = LocalServer()
    _, s = _drive(server, sidecar, "doc", n=8)
    assert sidecar.text("doc", "d", "s") == s.get_text()
    assert sanitizer.trips() == []
    assert any(
        e.root == "apply_window_pingpong"
        for e in sanitizer.donation_events()
    )


# ======================================================================
# prewarm coverage, runtime pin + the compile metric


def test_prewarm_covers_all_serving_compiles(sanitizer):
    """After prewarm, in-ladder traffic (incl. grow recovery) pays
    ZERO mid-serve compiles — the runtime form of shapecheck's
    prewarm-coverage rule."""
    ladder = BucketLadder(window_floor=16, max_bucket=32)
    sidecar = TpuMergeSidecar(
        max_docs=2, capacity=16, max_capacity=64, executor="scan",
        donate=False, ladder=ladder,
    )
    sidecar.prewarm()
    jitsan.reset()
    server = LocalServer()
    _drive(server, sidecar, "doc")
    assert sidecar.grow_count >= 1
    counts = sanitizer.compile_counts()
    assert all(n == 0 for n in counts.values()), (
        f"mid-serve compiles after prewarm: "
        f"{ {r: n for r, n in counts.items() if n} }"
    )


def test_prewarm_covers_pool_admission_compiles(sanitizer):
    """The pool tier (the gap the prewarm-coverage rule found live:
    SeqShardedPool dispatched through a program prewarm never
    walked): with a seq mesh attached, prewarm walks the pool's
    dispatch programs too, so the FIRST pool admission mid-serve
    compiles nothing."""
    from fluidframework_tpu.parallel.seq_shard import make_seq_mesh

    mesh = make_seq_mesh(jax.devices()[:1], doc_shards=1)
    sidecar = TpuMergeSidecar(
        max_docs=2, capacity=16, max_capacity=16, executor="scan",
        donate=False, seq_mesh=mesh, pool_capacity=64,
        ladder=BucketLadder(16, 16),
    )
    sidecar.prewarm()
    jitsan.reset()
    server = LocalServer()
    _, s = _drive(server, sidecar, "doc", n=24)
    assert sidecar.pooled_docs() == 1, "traffic must exercise the pool"
    assert sidecar.text("doc", "d", "s") == s.get_text()
    counts = sanitizer.compile_counts()
    assert all(n == 0 for n in counts.values()), (
        f"mid-serve compiles after prewarm: "
        f"{ {r: n for r, n in counts.items() if n} }"
    )


def _drive_tree(server, sidecar, doc: str, n: int = 20):
    """Frequent-flush tree writer traffic: small per-apply windows,
    node count climbing past the first capacity rung (a regrow)."""
    from fluidframework_tpu.models.tree import node

    factory = LocalDocumentServiceFactory(server)
    sidecar.subscribe(server, doc, "d", "t")
    c = Container.load(factory.create_document_service(doc),
                       client_id=f"{doc}-writer")
    t = c.runtime.create_datastore("d").create_channel(
        "sharedtree", "t")
    for i in range(n):
        t.insert_nodes(("root",), 0,
                       [node("n", value=i * 4 + j) for j in range(4)])
        c.flush()
        if i % 3 == 2:
            t.move_nodes(("root",), 0, 1, 3)
            c.flush()
        sidecar.apply()
    sidecar.sync()
    return c, t


@pytest.fixture()
def cold_tree_caches(monkeypatch):
    """Fresh jit caches for the tree serving roots (the
    ``cold_mesh_caches`` rule): a warm-cache run observes ZERO new
    compiles at prewarm, failing the non-vacuity asserts below
    depending on suite order. ``tree_sidecar`` binds
    ``pad_tree_capacity`` by value at import, so the fresh pad jit is
    patched in BOTH modules or the sidecar would keep dispatching the
    warm original while jitsan probes the cold replacement."""
    import fluidframework_tpu.ops.tree_apply as tree_apply
    import fluidframework_tpu.service.tree_sidecar as tree_sidecar_mod

    def _fresh_pad(table, new_slots):
        return tree_apply._pad_tree_impl(table, new_slots)

    fresh_pad = jax.jit(_fresh_pad, static_argnums=(1,))
    monkeypatch.setattr(tree_apply, "_jit_cache", {})
    monkeypatch.setattr(tree_apply, "pad_tree_capacity", fresh_pad)
    monkeypatch.setattr(
        tree_sidecar_mod, "pad_tree_capacity", fresh_pad)
    jitsan.reset()  # baseline the fresh (empty) caches


@pytest.mark.parametrize("route", ("atom", "macro"))
def test_tree_prewarm_covers_serving_compiles(
        sanitizer, cold_tree_caches, route):
    """The tree serving plane's prewarm-coverage pin, per route:
    after ``TreeSidecar.prewarm()`` (which walks the full
    (capacity rung x window bucket x BOTH routes) ladder plus the
    pad step), in-ladder tree traffic — including a grow recovery —
    pays ZERO mid-serve compiles on either tree root."""
    from fluidframework_tpu.service import TreeSidecar

    ladder = BucketLadder(window_floor=16, max_bucket=32)
    sidecar = TreeSidecar(max_docs=2, capacity=16, max_capacity=64,
                          executor=route, ladder=ladder)
    sidecar.prewarm()
    counts = sanitizer.compile_counts()
    # non-vacuity + ladder arithmetic: the window root holds at most
    # one signature per (rung, bucket, route, input-commitment) —
    # prewarm walks fresh AND dispatch-output tables — the pad root
    # one per rung transition
    rungs = len(BucketLadder.capacity_rungs(16, 64))
    buckets = len(ladder.window_buckets())
    assert 0 < counts["tree_window"] <= rungs * buckets * 2 * 2
    assert 0 < counts["tree_pad"] <= max(rungs - 1, 1)
    jitsan.reset()
    server = LocalServer()
    _drive_tree(server, sidecar, "doc")
    assert sidecar.grow_count >= 1, "traffic must exercise a regrow"
    counts = sanitizer.compile_counts()
    assert all(n == 0 for n in counts.values()), (
        f"mid-serve tree compiles after prewarm: "
        f"{ {r: n for r, n in counts.items() if n} }"
    )


def test_tree_prewarm_covers_pool_admission_compiles(
        sanitizer, cold_tree_caches):
    """With a pool mesh attached, TreeSidecar.prewarm walks the pool
    tier's first-admission programs too — the first mid-serve pool
    admission and its incremental dispatches compile nothing."""
    from fluidframework_tpu.parallel.seq_shard import make_seq_mesh
    from fluidframework_tpu.service import TreeSidecar

    mesh = make_seq_mesh(jax.devices()[:1], doc_shards=1)
    sidecar = TreeSidecar(max_docs=2, capacity=16, max_capacity=16,
                          executor="atom", pool_mesh=mesh,
                          pool_capacity=64,
                          ladder=BucketLadder(16, 16))
    sidecar.prewarm()
    jitsan.reset()
    server = LocalServer()
    _, t = _drive_tree(server, sidecar, "doc", n=8)
    assert sidecar.pooled_docs() == 1, "traffic must exercise the pool"
    counts = sanitizer.compile_counts()
    assert all(n == 0 for n in counts.values()), (
        f"mid-serve tree compiles after prewarm: "
        f"{ {r: n for r, n in counts.items() if n} }"
    )


def test_publish_compiles_feeds_the_registry_counter(sanitizer):
    from fluidframework_tpu.ops.merge_kernel import compact

    before = obs_metrics.REGISTRY.flat().get(
        'jax_compiles_total{root="compact"}', 0.0)
    compact(make_table(3, 32))
    sizes = jitsan.publish_compiles()
    assert sizes["compact"] >= 1
    after = obs_metrics.REGISTRY.flat()[
        'jax_compiles_total{root="compact"}']
    assert after > before
    # monotone watermark: publishing again without new compiles must
    # not double-count
    jitsan.publish_compiles()
    assert obs_metrics.REGISTRY.flat()[
        'jax_compiles_total{root="compact"}'] == after


def test_uninstall_sweeps_late_imported_wrapper_copies():
    """A module first-imported AFTER install() binds the trap wrapper
    by value (`from ..ops.merge_kernel import apply_window_pingpong`)
    and is not in the install-time patch record — uninstall() must
    sweep it back too, or that module keeps delete()ing donated
    tables with the sanitizer nominally off."""
    import sys
    import types

    from fluidframework_tpu.ops import merge_kernel

    if jitsan.installed():
        # FFTPU_SANITIZE=1 session: the conftest holds an install
        # refcount, so a nested install/uninstall pair never restores
        # anything (by design — the guard stays armed)
        pytest.skip("session-wide jitsan holds the install refcount")

    original = merge_kernel.apply_window_pingpong
    jitsan.install()
    try:
        wrapper = merge_kernel.apply_window_pingpong
        assert wrapper is not original
        late = types.ModuleType("fluidframework_tpu._jitsan_late")
        late.apply_window_pingpong = wrapper  # the by-value import
        sys.modules["fluidframework_tpu._jitsan_late"] = late
    finally:
        jitsan.uninstall()
    try:
        assert merge_kernel.apply_window_pingpong is original
        assert late.apply_window_pingpong is original, (
            "late importer kept the trap wrapper after uninstall()"
        )
    finally:
        sys.modules.pop("fluidframework_tpu._jitsan_late", None)
