"""The fluidlint gate: every pass family over the whole repo, wired
into tier-1 so the analyzer's invariants hold forever after.

Green means: zero non-allowlisted findings AND zero stale allowlist
entries (the ratchet — grandfathered findings may only disappear,
never accumulate; see docs/ANALYSIS.md for the policy).
"""
import json
import subprocess
import sys
import time

from fluidframework_tpu.analysis import core

# the ratchet cap (acceptance: <= 10 grandfathered findings). This
# number may be LOWERED as entries burn down; never raised.
MAX_ALLOWLIST_ENTRIES = 10

# wall-clock budget for ONE combined all-family run (the shared
# per-run callgraph + the memoized _gate() below are what keep this
# honest). The seven-family run measures ~10s on the dev box; the
# budget leaves CI headroom while still tripping on a superlinear
# regression (e.g. a fixpoint that stops converging, or a family
# rebuilding the callgraph per file).
GATE_BUDGET_S = 60.0

_GATE_CACHE = None
_GATE_RUNTIME_S = None


def _gate():
    # one full-tree run per pytest session: several tests read the
    # same result, and the interprocedural families are not free
    global _GATE_CACHE, _GATE_RUNTIME_S
    if _GATE_CACHE is None:
        t0 = time.perf_counter()
        findings = core.run_analysis()
        _GATE_RUNTIME_S = time.perf_counter() - t0
        allowlist = core.load_allowlist()
        kept, stale = core.apply_allowlist(findings, allowlist)
        _GATE_CACHE = (kept, stale, allowlist, findings)
    return _GATE_CACHE[:3]


def test_fluidlint_gate_is_clean():
    kept, stale, _ = _gate()
    problems = [f.format() for f in kept]
    problems += [
        f"stale allowlist entry '{rule} {key}' matches no live "
        "finding — delete it from analysis/allowlist.txt"
        for rule, key in stale
    ]
    assert not problems, (
        "fluidlint gate failed (fix the code, add a justified "
        "'# fluidlint: disable=<rule>' inline, or — for pre-existing "
        "debt only — allowlist it):\n" + "\n".join(problems)
    )


def test_allowlist_ratchet_cap():
    allowlist = core.load_allowlist()
    assert len(allowlist) <= MAX_ALLOWLIST_ENTRIES, (
        f"allowlist has {len(allowlist)} entries, cap is "
        f"{MAX_ALLOWLIST_ENTRIES}: the list only ratchets DOWN — fix "
        "findings instead of grandfathering new ones"
    )


def test_obs_untimed_hop_rule_fires_on_unregistered_hops(tmp_path):
    """The obs-untimed-hop rule (obscheck family): a module stamping
    a hop name outside the canonical table in obs/trace.py fails; a
    canonical stamp passes. Covers both the stamp() call form and a
    direct Trace(...) construction."""
    fixture = tmp_path / "bad_hops.py"
    fixture.write_text(
        "from fluidframework_tpu.obs.trace import stamp\n"
        "from fluidframework_tpu.protocol.messages import Trace\n"
        "def f(traces):\n"
        "    stamp(traces, 'client', 'submit')\n"       # canonical
        "    stamp(traces, 'warpdrive', 'engage')\n"    # not
        "    traces.append(Trace('sequencer', 'ticket'))\n"  # canonical
        "    traces.append(Trace('gremlin', 'nibble'))\n"    # not
        "    name = 'dyn'\n"
        "    stamp(traces, name, name)\n"  # dynamic: runtime's job
    )
    findings = core.run_analysis(
        roots=[str(fixture)], families=["obscheck"],
    )
    keys = sorted(f.key for f in findings)
    assert keys == [
        "bad_hops.py:gremlin:nibble",
        "bad_hops.py:warpdrive:engage",
    ]
    assert all(f.rule == "obs-untimed-hop" for f in findings)

    # a module's own unrelated stamp()/Trace() — no obs/protocol
    # import — must NOT false-positive the gate
    unrelated = tmp_path / "unrelated.py"
    unrelated.write_text(
        "def stamp(canvas, layer, mode):\n"
        "    return (canvas, layer, mode)\n"
        "class Trace:\n"
        "    def __init__(self, a, b):\n"
        "        pass\n"
        "def g(c):\n"
        "    stamp(c, 'fill', 'round')\n"
        "    Trace('not', 'a-hop')\n"
    )
    assert core.run_analysis(
        roots=[str(unrelated)], families=["obscheck"],
    ) == []


def test_canonical_hops_resolve_to_live_stamp_sites():
    """Registry non-vacuity (the WALL_CLOCK_SINKS / FANOUT_GATES
    contract, applied to the hop table): every CANONICAL_HOPS entry —
    including the PR13 replication/partition/pool hops — must be
    reachable from a real literal ``stamp()``/``Trace()`` call site
    in the package tree. A ghost hop entry fails HERE, so the table
    can only describe hops something actually emits."""
    from fluidframework_tpu.analysis.obscheck import (
        collect_stamped_hops,
        load_canonical_hops,
        stale_canonical_hops,
    )

    files = core.walk_python_files(["fluidframework_tpu"])
    stale = stale_canonical_hops(files)
    assert stale == [], (
        "CANONICAL_HOPS entries with no live stamp()/Trace() call "
        f"site (ghost vocabulary — delete or stamp them): {stale}"
    )
    # the new fleet hops specifically come from the surfaces the
    # tentpole instrumented: the replicated sequencer, the
    # partitioned transport, and the mesh pool's settle boundary
    by_file = {}
    for relpath in ("service/replication.py",
                    "service/partitioning.py",
                    "parallel/mesh_pool.py"):
        (src,) = [f for f in files if f.relpath.endswith(relpath)]
        by_file[relpath] = collect_stamped_hops([src])
    assert {("repl", "fence_check"), ("repl", "forward"),
            ("repl", "follower_append"), ("repl", "quorum_ack")} <= \
        by_file["service/replication.py"]
    assert ("partition", "route") in \
        by_file["service/partitioning.py"]
    assert ("pool", "migrate") in by_file["parallel/mesh_pool.py"]

    # the staleness detector itself is not vacuous: an injected
    # ghost entry is caught
    ghost = load_canonical_hops() | {("ghost", "hop")}
    assert stale_canonical_hops(files, hops=ghost) == \
        [("ghost", "hop")]


def test_obs_canonical_table_stays_statically_readable():
    """obscheck must keep extracting the hop table without importing
    the obs package (the linter depends on nothing it lints); this
    breaks loudly if CANONICAL_HOPS stops being a pure literal."""
    from fluidframework_tpu.analysis.obscheck import load_canonical_hops

    hops = load_canonical_hops()
    assert ("sequencer", "ticket") in hops
    assert ("client", "submit") in hops
    assert ("sidecar", "settle") in hops


def test_obscheck_family_is_in_the_gate():
    assert "obscheck" in core.FAMILIES


def test_slo_unbound_objective_rule_fires_on_unregistered_metric(
        tmp_path):
    """The slo-unbound-objective rule (obscheck family): a declared
    Objective whose metric literal names no registered family — or a
    family of the wrong kind — fails; objectives bound to families
    registered anywhere in the scanned tree pass; dynamic names are
    the runtime ValueError's job."""
    fixture = tmp_path / "objectives.py"
    fixture.write_text(
        "from fluidframework_tpu.obs.slo import Objective\n"
        "from fluidframework_tpu.obs import metrics as obs_metrics\n"
        "H = obs_metrics.REGISTRY.histogram('fix_lat_ms', 'h')\n"
        "C = obs_metrics.REGISTRY.counter('fix_good_total', 'c')\n"
        "T = obs_metrics.REGISTRY.counter('fix_total_total', 'c')\n"
        "G = obs_metrics.REGISTRY.gauge('fix_depth', 'g')\n"
        "OK1 = Objective('lat', metric='fix_lat_ms',\n"
        "                threshold_ms=5.0)\n"
        "OK2 = Objective('gp', kind='goodput',\n"
        "                good_metric='fix_good_total',\n"
        "                total_metric='fix_total_total')\n"
        "BAD1 = Objective('ghost', metric='fix_nonexistent_ms')\n"
        "BAD2 = Objective('wrongkind', metric='fix_good_total')\n"
        "BAD3 = Objective('gpbad', kind='goodput',\n"
        "                 good_metric='fix_depth',\n"
        "                 total_metric='fix_total_total')\n"
        "name = 'dyn_ms'\n"
        "DYN = Objective('dyn', metric=name)\n"  # runtime's job
    )
    findings = core.run_analysis(
        roots=[str(fixture)], families=["obscheck"],
    )
    assert sorted(f.key for f in findings) == [
        "objectives.py:ghost:fix_nonexistent_ms",
        "objectives.py:gpbad:fix_depth",
        "objectives.py:wrongkind:fix_good_total",
    ]
    assert all(f.rule == "slo-unbound-objective" for f in findings)

    # partial-path scans fall back to the real package's registered
    # families: an objective bound to a family registered OUTSIDE the
    # scanned files (here: the sidecar's settle histogram and the
    # ingress goodput counters) must stay clean
    partial = tmp_path / "partial.py"
    partial.write_text(
        "from fluidframework_tpu.obs.slo import Objective\n"
        "A = Objective('settle', metric='sidecar_settle_ms',\n"
        "              threshold_ms=100.0)\n"
        "B = Objective('gp', kind='goodput',\n"
        "              good_metric='ingress_ops_received_total',\n"
        "              total_metric='ingress_ops_offered_total')\n"
    )
    assert core.run_analysis(
        roots=[str(partial)], families=["obscheck"],
    ) == []

    # a module's own unrelated Objective class (no obs import) is
    # not the rule's business
    own = tmp_path / "own.py"
    own.write_text(
        "class Objective:\n"
        "    def __init__(self, *a, **k):\n"
        "        pass\n"
        "X = Objective('x', metric='definitely_not_registered')\n"
    )
    assert core.run_analysis(
        roots=[str(own)], families=["obscheck"],
    ) == []


def test_undocumented_metric_rule_staleness_both_ways(tmp_path):
    """The undocumented-metric rule (obscheck family): a registered
    family with no row in the fixture tree's docs/OBSERVABILITY.md
    fails; a documented ghost family nothing registers fails too; a
    documented + registered family passes. Scope: the doc is found
    by ascent, and files under tests/ are not the doc's business."""
    root = tmp_path / "repo"
    (root / "docs").mkdir(parents=True)
    (root / "docs" / "OBSERVABILITY.md").write_text(
        "# Observability\n\n"
        "| family | type | meaning |\n"
        "|---|---|---|\n"
        "| `fixture_documented_total` | counter | documented |\n"
        "| `fixture_ghost_family_total` | counter | nothing "
        "registers this |\n"
    )
    mod = root / "plane.py"
    mod.write_text(
        "def wire(reg):\n"
        "    reg.counter('fixture_documented_total', 'ok')\n"
        "    reg.counter('fixture_undocumented_total', 'missing "
        "row')\n"
        "    name = 'dyn_total'\n"
        "    reg.counter(name, 'dynamic: runtime concern')\n"
    )
    findings = core.run_analysis(
        roots=[str(mod)], families=["obscheck"],
    )
    assert sorted(f.key for f in findings) == [
        "fixture_ghost_family_total", "fixture_undocumented_total",
    ]
    assert all(f.rule == "undocumented-metric" for f in findings)
    ghost = next(f for f in findings
                 if f.key == "fixture_ghost_family_total")
    assert ghost.path.endswith("docs/OBSERVABILITY.md")
    missing = next(f for f in findings
                   if f.key == "fixture_undocumented_total")
    assert missing.path.endswith("plane.py")

    # a registry driven from under tests/ is a synthetic test rig,
    # not serving surface: out of the doc's scope
    tdir = root / "tests"
    tdir.mkdir()
    rig = tdir / "test_rig.py"
    rig.write_text(
        "def rig(reg):\n"
        "    reg.counter('rig_only_total', 'synthetic')\n"
    )
    assert core.run_analysis(
        roots=[str(rig)], families=["obscheck"],
    ) == []

    # no docs/OBSERVABILITY.md above the scan roots (plain fixture
    # trees): the rule is silent, not a false-positive storm
    bare = tmp_path / "bare.py"
    bare.write_text(
        "def wire(reg):\n"
        "    reg.counter('undocumented_anywhere_total', 'x')\n"
    )
    assert core.run_analysis(
        roots=[str(bare)], families=["obscheck"],
    ) == []


def test_undocumented_metric_live_tree_is_clean():
    """The acceptance bar for the heat PR's doc satellite: every
    family the real tree registers has a row in docs/
    OBSERVABILITY.md's metric family index, no ghost rows, NOTHING
    allowlisted — the doc can be trusted as the complete operator
    surface."""
    kept, _stale, allowlist = _gate()
    mine = [f for f in kept if f.rule == "undocumented-metric"]
    assert mine == [], "\n".join(f.format() for f in mine)
    assert not [e for e in allowlist
                if e[0] == "undocumented-metric"], (
        "undocumented-metric must not be allowlisted — document the "
        "family instead")


def test_service_unbounded_queue_rule_fires_in_service_paths(
        tmp_path):
    """The service-unbounded-queue rule (qoscheck family): an
    unbounded asyncio.Queue()/deque() in a service/qos path fails;
    bounded constructions and justified inline disables pass; the
    same code OUTSIDE a service path is not the rule's business."""
    svc_dir = tmp_path / "service"
    svc_dir.mkdir()
    bad = svc_dir / "bad.py"
    bad.write_text(
        "import asyncio\n"
        "from collections import deque\n"
        "class Session:\n"
        "    def __init__(self):\n"
        "        self.outbound = asyncio.Queue()\n"            # BAD
        "        self.infinite = asyncio.Queue(maxsize=0)\n"   # BAD
        "        self.bounded = asyncio.Queue(maxsize=100)\n"  # ok
        "        self.log = deque()\n"                         # BAD
        "        self.ring = deque((), 64)\n"                  # ok
        "        self.ok = deque(maxlen=8)\n"                  # ok
        "        self.justified = deque()  "
        "# fluidlint: disable=service-unbounded-queue -- test\n"
    )
    findings = core.run_analysis(
        roots=[str(bad)], families=["qoscheck"],
    )
    assert sorted(f.key for f in findings) == [
        "bad.py:Session.__init__.infinite",
        "bad.py:Session.__init__.log",
        "bad.py:Session.__init__.outbound",
    ]
    assert all(
        f.rule == "service-unbounded-queue" for f in findings
    )

    # a module's own class named Queue/deque (no import) must not
    # false-positive, and non-service paths are out of scope
    other = tmp_path / "elsewhere.py"
    other.write_text(
        "import asyncio\n"
        "q = asyncio.Queue()\n"
    )
    assert core.run_analysis(
        roots=[str(other)], families=["qoscheck"],
    ) == []
    own = svc_dir / "own.py"
    own.write_text(
        "class deque:\n"
        "    pass\n"
        "d = deque()\n"
    )
    assert core.run_analysis(
        roots=[str(own)], families=["qoscheck"],
    ) == []


def test_retry_without_jitter_rule(tmp_path):
    """qoscheck:retry-without-jitter — a constant time.sleep inside a
    retry/reconnect loop in drivers/service/qos paths flags
    (synchronized reconnect storms after a mass disconnect); delays
    routed through driver_utils.full_jitter_delay pass, as do sleeps
    outside loops, unknown-provenance values, suppressed lines and
    out-of-scope paths."""
    drv = tmp_path / "drivers"
    drv.mkdir()
    bad = drv / "bad.py"
    bad.write_text(
        "import time\n"
        "from .driver_utils import full_jitter_delay\n"
        "class Conn:\n"
        "    def reconnect(self):\n"
        "        attempt = 0\n"
        "        while True:\n"
        "            try:\n"
        "                return self.dial()\n"
        "            except OSError:\n"
        "                attempt += 1\n"
        "                time.sleep(0.5)\n"                     # BAD
        "    def reconnect_scaled(self):\n"
        "        delay = 0.1 * 2\n"
        "        for _ in range(5):\n"
        "            time.sleep(delay)\n"                       # BAD
        "    def reconnect_jittered(self, attempt):\n"
        "        while True:\n"
        "            time.sleep(full_jitter_delay(attempt))\n"  # ok
        "    def reconnect_jittered_var(self, attempt):\n"
        "        while True:\n"
        "            d = full_jitter_delay(attempt)\n"
        "            time.sleep(d)\n"                           # ok
        "    def settle_once(self):\n"
        "        time.sleep(0.5)\n"          # ok: not a retry loop
        "    def injected(self, delay_fn):\n"
        "        while True:\n"
        "            time.sleep(delay_fn())\n"  # ok: unknown prov
        "    def justified(self):\n"
        "        while True:\n"
        "            time.sleep(1.0)  "
        "# fluidlint: disable=retry-without-jitter -- test\n"
    )
    findings = core.run_analysis(
        roots=[str(bad)], families=["qoscheck"],
    )
    assert sorted(f.key for f in findings) == [
        "bad.py:Conn.reconnect.sleep",
        "bad.py:Conn.reconnect_scaled.sleep",
    ]
    assert all(f.rule == "retry-without-jitter" for f in findings)

    # two raw sleeps in ONE scope get distinct stable keys
    two = drv / "two.py"
    two.write_text(
        "import time\n"
        "def pump():\n"
        "    while True:\n"
        "        time.sleep(0.1)\n"
        "        time.sleep(0.2)\n"
    )
    keys = sorted(f.key for f in core.run_analysis(
        roots=[str(two)], families=["qoscheck"]))
    assert keys == ["two.py:pump.sleep", "two.py:pump.sleep2"]

    # the same code OUTSIDE a drivers/service/qos path component is
    # not the rule's business
    other = tmp_path / "elsewhere.py"
    other.write_text(
        "import time\n"
        "def pump():\n"
        "    while True:\n"
        "        time.sleep(0.1)\n"
    )
    assert core.run_analysis(
        roots=[str(other)], families=["qoscheck"],
    ) == []


def test_fence_before_fanout_rule(tmp_path):
    """qoscheck:fence-before-fanout — a call to a replication gate
    (the reviewed FANOUT_GATES registry) in a service path must be
    textually preceded, in the same function, by an epoch fence
    check; both ``<...>.fence.check(...)`` and ``check_epoch(...)``
    spellings count, suppression works, non-service paths are out of
    scope."""
    svc = tmp_path / "service"
    svc.mkdir()
    bad = svc / "bad.py"
    bad.write_text(
        "class Log:\n"
        "    def persist(self, msg):\n"
        "        self.write(msg)\n"
        "        self.group.replicate_before_fanout(msg)\n"   # BAD
        "    def persist_checked(self, msg):\n"
        "        self.group.fence.check(self.epoch)\n"
        "        self.group.replicate_before_fanout(msg)\n"   # ok
        "    def persist_epoch(self, msg):\n"
        "        check_epoch(self.epoch)\n"
        "        self._replicate_before_fanout(msg)\n"        # ok
        "    def persist_late_fence(self, msg):\n"
        "        self._replicate_before_fanout(msg)\n"        # BAD
        "        self.group.fence.check(self.epoch)\n"
        "    def persist_justified(self, msg):\n"
        "        self.group.replicate_before_fanout(msg)  "
        "# fluidlint: disable=fence-before-fanout -- test\n"
        "    def persist_nested_fence(self, msg):\n"
        "        def helper():\n"
        "            self.group.fence.check(self.epoch)\n"
        "        self.group.replicate_before_fanout(msg)\n"  # BAD
        "    def persist_nested_gate(self, msg):\n"
        "        def flush():\n"
        "            self.group.replicate_before_fanout(msg)\n"  # BAD
        "        flush()\n"
    )
    findings = [f for f in core.run_analysis(
        roots=[str(bad)], families=["qoscheck"])
        if f.rule == "fence-before-fanout"]
    assert sorted(f.key for f in findings) == [
        "bad.py:Log.persist.fanout",
        "bad.py:Log.persist_late_fence.fanout",
        # a fence check hidden inside a nested helper does NOT guard
        # the outer gate — the hoist the rule exists to catch
        "bad.py:Log.persist_nested_fence.fanout",
        # a gate inside a nested def is ONE finding against the
        # nested scope, not a duplicate against the method too
        "bad.py:flush.fanout",
    ]

    # the same code outside a service path component is not the
    # rule's business (the replicated sequencer lives in service/)
    other = tmp_path / "elsewhere.py"
    other.write_text(
        "def persist(group, msg):\n"
        "    group.replicate_before_fanout(msg)\n"
    )
    assert [f for f in core.run_analysis(
        roots=[str(other)], families=["qoscheck"])
        if f.rule == "fence-before-fanout"] == []


def test_fence_before_fanout_live_tree_is_clean():
    """The replicated sequencer's real gates (document plane +
    partitioned queue) all check the fence first — and the rule
    actually SEES them (non-vacuity: the gate callees exist in the
    scanned tree)."""
    findings = [
        f for f in core.run_analysis(families=["qoscheck"])
        if f.rule == "fence-before-fanout"
    ]
    assert findings == [], [f.key for f in findings]
    import ast as _ast

    repl = open("fluidframework_tpu/service/replication.py").read()
    gates = [n for n in _ast.walk(_ast.parse(repl))
             if isinstance(n, _ast.Call)
             and getattr(n.func, "attr", None)
             and n.func.attr.lstrip("_") == "replicate_before_fanout"]
    assert gates, "the rule's registry no longer matches the code"


def test_retry_without_jitter_live_tree_is_clean():
    findings = [
        f for f in core.run_analysis(families=["qoscheck"])
        if f.rule == "retry-without-jitter"
    ]
    assert findings == [], [f.key for f in findings]


def test_unbounded_blocking_wait_rule(tmp_path):
    """qoscheck:unbounded-blocking-wait — a while loop in a service
    path that SLEEPS while waiting for external progress must carry a
    deadline (a comparison against a clock reading or a
    deadline/timeout-named bound): the minority-side quorum barrier
    that hung every submitter forever is the bug class. Clean shapes:
    the deadline-bounded barrier, a bounded ``for`` retry, and
    non-service paths."""
    svc = tmp_path / "service"
    svc.mkdir()
    bad = svc / "bad.py"
    bad.write_text(
        "import time\n"
        "class Barrier:\n"
        "    def replicate(self, acked, quorum):\n"
        "        while acked < quorum:\n"                    # BAD
        "            time.sleep(0.05)\n"
        "            acked = self.poll()\n"
        "    def wait_injectable(self, acked, quorum):\n"
        "        while acked < quorum:\n"                    # BAD
        "            self._sleep(0.05)\n"
        "            acked = self.poll()\n"
        "    def bounded(self, acked, quorum, clock):\n"
        "        deadline = clock() + 0.5\n"
        "        while acked < quorum:\n"                    # ok
        "            if clock() >= deadline:\n"
        "                raise RuntimeError('unavailable')\n"
        "            self._sleep(0.05)\n"
        "            acked = self.poll()\n"
        "    def named_timeout(self, acked, quorum):\n"
        "        while acked < quorum and "
        "self.elapsed() < self.timeout_s:\n"                 # ok
        "            self._sleep(0.05)\n"
        "            acked = self.poll()\n"
        "    def no_sleep(self, items):\n"
        "        while items:\n"          # ok: not a wait, no sleep
        "            items.pop()\n"
        "    def justified(self, acked, quorum):\n"
        "        while acked < quorum:  "
        "# fluidlint: disable=unbounded-blocking-wait -- test\n"
        "            time.sleep(0.05)\n"
        "            acked = self.poll()\n"
    )
    findings = [f for f in core.run_analysis(
        roots=[str(bad)], families=["qoscheck"])
        if f.rule == "unbounded-blocking-wait"]
    assert sorted(f.key for f in findings) == [
        "bad.py:Barrier.replicate.blockwait",
        "bad.py:Barrier.wait_injectable.blockwait",
    ]

    # non-service paths are out of scope (drivers poll sockets with
    # their own lifecycle; the rule is about the serving plane)
    other = tmp_path / "elsewhere.py"
    other.write_text(
        "import time\n"
        "def spin(q):\n"
        "    while not q:\n"
        "        time.sleep(0.01)\n"
    )
    assert [f for f in core.run_analysis(
        roots=[str(other)], families=["qoscheck"])
        if f.rule == "unbounded-blocking-wait"] == []


def test_unbounded_blocking_wait_live_tree_is_clean():
    """The quorum barrier's wait is deadline-bounded (the netsplit
    fix) and nothing else in the service plane blocks unboundedly —
    and the rule actually SEES the barrier (non-vacuity: a sleeping
    while loop exists in replication.py)."""
    findings = [
        f for f in core.run_analysis(families=["qoscheck"])
        if f.rule == "unbounded-blocking-wait"
    ]
    assert findings == [], [f.key for f in findings]
    import ast as _ast

    repl = open("fluidframework_tpu/service/replication.py").read()
    loops = [n for n in _ast.walk(_ast.parse(repl))
             if isinstance(n, _ast.While)]
    sleeping = [
        loop for loop in loops
        if any(isinstance(n, _ast.Call)
               and getattr(n.func, "attr", "") == "_sleep"
               for stmt in loop.body for n in _ast.walk(stmt))
    ]
    assert sleeping, (
        "the quorum barrier's deadline wait vanished — the rule has "
        "nothing left to pin")


def test_qoscheck_family_is_in_the_gate():
    assert "qoscheck" in core.FAMILIES


def test_concheck_family_is_in_the_gate():
    assert "concheck" in core.FAMILIES


def test_shapecheck_family_is_in_the_gate():
    assert "shapecheck" in core.FAMILIES


def test_detcheck_family_is_in_the_gate():
    assert "detcheck" in core.FAMILIES


def test_wirecheck_family_is_in_the_gate():
    assert "wirecheck" in core.FAMILIES


def test_failcheck_family_is_in_the_gate():
    assert "failcheck" in core.FAMILIES


def test_wall_clock_unrouted_rule(tmp_path):
    """detcheck:wall-clock-unrouted — a direct time.* read reachable
    from a deterministic-contract root (here: a fixture matching the
    sequencer-root suffix) fails; reads routed through an injected
    ``clock()`` pass; a fixture matching a WALL_CLOCK_SINKS suffix
    (obs/trace.py stamp) is a reviewed sink; code NOT reachable from
    any root is out of the rule's scope."""
    svc = tmp_path / "service"
    svc.mkdir()
    bad = svc / "sequencer.py"
    bad.write_text(
        "import time\n"
        "class DocumentSequencer:\n"
        "    def __init__(self, clock=None):\n"
        "        self._clock = clock or time.time\n"
        "    def ticket(self, op):\n"
        # the first read is NESTED deeper than the second: ordinals
        # must still follow SOURCE order, not ast.walk's BFS order
        "        raw = max(1.0, time.time())\n"            # BAD
        "        raw2 = time.time()\n"                     # BAD
        "        routed = self._clock()\n"                 # ok
        "        return self._stamp(op, raw + raw2 + routed)\n"
        "    def _stamp(self, op, t):\n"
        "        return (op, t, time.monotonic())\n"       # BAD
    )
    # reads in a module no deterministic root reaches are out of the
    # rule's scope (reachability IS the scope)
    (svc / "util.py").write_text(
        "import time\n"
        "def helper_not_reachable():\n"
        "    return time.perf_counter()\n"
    )
    findings = core.run_analysis(
        roots=[str(svc)], families=["detcheck"])
    assert sorted(f.key for f in findings) == [
        "sequencer.py:DocumentSequencer._stamp:time.monotonic",
        "sequencer.py:DocumentSequencer.ticket:time.time",
        "sequencer.py:DocumentSequencer.ticket:time.time2",
    ]
    assert all(f.rule == "wall-clock-unrouted" for f in findings)
    # ordinal suffixes follow source order: the nested read on the
    # EARLIER line owns the unsuffixed key
    by_key = {f.key.rsplit(":", 1)[-1]: f.line for f in findings
              if "ticket" in f.key}
    assert by_key["time.time"] < by_key["time.time2"]

    # a registered sink suffix is the reviewed escape hatch
    obs = tmp_path / "sink" / "obs"
    obs.mkdir(parents=True)
    (tmp_path / "sink" / "service").mkdir()
    (obs / "trace.py").write_text(
        "import time\n"
        "def stamp(traces):\n"
        "    traces.append(time.time())\n"
    )
    (tmp_path / "sink" / "service" / "sequencer.py").write_text(
        "from ..obs.trace import stamp\n"
        "class DocumentSequencer:\n"
        "    def ticket(self, traces):\n"
        "        stamp(traces)\n"
    )
    assert core.run_analysis(
        roots=[str(tmp_path / "sink")], families=["detcheck"],
    ) == []


def test_unseeded_rng_rule(tmp_path):
    """detcheck:unseeded-rng — unseeded random.Random(), the
    process-global random.* stream, and seedless np.random draws fail
    in deterministic-plane components; seeded/injected RNG passes;
    the same code outside the planes is out of scope."""
    drv = tmp_path / "drivers"
    drv.mkdir()
    bad = drv / "bad.py"
    bad.write_text(
        "import random\n"
        "import numpy as np\n"
        "_RNG = random.Random()\n"                         # BAD
        "def jitter():\n"
        "    return random.uniform(0.0, 1.0)\n"            # BAD
        "def noise():\n"
        "    return np.random.rand()\n"                    # BAD
        "def seeded(seed):\n"
        "    rng = random.Random(seed)\n"                  # ok
        "    gen = np.random.default_rng(seed)\n"          # ok
        "    return rng.random() + gen.random()\n"
        "def injected(rng):\n"
        "    return rng.uniform(0.0, 1.0)\n"               # ok
    )
    findings = core.run_analysis(
        roots=[str(bad)], families=["detcheck"])
    assert sorted(f.key for f in findings) == [
        "bad.py:<module>:Random",
        "bad.py:jitter:random.uniform",
        "bad.py:noise:rand",
    ]
    assert all(f.rule == "unseeded-rng" for f in findings)

    other = tmp_path / "elsewhere.py"
    other.write_text(
        "import random\n"
        "x = random.random()\n"
    )
    assert core.run_analysis(
        roots=[str(other)], families=["detcheck"]) == []


def test_iteration_order_leak_rule(tmp_path):
    """detcheck:iteration-order-leak — sets iterated into
    order-sensitive sinks (fan-out/append loops, list()/tuple()
    materialization, join) fail; sorted(...) kills the taint;
    order-insensitive consumption (membership, len, building another
    set) passes."""
    svc = tmp_path / "service"
    svc.mkdir()
    bad = svc / "bad.py"
    bad.write_text(
        "class Fanout:\n"
        "    def __init__(self):\n"
        "        self.writers = set()\n"
        "    def broadcast(self, out, frame):\n"
        "        for w in self.writers:\n"                 # BAD
        "            out.append((w, frame))\n"
        "    def snapshot(self, ids):\n"
        "        pending = set(ids)\n"
        "        return list(pending)\n"                   # BAD
        "    def wire(self, ids):\n"
        "        return ','.join(set(ids))\n"              # BAD
        "    def stable(self, ids):\n"
        "        pending = set(ids)\n"
        "        for w in sorted(pending):\n"              # ok
        "            ids.append(w)\n"
        "        return sorted(self.writers)\n"            # ok
        "    def insensitive(self, ids):\n"
        "        pending = set(ids)\n"
        "        n = len(pending)\n"                       # ok
        "        return {x for x in pending}, n\n"         # ok
        # a defect inside a nested def is ONE finding against the
        # nested scope, not a duplicate against the method too (the
        # fence-before-fanout nested-gate contract)
        "    def wrap(self, out):\n"
        "        def inner(ids):\n"
        "            pend = set(ids)\n"
        "            for w in pend:\n"                     # BAD once
        "                out.append(w)\n"
        "        return inner\n"
    )
    findings = core.run_analysis(
        roots=[str(bad)], families=["detcheck"])
    assert sorted(f.key for f in findings) == [
        "bad.py:Fanout.broadcast:writers",
        "bad.py:Fanout.snapshot:pending",
        "bad.py:Fanout.wire:<set>",
        "bad.py:Fanout.wrap.inner:pend",
    ]
    assert all(
        f.rule == "iteration-order-leak" for f in findings)


def test_hash_order_dependence_rule(tmp_path):
    """detcheck:hash-order-dependence — builtin hash() of str/bytes,
    and hash(x) %% n partition selection, fail in deterministic
    planes; __hash__ methods and integer hashing pass."""
    svc = tmp_path / "service"
    svc.mkdir()
    bad = svc / "bad.py"
    bad.write_text(
        "class Router:\n"
        "    def partition(self, doc_id, n):\n"
        "        return hash(doc_id) % n\n"                # BAD (%)
        "    def key(self, tenant, doc):\n"
        "        return hash(f'{tenant}/{doc}')\n"         # BAD (str)
        "    def __hash__(self):\n"
        "        return hash(('Router', self.key))\n"      # ok
        "    def int_ok(self, seq):\n"
        "        return hash(seq + 1)\n"                   # ok
    )
    findings = core.run_analysis(
        roots=[str(bad)], families=["detcheck"])
    assert sorted(f.key for f in findings) == [
        "bad.py:Router.key:hash",
        "bad.py:Router.partition:hash",
    ]
    assert all(
        f.rule == "hash-order-dependence" for f in findings)


def test_detcheck_live_tree_is_clean_with_empty_allowlist():
    """The acceptance bar (the PR1/PR5/PR7 precedent): zero live
    detcheck findings over the whole repo and NOTHING grandfathered —
    the sites the family found live (driver_utils' module RNG, the
    collab-window/scheduler clocks, the sequencer wire timestamps,
    the broker writer set, the interval pending-delete resubmission)
    were FIXED in the PR that introduced it. WALL_CLOCK_SINKS is the
    reviewed escape hatch, not the allowlist."""
    kept, _stale, allowlist = _gate()
    det_rules = set(core.FAMILY_RULES["detcheck"])
    det_kept = [f for f in kept if f.rule in det_rules]
    assert det_kept == [], \
        "\n".join(f.format() for f in det_kept)
    grandfathered = [e for e in allowlist if e[0] in det_rules]
    assert grandfathered == [], (
        "detcheck findings must be fixed, never grandfathered: "
        f"{grandfathered}"
    )


def test_wirecheck_live_tree_is_clean_with_empty_allowlist():
    """The acceptance bar (the PR1/PR5/PR11 precedent): zero live
    wirecheck findings over the whole repo and NOTHING grandfathered
    — the unguarded optional emits the family found live (the nack
    retry hint in nack_to_json, the throttle error's qos attribution
    in _send_shed) were FIXED in the PR that introduced it. The
    WIRE_SCHEMA registry's '?'/'~' flags are the reviewed escape
    hatch, not the allowlist."""
    kept, _stale, allowlist = _gate()
    wire_rules = set(core.FAMILY_RULES["wirecheck"])
    wire_kept = [f for f in kept if f.rule in wire_rules]
    assert wire_kept == [], \
        "\n".join(f.format() for f in wire_kept)
    grandfathered = [e for e in allowlist if e[0] in wire_rules]
    assert grandfathered == [], (
        "wirecheck findings must be fixed, never grandfathered: "
        f"{grandfathered}"
    )


def test_wire_schema_registry_resolves_to_live_traffic():
    """Registry non-vacuity (the WALL_CLOCK_SINKS contract): every
    non-tolerated WIRE_SCHEMA entry must still name a field some
    in-scope encoder emits or decoder reads — ghost vocabulary fails
    HERE so the registry can only describe the live protocol. (The
    staleness detector's own non-vacuity is pinned by
    test_wirecheck.py's ghost-entry fixture; the registry is a pure
    literal in the scanned tree, so there is nothing to monkeypatch
    live.)"""
    from fluidframework_tpu.analysis import wirecheck

    files = core.walk_python_files(["fluidframework_tpu"])
    stale = wirecheck.stale_schema_entries(files)
    assert stale == [], (
        "stale WIRE_SCHEMA entries (no emit or read resolves to "
        f"them anymore — delete or mark '~'): {stale}"
    )
    registry = wirecheck.load_registry(files)
    assert registry, "WIRE_SCHEMA registry unexpectedly empty"


def test_failcheck_live_tree_is_clean_with_empty_allowlist():
    """The acceptance bar (the PR1/PR5/PR11/PR19 precedent): zero
    live failcheck findings over the whole repo and NOTHING
    grandfathered — every silent handler the family found live was
    either made loud or reviewed into SILENT_HANDLERS in the PR that
    introduced it. The registry is the escape hatch, never the
    allowlist."""
    kept, _stale, allowlist = _gate()
    fail_rules = set(core.FAMILY_RULES["failcheck"])
    fail_kept = [f for f in kept if f.rule in fail_rules]
    assert fail_kept == [], \
        "\n".join(f.format() for f in fail_kept)
    grandfathered = [e for e in allowlist if e[0] in fail_rules]
    assert grandfathered == [], (
        "failcheck findings must be fixed, never grandfathered: "
        f"{grandfathered}"
    )


def test_silent_handlers_registry_resolves_to_live_sites():
    """Registry non-vacuity (the WALL_CLOCK_SINKS contract): every
    SILENT_HANDLERS entry must still match a statically-silent
    handler at its site — an entry whose handler vanished or went
    loud describes nothing and fails HERE so the registry can only
    describe live code. The staleness detector itself is pinned
    non-vacuous with a planted ghost."""
    from fluidframework_tpu.analysis import failcheck

    files = core.walk_python_files(["fluidframework_tpu"])
    stale = failcheck.stale_silent_handlers(files)
    assert stale == [], (
        "stale SILENT_HANDLERS entries (no statically-silent "
        f"handler at the registered site anymore — delete): {stale}"
    )
    assert failcheck.SILENT_HANDLERS, "registry unexpectedly empty"

    # the staleness detector itself is not vacuous
    ghost = ("service/ingress.py",
             "AlfredServer._handle:except-ZeroDivisionError")
    assert ghost not in failcheck.SILENT_HANDLERS
    try:
        failcheck.SILENT_HANDLERS[ghost] = "test-only ghost entry"
        assert ghost in failcheck.stale_silent_handlers(files)
    finally:
        del failcheck.SILENT_HANDLERS[ghost]


def test_wall_clock_sinks_registry_resolves_to_live_sites():
    """Registry non-vacuity (the FANOUT_GATES contract): every
    WALL_CLOCK_SINKS entry must still name a function (or module)
    containing a real wall-clock call — a stale entry fails HERE so
    the registry can only describe live code."""
    from fluidframework_tpu.analysis import determinism

    files = core.walk_python_files(["fluidframework_tpu"])
    stale = determinism.stale_wall_clock_sinks(files)
    assert stale == [], (
        "stale WALL_CLOCK_SINKS entries (no wall-clock call at the "
        f"registered site anymore — delete them): {stale}"
    )
    assert determinism.WALL_CLOCK_SINKS, "registry unexpectedly empty"

    # the staleness detector itself is not vacuous
    ghost = ("service/sequencer.py", "DocumentSequencer.ticket")
    assert ghost not in determinism.WALL_CLOCK_SINKS
    try:
        determinism.WALL_CLOCK_SINKS[ghost] = "test-only ghost entry"
        assert ghost in determinism.stale_wall_clock_sinks(files)
    finally:
        del determinism.WALL_CLOCK_SINKS[ghost]


def test_family_rules_map_stays_complete():
    """RULE_FAMILY is how one combined run's findings group per
    family (bench records); a family missing from the map would
    silently drop its counts."""
    assert set(core.FAMILY_RULES) == set(core.FAMILIES)
    for rule in ("layer-undeclared", "jit-nondeterminism",
                 "lock-unlocked-write", "obs-untimed-hop",
                 "slo-unbound-objective", "undocumented-metric",
                 "service-unbounded-queue", "lock-order-cycle",
                 "async-blocking-call", "await-holding-lock",
                 "dispatch-loop-sync", "donated-buffer-reuse",
                 "unladdered-jit-shape", "kernel-dtype-widen",
                 "shape-mismatch", "prewarm-coverage",
                 "wall-clock-unrouted", "unseeded-rng",
                 "iteration-order-leak", "hash-order-dependence",
                 "encoder-decoder-drift",
                 "optional-field-unconditional-emit",
                 "ungated-wire-read", "unversioned-frame-field",
                 "swallowed-exception",
                 "broad-except-in-dispatch-loop",
                 "exception-context-dropped", "return-in-finally"):
        assert rule in core.RULE_FAMILY, rule


def test_concheck_live_tree_is_clean_within_the_ratchet():
    """The acceptance bar: concheck over the whole repo, at most the
    allowlist cap grandfathered (today: zero — the moira event-loop
    file I/O it found was FIXED, not grandfathered)."""
    kept, _stale, allowlist = _gate()
    concheck_rules = {"lock-order-cycle", "async-blocking-call",
                      "await-holding-lock"}
    concheck_kept = [f for f in kept if f.rule in concheck_rules]
    assert concheck_kept == [], \
        "\n".join(f.format() for f in concheck_kept)
    grandfathered = [e for e in allowlist if e[0] in concheck_rules]
    assert len(grandfathered) <= MAX_ALLOWLIST_ENTRIES


def test_shapecheck_live_tree_is_clean_within_the_ratchet():
    """The acceptance bar for the shapecheck family: zero live
    findings over the real kernel layer with an EMPTY allowlist —
    everything the new family found (the unwarmed pool-tier dispatch
    programs) was FIXED in the PR that introduced it, the PR1/PR5
    precedent. The registries (LADDERED_CALLS, PREWARM_INDIRECT) are
    the reviewed escape hatch, not the allowlist."""
    kept, _stale, allowlist = _gate()
    shape_rules = set(core.FAMILY_RULES["shapecheck"])
    shape_kept = [f for f in kept if f.rule in shape_rules]
    assert shape_kept == [], \
        "\n".join(f.format() for f in shape_kept)
    grandfathered = [e for e in allowlist if e[0] in shape_rules]
    assert grandfathered == [], (
        "shapecheck findings must be fixed, never grandfathered: "
        f"{grandfathered}"
    )


def test_combined_gate_run_stays_under_budget():
    """The CI/tooling satellite: ten families, one shared
    callgraph, one budget. A blowup here means a family stopped
    reusing the per-run graph or a fixpoint regressed superlinear."""
    _gate()  # ensures the timed run happened (memoized per session)
    assert _GATE_RUNTIME_S is not None
    assert _GATE_RUNTIME_S < GATE_BUDGET_S, (
        f"combined {len(core.FAMILIES)}-family run took "
        f"{_GATE_RUNTIME_S:.1f}s, budget is {GATE_BUDGET_S:.0f}s"
    )


def test_cli_sarif_mode_emits_valid_report(tmp_path, monkeypatch):
    """`--sarif` (diff-annotation tooling): findings carry ruleId,
    message, physical location, and the allowlist key as a
    fingerprint; a dirty tree still exits 1."""
    from fluidframework_tpu.analysis import __main__ as cli

    svc = tmp_path / "service"
    svc.mkdir()
    bad = svc / "bad.py"
    bad.write_text(
        "import time\n"
        "async def handle():\n"
        "    time.sleep(1)\n"
    )
    monkeypatch.setattr(cli, "REPO_ROOT", str(tmp_path))
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([str(bad), "--sarif", "--rules", "concheck"])
    assert rc == 1
    report = json.loads(buf.getvalue())
    assert report["version"] == "2.1.0"
    (run,) = report["runs"]
    assert run["tool"]["driver"]["name"] == "fluidlint"
    (result,) = run["results"]
    assert result["ruleId"] == "async-blocking-call"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] == 3
    assert result["partialFingerprints"]["fluidlintKey"] == \
        "bad.py:handle:time.sleep"
    # SARIF semantics: findings do NOT make the run unsuccessful (the
    # tool completed); consumers discard results of "failed" runs
    assert report["runs"][0]["invocations"][0]["executionSuccessful"]

    # clean tree: empty results, executionSuccessful, exit 0
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([str(clean), "--sarif"])
    assert rc == 0
    report = json.loads(buf.getvalue())
    assert report["runs"][0]["results"] == []
    assert report["runs"][0]["invocations"][0]["executionSuccessful"]


def test_cli_json_mode_exits_zero_on_clean_tree():
    """The `--json` surface BENCH/ADVICE tooling consumes: exit 0 and
    a well-formed empty report on a clean tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "fluidframework_tpu.analysis",
         "--json"],
        capture_output=True, text=True, cwd=core.REPO_ROOT,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["findings"] == []
    assert report["stale_allowlist"] == []
    assert sorted(report["families"]) == sorted(core.FAMILIES)
