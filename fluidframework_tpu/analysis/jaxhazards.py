"""jaxhazards — nondeterminism and recompile hazards in jitted code.

A jitted function traces ONCE per input shape: a wall-clock or RNG
call inside it bakes one arbitrary value into the compiled program
(silent nondeterminism between runs that share a compile cache but not
between reruns — the worst kind for a differential-oracle repo), a
Python ``if`` on a tracer raises at best and silently specializes at
worst, an unhashable static arg fails at call time, and a host
callback stalls the device pipeline per step. All four are cheap to
pin down mechanically.

Jit roots are functions decorated ``@jax.jit`` /
``@partial(jax.jit, ...)`` or wrapped via ``jax.jit(fn, ...)`` call
forms. Reachability is two-tier: the module-local walker follows
bare-name calls (nested defs included — a jitted closure's helpers
count), and from every locally-reachable function the shared call
graph (analysis/callgraph.py) follows resolvable CROSS-MODULE edges —
imported helpers, imported-module attributes, imported-class methods —
so a hazard in another module's helper no longer hides behind the
import boundary. ``jax.debug.print`` is NOT flagged: it is the
sanctioned in-jit debug mechanism.
"""
from __future__ import annotations

import ast
from typing import Optional

from .callgraph import build_callgraph
from .core import (
    Finding,
    SourceFile,
    dotted_path as _dotted,
    import_aliases,
)

# dotted-path prefixes whose call inside jit-reachable code is
# nondeterministic at trace time
NONDET_PREFIXES = (
    "time.time",
    "time.perf_counter",
    "time.monotonic",
    "time.process_time",
    "time.time_ns",
    "random.",
    "numpy.random.",
    "os.urandom",
    "uuid.uuid1",   # uuid3/uuid5 are deterministic in their inputs
    "uuid.uuid4",
    "secrets.",
)

# host-callback / side-effect surfaces inside traced code
HOST_CALLBACKS = (
    "print",
    "input",
    "jax.debug.callback",
    "jax.pure_callback",
    "jax.experimental.io_callback",
    "jax.experimental.host_callback.",
)

# host<->device sync points: a call that forces a device->host
# transfer (or blocks on device completion) serializes the dispatch
# pipeline it appears in
SYNC_PREFIXES = (
    "numpy.asarray",
    "jax.device_get",
)
SYNC_METHODS = ("block_until_ready",)

# Dispatch-loop registry for the ``dispatch-loop-sync`` rule: module
# (relpath suffix) -> (loop root functions, designated sync-boundary
# functions). The sidecar's apply loop is a host/device pipeline whose
# ONLY sanctioned sync is ``_settle`` (where the overflow flag is read
# and recovery runs — service/tpu_sidecar.py); any np.asarray /
# device_get / block_until_ready reachable from the loop outside that
# boundary re-serializes packing against device compute and silently
# un-pipelines serving.
DISPATCH_LOOPS = {
    "service/tpu_sidecar.py": (
        ("apply", "_dispatch", "_pack_rows", "_compile_program",
         "_apply_program"),
        ("_settle", "sync"),
    ),
    # The egwalker route's dispatch path (ops/event_graph.py): the
    # host graph/span compiler runs in the pipeline's pack stage and
    # the walker and suffix dispatch wrappers run in its device stage — a
    # device->host read in either re-serializes the pipeline exactly
    # like one in the sidecar module itself (the sidecar's
    # _compile_program/_apply_program call straight into these).
    "ops/event_graph.py": (
        ("build_event_graph", "apply_window_egwalker",
         "apply_window_egwalker_pingpong", "apply_suffix",
         "apply_batch_egwalker"),
        (),
    ),
    # The obs instrumentation the dispatch loop calls into (flight-
    # recorder records, metric bumps, trace stamps) must itself stay
    # sync-free: host timestamps and pre-fetched scalars only. Rooting
    # the rule at these entry points extends dispatch-loop-sync over
    # the new obs call sites — a device read sneaking into record()/
    # inc()/observe()/stamp() would silently re-serialize every
    # instrumented loop in the repo.
    "obs/flight_recorder.py": (
        ("record", "dump", "dump_to", "events"),
        (),
    ),
    "obs/metrics.py": (
        ("inc", "dec", "set", "observe", "labels"),
        (),
    ),
    "obs/trace.py": (
        ("stamp",),
        (),
    ),
    # The heat ledger is charged from the sidecar's settle boundary
    # and ticked from the mesh pool's dispatch path: its mutation and
    # read methods must stay pure host math (SoA numpy over
    # host-resident rows), never a device fetch.
    "obs/heat.py": (
        ("ewma_tick", "charge", "get", "pop", "attribute_round"),
        (),
    ),
}


def _import_aliases(tree: ast.AST) -> dict[str, str]:
    # "skip" relative imports: this pass matches ABSOLUTE stdlib
    # prefixes, and a relative `..random` tail must not collide with
    # the stdlib `random.` registry entry
    return import_aliases(tree, relative="skip")


def _matches(dotted: str, prefixes: tuple[str, ...]) -> bool:
    return any(
        dotted == p or (p.endswith(".") and dotted.startswith(p))
        or (not p.endswith(".") and dotted.startswith(p + "."))
        for p in prefixes
    )


class _JitRoot:
    def __init__(self, fn: ast.FunctionDef,
                 static_argnums: tuple[int, ...],
                 static_argnames: tuple[str, ...],
                 analyze_params: bool = True):
        self.fn = fn
        self.static_argnums = static_argnums
        self.static_argnames = static_argnames
        # False for functions reached through a jitted LAMBDA
        # (jax.jit(lambda st: _loop(st, k))): their params bind
        # closure values that are static at trace time, so the
        # tracer-branch/static-arg rules would misfire — only the
        # reachability rules (nondeterminism, host callbacks) apply
        self.analyze_params = analyze_params


def _literal(node: Optional[ast.AST]):
    if node is None:
        return None
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def _statics_from_call(call: ast.Call) -> tuple[tuple[int, ...],
                                                tuple[str, ...]]:
    nums = _literal(next(
        (k.value for k in call.keywords if k.arg == "static_argnums"),
        None,
    ))
    names = _literal(next(
        (k.value for k in call.keywords if k.arg == "static_argnames"),
        None,
    ))
    if isinstance(nums, int):
        nums = (nums,)
    if isinstance(names, str):
        names = (names,)
    return tuple(nums or ()), tuple(names or ())


def _find_roots(tree: ast.AST, aliases: dict[str, str]
                ) -> list[_JitRoot]:
    by_name: dict[str, list[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)

    roots: list[_JitRoot] = []

    def is_jit(node: ast.AST) -> bool:
        return _dotted(node, aliases) == "jax.jit"

    for fns in by_name.values():
        for fn in fns:
            for dec in fn.decorator_list:
                if is_jit(dec):
                    roots.append(_JitRoot(fn, (), ()))
                elif isinstance(dec, ast.Call):
                    target = _dotted(dec.func, aliases)
                    if target == "jax.jit":
                        roots.append(
                            _JitRoot(fn, *_statics_from_call(dec))
                        )
                    elif target in ("functools.partial", "partial") \
                            and dec.args and is_jit(dec.args[0]):
                        roots.append(
                            _JitRoot(fn, *_statics_from_call(dec))
                        )
    # call-wrapping forms: x = jax.jit(fn, ...) and
    # x = jax.jit(lambda ...: helper(...), ...)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and is_jit(node.func)
                and node.args):
            continue
        wrapped = node.args[0]
        if isinstance(wrapped, ast.Name):
            for fn in by_name.get(wrapped.id, []):
                roots.append(_JitRoot(fn, *_statics_from_call(node)))
        elif isinstance(wrapped, ast.Lambda):
            for sub in ast.walk(wrapped):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Name):
                    for fn in by_name.get(sub.func.id, []):
                        roots.append(
                            _JitRoot(fn, (), (), analyze_params=False)
                        )
    return roots


def _reachable(roots: list[_JitRoot], tree: ast.AST
               ) -> list[ast.FunctionDef]:
    """Functions reachable from jit roots via bare-name calls to
    module-local definitions (the roots themselves included)."""
    by_name: dict[str, list[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
    seen: dict[int, ast.FunctionDef] = {}
    queue = [r.fn for r in roots]
    while queue:
        fn = queue.pop()
        if id(fn) in seen:
            continue
        seen[id(fn)] = fn
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name):
                for callee in by_name.get(node.func.id, []):
                    if id(callee) not in seen:
                        queue.append(callee)
    return list(seen.values())


def _is_value_branch(test: ast.expr) -> bool:
    """True for tests whose truthiness needs the VALUE of the operand
    (tracer hazard). Identity checks against None, isinstance, and
    shape/dtype attribute probes resolve at trace time and are fine."""
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _is_value_branch(test.operand)
    if isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
    ):
        return False
    if isinstance(test, ast.Call):
        callee = test.func
        if isinstance(callee, ast.Name) and callee.id in (
            "isinstance", "callable", "hasattr", "len",
        ):
            return False
    return True


def _names_in(node: ast.AST) -> list[ast.Name]:
    """Name refs whose VALUE the test consumes. A name only reached
    through an attribute access (``table.capacity``, ``x.shape``) is a
    metadata/aux-field probe — static under tracing — and excluded."""
    attr_bases = {
        id(n.value) for n in ast.walk(node)
        if isinstance(n, ast.Attribute)
    }
    return [
        n for n in ast.walk(node)
        if isinstance(n, ast.Name) and id(n) not in attr_bases
    ]


def _check_dispatch_loops(files: list[SourceFile],
                          loops: dict = DISPATCH_LOOPS
                          ) -> list[Finding]:
    """``dispatch-loop-sync``: host<->device sync points inside a
    registered dispatch loop, outside its designated sync boundary.
    Reachability is module-local over bare-name calls AND
    ``self.<name>()`` method calls (the loops are methods); traversal
    prunes at the boundary functions — syncing there is the design."""
    findings: list[Finding] = []
    for src in files:
        if src.tree is None:
            continue
        cfg = next(
            (v for suffix, v in loops.items()
             if src.relpath.endswith(suffix)),
            None,
        )
        if cfg is None:
            continue
        root_names, boundary = cfg
        aliases = _import_aliases(src.tree)
        module = src.relpath.rsplit("/", 1)[-1]
        by_name: dict[str, list[ast.FunctionDef]] = {}
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                by_name.setdefault(node.name, []).append(node)
        seen: dict[int, ast.FunctionDef] = {}
        queue = [fn for name in root_names
                 for fn in by_name.get(name, [])]
        while queue:
            fn = queue.pop()
            if id(fn) in seen:
                continue
            seen[id(fn)] = fn
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = None
                if isinstance(node.func, ast.Name):
                    callee = node.func.id
                elif isinstance(node.func, ast.Attribute) and \
                        isinstance(node.func.value, ast.Name) and \
                        node.func.value.id == "self":
                    callee = node.func.attr
                if callee is not None and callee not in boundary:
                    queue.extend(by_name.get(callee, []))
        for fn in seen.values():
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                dotted = _dotted(node.func, aliases)
                hit = None
                if dotted is not None and _matches(dotted, SYNC_PREFIXES):
                    hit = dotted
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr in SYNC_METHODS:
                    hit = node.func.attr
                if hit is not None:
                    findings.append(Finding(
                        rule="dispatch-loop-sync",
                        path=src.relpath, line=node.lineno,
                        message=(
                            f"{hit}() inside dispatch-loop "
                            f"{fn.name}() outside the designated "
                            f"sync boundary {boundary}: a host<->"
                            "device sync here re-serializes host "
                            "packing against device compute — move "
                            "the read into the settle boundary"
                        ),
                        key=f"{module}:{fn.name}:{hit}",
                    ))
    return findings


def _scan_effects(fn: ast.AST, aliases: dict, module: str,
                  relpath: str, findings: list[Finding],
                  emitted: set) -> None:
    """Nondeterminism + host-callback calls inside one jit-reachable
    function, deduped across the local and cross-module walks."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func, aliases)
        if dotted is None:
            continue
        if _matches(dotted, NONDET_PREFIXES):
            rule, why = "jit-nondeterminism", (
                "the value is baked in at trace time (one arbitrary "
                "sample per compile) — pass it in as an argument"
            )
        elif _matches(dotted, HOST_CALLBACKS):
            rule, why = "jit-host-callback", (
                "host callbacks stall the device pipeline per step "
                "(use jax.debug.print for debugging, or move the "
                "effect outside the kernel)"
            )
        else:
            continue
        key = f"{module}:{fn.name}:{dotted}"
        if (rule, key) in emitted:
            continue
        emitted.add((rule, key))
        findings.append(Finding(
            rule=rule, path=relpath, line=node.lineno,
            message=(
                f"{dotted}() inside jit-reachable {fn.name}(): {why}"
            ),
            key=key,
        ))


def check(files: list[SourceFile], graph=None) -> list[Finding]:
    findings = _check_dispatch_loops(files)
    graph = graph or build_callgraph(files)
    emitted: set = set()
    # cross-module frontier: FunctionInfos (keyed by node id) reached
    # from any module's jit roots through resolvable imported edges
    foreign_seeds: dict[int, object] = {}
    for src in files:
        if src.tree is None:
            continue
        aliases = _import_aliases(src.tree)
        roots = _find_roots(src.tree, aliases)
        if not roots:
            continue
        module = src.relpath.rsplit("/", 1)[-1]

        # -- nondeterminism + host callbacks in jit-reachable code ----
        local_fns = _reachable(roots, src.tree)
        for fn in local_fns:
            _scan_effects(fn, aliases, module, src.relpath, findings,
                          emitted)
        # cross-module callees of everything locally reachable: the
        # shared call graph resolves imported helpers the bare-name
        # walker cannot see
        for fn in local_fns:
            caller = graph.info_for_node(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                for target in graph.resolve_call(node, caller, src):
                    if target.relpath != src.relpath:
                        foreign_seeds[id(target.node)] = target

        # -- per-root: tracer branches + unhashable statics ------------
        for root in roots:
            if not root.analyze_params:
                continue
            fn = root.fn
            args = fn.args
            pos = list(args.posonlyargs) + list(args.args)
            nonstatic = {
                a.arg for i, a in enumerate(pos)
                if i not in root.static_argnums
                and a.arg not in root.static_argnames
                and a.arg not in ("self", "cls")
            }
            # keyword-only params trace too; only static_argnames can
            # mark them static (static_argnums is positional)
            nonstatic |= {
                a.arg for a in args.kwonlyargs
                if a.arg not in root.static_argnames
            }
            for node in ast.walk(fn):
                tests = []
                if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                    tests.append(node.test)
                elif isinstance(node, ast.Assert):
                    tests.append(node.test)
                for test in tests:
                    if not _is_value_branch(test):
                        continue
                    hit = next(
                        (n for n in _names_in(test)
                         if n.id in nonstatic), None,
                    )
                    if hit is not None:
                        findings.append(Finding(
                            rule="jit-tracer-branch",
                            path=src.relpath, line=test.lineno,
                            message=(
                                f"Python branch on parameter "
                                f"{hit.id!r} of jitted {fn.name}(): "
                                "under tracing this raises (or "
                                "silently specializes); use lax.cond/"
                                "jnp.where, or mark the arg static"
                            ),
                            key=f"{module}:{fn.name}:{hit.id}",
                        ))
            defaults = args.defaults
            # defaults align with the TAIL of positional params;
            # kw_defaults align 1:1 with kwonlyargs (None = absent)
            offset = len(pos) - len(defaults)
            static_with_default = []
            for i, a in enumerate(pos):
                if i not in root.static_argnums and \
                        a.arg not in root.static_argnames:
                    continue
                static_with_default.append(
                    (a, defaults[i - offset] if i >= offset else None)
                )
            static_with_default.extend(
                (a, d) for a, d in zip(args.kwonlyargs,
                                       args.kw_defaults)
                if a.arg in root.static_argnames
            )
            for a, default in static_with_default:
                if isinstance(default, (ast.List, ast.Dict, ast.Set,
                                        ast.ListComp, ast.DictComp,
                                        ast.SetComp)):
                    findings.append(Finding(
                        rule="jit-static-unhashable",
                        path=src.relpath, line=default.lineno,
                        message=(
                            f"static arg {a.arg!r} of jitted "
                            f"{fn.name}() defaults to an unhashable "
                            "mutable — static args key the compile "
                            "cache and must be hashable (use a tuple/"
                            "frozenset or a frozen dataclass)"
                        ),
                        key=f"{module}:{fn.name}:{a.arg}",
                    ))

    # -- cross-module reachability: scan every function the shared
    # call graph reaches from the per-module frontiers, with the
    # DEFINING module's aliases (a hazard reports in its own file) ---
    for info in graph.reachable(foreign_seeds.values()):
        _scan_effects(
            info.node, graph.module_aliases(info.relpath),
            info.relpath.rsplit("/", 1)[-1], info.relpath,
            findings, emitted,
        )
    return findings
