"""shapecheck — abstract shape/dtype/donation analysis over the
kernel layer.

The kernel layer (``ops/``, ``parallel/seq_shard.py``, the sidecar's
dispatch loop) runs under three conventions that until this pass were
prose only (docs/PERF.md): donation safety ("never read a donated
buffer"), the bucket ladder as the ONE shape source (an unladdered
call site is a silent recompile storm — 20-40s per shape on the real
chip), and dtype stability (a silent int32->int64 widen doubles HBM).
This family turns each into a machine-checked rule, by abstract
interpretation over the AST: dataflow for donated values, a
laddered-ness lattice for shape arguments, dtype/shape propagation
through jit-reachable kernel bodies.

The runtime cross-check is ``testing/jitsan.py`` (the PR5
static<->runtime differential pattern): jitsan counts the shapes each
jit root actually compiles and traps reads of donated buffers;
``tests/test_jitsan.py`` pins (a) observed compile counts per root <=
the ladder size this module derives (:func:`ladder_bounds`) and (b)
this module's inferred output shapes/dtypes (:func:`infer_kernel_output`)
== ``jax.eval_shape`` across every ladder rung — an
abstract-interpreter gap fails by name, never silently.

Like every fluidlint pass, this module imports NOTHING it lints (no
jax, no ops): signatures and ladder arithmetic are pure Python over
``(shape-tuple, dtype-string)`` descriptors.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Optional

from .callgraph import CallGraph, build_callgraph
from .core import (
    Finding,
    SourceFile,
    dotted_path as _dotted,
    import_aliases,
)

# ---------------------------------------------------------------------------
# reviewed registries (the INDIRECT_CALLS pattern: every entry is a
# deliberate, justified exception or blessing — widen with review only)

# Path components where the unladdered-jit-shape rule applies: the
# serving kernel layer. tests/ dispatch deliberately
# exact-fit shapes (fuzz sweeps, shape-cliff measurements) — that is
# their job, and each runs a bounded number of shapes once; the storm
# the rule exists to stop is an unladdered shape source on the SERVING
# path, where windows vary per flush.
LADDER_SCOPE_COMPONENTS = ("ops", "parallel", "service", "tools")

# Functions whose RESULTS carry ladder-governed shapes (relpath
# suffix, qualname). _pack_rows buckets via BucketLadder internally;
# make_table's capacities come from the ladder's rungs at
# every serve-path call site (prewarm/regrow walk capacity_rungs) and
# fresh tables are setup-time, not per-flush.
LADDER_SOURCES = (
    # pack_rows lives in ops/host_bridge.py since the mesh-pool PR;
    # the sidecar re-exports it as _pack_rows (both names resolve)
    ("ops/host_bridge.py", "pack_rows"),
    ("service/tpu_sidecar.py", "_pack_rows"),
    # the event-graph compiler re-buckets its prefix/suffix windows
    # through the BucketLadder internally (same contract as pack_rows)
    ("ops/event_graph.py", "build_event_graph"),
    # the wire-1.3 columnar slice entry point: its [n, 12] block is
    # consumed by pack_rows' block fast path, so its column widths
    # reach the device only through the same BucketLadder bucketing
    ("ops/host_bridge.py", "lower_columns"),
    ("ops/segment_table.py", "make_table"),
    # the tree plane's packer buckets window depth via the same
    # BucketLadder; make_tree_table is the tree slab's make_table
    # (serve-path capacities come from capacity_rungs / the pool's
    # fixed per-doc capacity)
    ("ops/tree_apply.py", "pack_tree_window"),
    ("ops/tree_apply.py", "make_tree_table"),
)

# Reviewed per-call-site exceptions: (module, caller-qualname, donated
# or shape argument display) -> justification. Keys mirror finding
# keys so an entry here is exactly one suppressed finding.
LADDERED_CALLS: dict[tuple[str, str, str], str] = {
    # K is the egwalker factory's cache key — the static
    # program-selection knob, not a per-dispatch shape. These sites
    # pass the module constant EG_K: exactly one program per route,
    # and prewarm dispatches through the same K, so the one compile
    # is paid before serving. A DATA-DEPENDENT K elsewhere still gets
    # flagged (one XLA program per distinct value).
    ("tpu_sidecar.py", "TpuMergeSidecar._apply_program",
     "apply_window_egwalker[K]"):
        "K=EG_K module constant; prewarm walks the egwalker route",
    ("tpu_sidecar.py", "TpuMergeSidecar._apply_program",
     "apply_window_egwalker_pingpong[K]"):
        "K=EG_K module constant; prewarm walks the ping-pong jits",
}

# Calls whose result is freshly allocated (never aliases argument
# buffers): names passed INTO them are not donated when the result is.
FRESH_CONSTRUCTORS = ("make_table", "make_tree_table")

# ---------------------------------------------------------------------------
# prewarm-coverage registries

# Dispatch-loop roots (relpath suffix -> qualnames): every jit compile
# site reachable from these must also be reachable from the prewarm
# roots below, or first-request latency pays a mid-serve XLA compile
# the BucketLadder prewarm never saw.
DISPATCH_ROOTS = {
    "service/tpu_sidecar.py": (
        "TpuMergeSidecar._dispatch",
        "TpuMergeSidecar._apply_program",
        "TpuMergeSidecar._settle",
        "TpuMergeSidecar._recover",
        "TpuMergeSidecar._grow",
        "TpuMergeSidecar.apply",
    ),
    "service/tree_sidecar.py": (
        "TreeSidecar._dispatch",
        "TreeSidecar._settle",
        "TreeSidecar._recover",
        "TreeSidecar._grow",
        "TreeSidecar.apply",
    ),
}

PREWARM_ROOTS = {
    "service/tpu_sidecar.py": (
        "TpuMergeSidecar.prewarm",
    ),
    "service/tree_sidecar.py": (
        "TreeSidecar.prewarm",
    ),
}

# Edges the call graph cannot resolve syntactically (attribute-held
# objects), declared like concurrency.INDIRECT_CALLS:
#   (relpath suffix, caller qualname) -> ((relpath suffix, qualname), ...)
PREWARM_INDIRECT = {
    # the pool tier dispatches at the settle boundary through the
    # attribute-held pool — EITHER tier select_pool can return
    ("service/tpu_sidecar.py", "TpuMergeSidecar._settle"): (
        ("service/tpu_sidecar.py", "SeqShardedPool.dispatch_pending"),
        ("parallel/mesh_pool.py", "MeshShardedPool.dispatch_pending"),
    ),
    ("service/tpu_sidecar.py", "TpuMergeSidecar._recover"): (
        ("service/tpu_sidecar.py", "TpuMergeSidecar._admit_to_pool"),
    ),
    ("service/tpu_sidecar.py", "TpuMergeSidecar._admit_to_pool"): (
        ("service/tpu_sidecar.py", "SeqShardedPool.admit"),
        ("parallel/mesh_pool.py", "MeshShardedPool.admit"),
    ),
    # prewarm warms the pool tier through the same attribute
    ("service/tpu_sidecar.py", "TpuMergeSidecar._warm_pool"): (
        ("service/tpu_sidecar.py", "SeqShardedPool.prewarm"),
        ("parallel/mesh_pool.py", "MeshShardedPool.prewarm"),
    ),
    # replay_chunked receives the pool's _apply as a callback value
    # (lives in ops/host_bridge.py since the mesh-pool PR; the
    # sidecar re-exports it as _replay_chunked)
    ("ops/host_bridge.py", "replay_chunked"): (
        ("service/tpu_sidecar.py", "SeqShardedPool._apply"),
        ("parallel/mesh_pool.py", "MeshShardedPool._apply"),
    ),
    # the tree plane's attribute-held pool, same edges as the merge
    # sidecar's: settle-boundary dispatch, recovery admission, and
    # the prewarm walk through _warm_pool
    ("service/tree_sidecar.py", "TreeSidecar._settle"): (
        ("service/tree_sidecar.py", "TreeSeqPool.dispatch_pending"),
    ),
    ("service/tree_sidecar.py", "TreeSidecar._recover"): (
        ("service/tree_sidecar.py", "TreeSidecar._admit_to_pool"),
    ),
    ("service/tree_sidecar.py", "TreeSidecar._admit_to_pool"): (
        ("service/tree_sidecar.py", "TreeSeqPool.admit"),
    ),
    ("service/tree_sidecar.py", "TreeSidecar._warm_pool"): (
        ("service/tree_sidecar.py", "TreeSeqPool.prewarm"),
    ),
}

# ---------------------------------------------------------------------------
# dtype-widen registry

WIDE_DTYPE_SUFFIXES = (
    "int64", "uint64", "float64", "complex128", "longlong",
)
WIDE_DTYPE_STRINGS = ("int64", "uint64", "float64", "complex128")
# astype(int)/astype(float): the Python builtins map to 64-bit under
# x64 mode — inside a kernel that is a latent 2x HBM widen
WIDE_BUILTINS = ("int", "float")


# ===========================================================================
# jit-object collection (shared by every rule in this family)


@dataclasses.dataclass
class JitObject:
    """One ``jax.jit`` compile site in a module."""

    module: str                 # file name, e.g. "merge_kernel.py"
    relpath: str
    name: str                   # bound name, or enclosing qualname
    donate_argnums: tuple       # positional indices donated
    static_argnums: tuple
    static_argnames: tuple
    wrapped: Optional[str]      # wrapped function name, if a Name
    lambda_callees: tuple       # bare names called from a jitted lambda
    scope: Optional[str]        # enclosing function qualname (factory)
    line: int


def _literal(node):
    if node is None:
        return None
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def _jit_kwargs(call: ast.Call) -> tuple[tuple, tuple, tuple]:
    def tup(name):
        val = _literal(next(
            (k.value for k in call.keywords if k.arg == name), None))
        if isinstance(val, int):
            val = (val,)
        if isinstance(val, str):
            val = (val,)
        return tuple(val or ())

    return (tup("donate_argnums"), tup("static_argnums"),
            tup("static_argnames"))


def collect_jit_objects(src: SourceFile,
                        aliases: dict) -> list[JitObject]:
    """Every jit compile site in one module: module-level/assigned
    ``X = jax.jit(fn, ...)`` forms, decorated defs, and jit calls
    nested inside factory functions (``_jit_cache[K] = jax.jit(...)``
    — identity is the enclosing function)."""
    if src.tree is None:
        return []
    module = src.relpath.rsplit("/", 1)[-1]

    def is_jit(node) -> bool:
        return _dotted(node, aliases) == "jax.jit"

    # enclosing-function map for factory identity. A def does NOT
    # enclose itself: a decorated module-level jit (``@jax.jit`` on
    # ``compact``) is a plain module jit, and self-scoping it made the
    # prewarm walker treat it as factory-cached and skip its call
    # edges entirely.
    scope_of: dict[int, str] = {}

    def map_scope(fn, qual):
        for sub in ast.walk(fn):
            if sub is not fn:
                scope_of.setdefault(id(sub), qual)

    for node in src.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            map_scope(node, node.name)
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    map_scope(sub, f"{node.name}.{sub.name}")

    out: list[JitObject] = []
    seen_calls: set[int] = set()

    # decorated defs
    for node in ast.walk(src.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            call = None
            if is_jit(dec):
                donate, statics, statnames = (), (), ()
            elif isinstance(dec, ast.Call):
                target = _dotted(dec.func, aliases)
                if target == "jax.jit":
                    call = dec
                elif target in ("functools.partial", "partial") and \
                        dec.args and is_jit(dec.args[0]):
                    call = dec
                else:
                    continue
                donate, statics, statnames = _jit_kwargs(call)
            else:
                continue
            if call is not None:
                seen_calls.add(id(call))
            out.append(JitObject(
                module, src.relpath, node.name, donate, statics,
                statnames, wrapped=node.name, lambda_callees=(),
                scope=scope_of.get(id(node)), line=node.lineno,
            ))

    # bound names: one pass over the module's Assigns instead of one
    # full-tree walk per jit call
    assigned_name: dict[int, str] = {}
    for stmt in ast.walk(src.tree):
        if isinstance(stmt, ast.Assign):
            for tgt in stmt.targets:
                if isinstance(tgt, ast.Name):
                    assigned_name[id(stmt.value)] = tgt.id

    # call forms: X = jax.jit(fn, ...) / cache[K] = jax.jit(fn, ...)
    for node in ast.walk(src.tree):
        if not (isinstance(node, ast.Call) and is_jit(node.func)
                and node.args) or id(node) in seen_calls:
            continue
        donate, statics, statnames = _jit_kwargs(node)
        wrapped = None
        lambda_callees: tuple = ()
        arg0 = node.args[0]
        if isinstance(arg0, ast.Name):
            wrapped = arg0.id
        elif isinstance(arg0, ast.Lambda):
            lambda_callees = tuple(sorted({
                sub.func.id for sub in ast.walk(arg0)
                if isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Name)
            }))
        # bound name: the enclosing Assign with a Name target, else
        # the enclosing function (factory), else anonymous
        parent_scope = scope_of.get(id(node))
        name = assigned_name.get(id(node))
        if name is None:
            name = parent_scope or f"<jit@{node.lineno}>"
        out.append(JitObject(
            module, src.relpath, name, donate, statics, statnames,
            wrapped=wrapped, lambda_callees=lambda_callees,
            scope=parent_scope, line=node.lineno,
        ))
    return out


# ===========================================================================
# per-function dataflow helpers


def _functions(tree: ast.AST):
    """(qualname, node) for every def, class methods qualified."""
    out = []
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.append((node.name, node))
            for sub in ast.walk(node):
                if sub is not node and isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.append((f"{node.name}.{sub.name}", sub))
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    out.append((f"{node.name}.{sub.name}", sub))
    return out


def _param_names(fn) -> list[str]:
    args = fn.args
    return [a.arg for a in
            list(args.posonlyargs) + list(args.args)
            + list(args.kwonlyargs)]


def _names_loaded(node: ast.AST) -> set[str]:
    return {
        n.id for n in ast.walk(node)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


def _call_target_names(call: ast.Call) -> list[str]:
    """Candidate names a call site may dispatch through: the bare
    name, a module-attr tail (``merge_kernel.apply_window`` ->
    "apply_window"), or ``self.method``."""
    func = call.func
    if isinstance(func, ast.Name):
        return [func.id]
    if isinstance(func, ast.Attribute):
        return [func.attr]
    return []


def _assigned_names(stmt: ast.stmt) -> set[str]:
    out: set[str] = set()
    if isinstance(stmt, ast.Assign):
        for tgt in stmt.targets:
            if isinstance(tgt, ast.Name):
                out.add(tgt.id)
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                out.update(e.id for e in tgt.elts
                           if isinstance(e, ast.Name))
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        if isinstance(stmt.target, ast.Name):
            out.add(stmt.target.id)
    elif isinstance(stmt, ast.For) and isinstance(stmt.target, ast.Name):
        out.add(stmt.target.id)
    return out


class _Index:
    """Per-run parse products, computed ONCE per file: import
    aliases, the function list, and each function's call sites. The
    fixpoint solvers re-traverse these every iteration — without the
    index each pass re-ran ``ast.walk`` over the whole tree per
    (file x iteration), which dominated the family's runtime (the
    gate-budget satellite of the shapecheck PR)."""

    def __init__(self, files: list[SourceFile], graph: CallGraph):
        self.files = files
        self.graph = graph
        self.aliases: dict[str, dict] = {}
        self.functions: dict[str, list] = {}
        self.calls: dict[int, list] = {}
        for src in files:
            if src.tree is None:
                continue
            self.aliases[src.relpath] = import_aliases(src.tree)
            fns = _functions(src.tree)
            self.functions[src.relpath] = fns
            for _, fn in fns:
                self.calls[id(fn)] = [
                    n for n in ast.walk(fn)
                    if isinstance(n, ast.Call)
                ]


# ===========================================================================
# rule: donated-buffer-reuse


class _DonationAnalysis:
    """Fixpoint over the call graph: which callables donate which
    argument positions/param names, then read-after-donation checks at
    every call site."""

    def __init__(self, idx: _Index, jits_by_file: dict):
        self.idx = idx
        self.files = idx.files
        self.graph = idx.graph
        self.jits_by_file = jits_by_file
        # jit-object donating positions per (relpath, name)
        self.jit_donors: dict[tuple, tuple] = {}
        # function donating param NAMES per (relpath, qualname)
        self.fn_donors: dict[tuple, set] = {}
        # factory functions returning a donating jit:
        # (relpath, qualname) -> donated positions
        self.factory_donors: dict[tuple, tuple] = {}
        for src in self.files:
            for jit in jits_by_file.get(src.relpath, ()):
                if not jit.donate_argnums:
                    continue
                self.jit_donors[(jit.relpath, jit.name)] = \
                    jit.donate_argnums
                if jit.scope is not None:
                    # a jit created inside a function: treat the
                    # enclosing function as a factory whose RESULT
                    # donates (the `_get_jit_pingpong(K)(dead, ...)`
                    # call-of-call shape)
                    self.factory_donors[(jit.relpath, jit.scope)] = \
                        jit.donate_argnums

    # -- donated positions of one call site ---------------------------
    def donated_positions(self, call: ast.Call, src: SourceFile,
                          caller_info) -> tuple:
        # direct jit-object call: f(...) where f is a donating jit
        # bound in this module (or `mod.f(...)`)
        for name in _call_target_names(call):
            pos = self.jit_donors.get((src.relpath, name))
            if pos:
                return pos
        # call-of-call through a donating factory:
        # `factory(K)(dead, ...)`
        if isinstance(call.func, ast.Call):
            inner = call.func
            for target in self.graph.resolve_call(
                    inner, caller_info, src):
                pos = self.factory_donors.get(target.key)
                if pos:
                    return pos
            for name in _call_target_names(inner):
                # module-local factory the graph may not resolve in
                # fixture trees
                for key, pos in self.factory_donors.items():
                    if key[0] == src.relpath and key[1] == name:
                        return pos
        # resolved call to a function with donating params
        donated: list[int] = []
        for target in self.graph.resolve_call(call, caller_info, src):
            names = self.fn_donors.get(target.key)
            if not names:
                continue
            params = _param_names(target.node)
            offset = 1 if params[:1] in (["self"], ["cls"]) else 0
            for i, p in enumerate(params):
                if p in names:
                    donated.append(i - offset)
        return tuple(sorted(set(d for d in donated if d >= 0)))

    def donated_name_args(self, call: ast.Call, positions: tuple,
                          ) -> tuple[set, int]:
        """Names feeding donated argument expressions at a call site
        (FRESH_CONSTRUCTORS excluded). Also returns the line of the
        first donated argument for reporting.

        A name that appears only as an ATTRIBUTE BASE inside the
        donated expression (``dead if self.donate else None`` loads
        ``self`` but donates ``dead``) is not itself donated — the
        donated value is the attribute, which the pass treats as
        attribute-held state (a documented conservative gap), not the
        base object."""
        names: set[str] = set()
        line = call.lineno
        exprs = []
        for i, arg in enumerate(call.args):
            if i in positions:
                exprs.append(arg)
        # keywords cannot map to donate_argnums positions statically;
        # conservatively skipped (jax donation is positional anyway)
        for expr in exprs:
            line = expr.lineno
            attr_bases = {
                id(n.value) for n in ast.walk(expr)
                if isinstance(n, ast.Attribute)
            }
            # a fresh-constructor result is unaliased: exempt THAT
            # call subtree only (its args do not alias its result),
            # not the whole expression — the other branch of
            # ``fodder if ok else make_table(n, c)`` is still donated
            fresh_ids: set[int] = set()
            for node in ast.walk(expr):
                if isinstance(node, ast.Call) and \
                        id(node) not in fresh_ids:
                    tgt = _call_target_names(node)
                    if any(t in FRESH_CONSTRUCTORS for t in tgt):
                        fresh_ids.update(
                            id(sub) for sub in ast.walk(node))
            for node in ast.walk(expr):
                if id(node) in fresh_ids:
                    continue
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        id(node) not in attr_bases:
                    names.add(node.id)
        return names, line

    # -- fixpoint: propagate donation through wrapper params ----------
    def solve(self) -> None:
        changed = True
        while changed:
            changed = False
            for src in self.files:
                if src.tree is None:
                    continue
                for qual, fn in self.idx.functions[src.relpath]:
                    params = set(_param_names(fn))
                    info = self.graph.info_for_node(fn)
                    for node in self.idx.calls[id(fn)]:
                        pos = self.donated_positions(node, src, info)
                        if not pos:
                            continue
                        names, _ = self.donated_name_args(node, pos)
                        donated_params = names & params
                        if donated_params:
                            key = (src.relpath, qual)
                            have = self.fn_donors.setdefault(key, set())
                            if not donated_params <= have:
                                have |= donated_params
                                changed = True


def _reads_after_call(fn, call: ast.Call, names: set,
                      ) -> Optional[ast.Name]:
    """First Load of a donated name on any path after ``call`` inside
    ``fn``. 'After' = sibling statements after the containing
    statement at every enclosing block level; when the call sits in a
    ``try`` body the except-handler bodies, ``else`` and ``finally``
    blocks are post-call paths too (an exception AFTER the donating
    dispatch lands in the handler with the buffer already consumed,
    and ``finally`` runs on every path — including after a
    ``return pingpong(dead, ...)``); when the call sits inside a
    loop, the loop body from the top is the wrap-around path. A
    top-level reassignment of a name kills its taint; reassignments
    inside nested branches do NOT (any-path semantics — a documented
    conservative approximation)."""

    # statement spine: enclosing block chain down to the call
    spine: list[tuple[list, int]] = []

    def find(block: list) -> bool:
        for i, stmt in enumerate(block):
            found_here = any(n is call for n in ast.walk(stmt))
            if not found_here:
                continue
            spine.append((block, i))
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, field, None)
                if sub and find(sub):
                    return True
            for handler in getattr(stmt, "handlers", []):
                if find(handler.body):
                    return True
            return True
        return False

    if not find(fn.body):
        return None

    def scan(stmts, live: set) -> Optional[ast.Name]:
        for stmt in stmts:
            if not live:
                return None
            for node in ast.walk(stmt):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in live:
                    return node
            live.difference_update(_assigned_names(stmt))
        return None

    # the statement directly containing the call may end the function
    # (``return pingpong(dead, ...)`` / ``raise``): no sibling runs
    # afterward — scanning them would walk OTHER branches' dead code
    # (the _apply_program false positive). Enclosing try blocks still
    # get their post-branch scan below: ``finally`` runs even after a
    # return, and a raise lands in the matching handler.
    inner_block, inner_i = spine[-1]
    terminal = isinstance(inner_block[inner_i], (ast.Return, ast.Raise))
    is_raise = isinstance(inner_block[inner_i], ast.Raise)

    live = set(names)

    # innermost-out: siblings after the call at each level, plus the
    # post-call branches of enclosing try statements
    loops: list = []
    child_block: Optional[list] = None
    for block, i in reversed(spine):
        # the containing statement itself may reassign (x = f(x,...))
        live.difference_update(_assigned_names(block[i]))
        stmt = block[i]
        if isinstance(stmt, ast.Try) and child_block is not None:
            in_handler = any(
                child_block is h.body for h in stmt.handlers)
            if child_block is stmt.body:
                if terminal and not is_raise:
                    # return exits through finally only
                    post = [stmt.finalbody]
                elif is_raise:
                    post = [h.body for h in stmt.handlers] + \
                        [stmt.finalbody]
                else:
                    post = [h.body for h in stmt.handlers] + \
                        [stmt.orelse, stmt.finalbody]
            elif in_handler or child_block is stmt.orelse:
                post = [stmt.finalbody]
            else:           # call inside finally: nothing follows
                post = []
            for branch in post:
                # independent live copy per branch (any-path)
                hit = scan(branch, set(live))
                if hit is not None:
                    return hit
        if not terminal:
            hit = scan(block[i + 1:], live)
            if hit is not None:
                return hit
        owner = next(
            (st for st in ast.walk(fn)
             if getattr(st, "body", None) is block
             or getattr(st, "orelse", None) is block
             or getattr(st, "finalbody", None) is block
             or any(getattr(h, "body", None) is block
                    for h in getattr(st, "handlers", []))),
            None,
        )
        if not terminal and isinstance(
                owner, (ast.For, ast.While, ast.AsyncFor)):
            # snapshot the taint surviving to the END of this loop's
            # body: the containing statement's own rebinding and the
            # sibling scan just ran have already killed their names —
            # seeding the wrap path with the ORIGINAL set would flag
            # the sanctioned rotate-in-a-loop idiom
            # (``dead = pingpong(dead, b)`` then loop around)
            loops.append((block, i, set(live)))
        child_block = block
    # wrap-around: for each enclosing loop, the body re-executes from
    # its top down to the call statement
    for block, i, survived in loops:
        hit = scan(block[:i], survived)
        if hit is not None:
            return hit
    return None


def _check_donated(idx: _Index, jits_by_file: dict) -> list[Finding]:
    ana = _DonationAnalysis(idx, jits_by_file)
    ana.solve()
    findings: list[Finding] = []
    graph = idx.graph
    for src in idx.files:
        if src.tree is None:
            continue
        module = src.relpath.rsplit("/", 1)[-1]
        for qual, fn in idx.functions[src.relpath]:
            info = graph.info_for_node(fn)
            for node in idx.calls[id(fn)]:
                pos = ana.donated_positions(node, src, info)
                if not pos:
                    continue
                names, line = ana.donated_name_args(node, pos)
                if not names:
                    continue
                # a name passed BOTH donated and live in one call is
                # an immediate aliasing bug (donating the live input);
                # live inputs count whether positional or keyword
                other_names: set[str] = set()
                for i, arg in enumerate(node.args):
                    if i not in pos:
                        other_names |= _names_loaded(arg)
                for kw in node.keywords:
                    other_names |= _names_loaded(kw.value)
                overlap = names & other_names
                if overlap:
                    nm = sorted(overlap)[0]
                    findings.append(Finding(
                        rule="donated-buffer-reuse",
                        path=src.relpath, line=line,
                        message=(
                            f"{nm!r} is passed both as a DONATED "
                            f"argument and as a live input in the "
                            "same dispatch: XLA may reuse its "
                            "buffers for the output while the "
                            "kernel still reads them"
                        ),
                        key=f"{module}:{qual}:{nm}",
                    ))
                    continue
                hit = _reads_after_call(fn, node, names)
                if hit is not None:
                    findings.append(Finding(
                        rule="donated-buffer-reuse",
                        path=src.relpath, line=hit.lineno,
                        message=(
                            f"{hit.id!r} is read after being donated "
                            f"to a jit with donate_argnums (call at "
                            f"line {line}): its buffers may already "
                            "back the dispatch output — drop every "
                            "reference after donating (docs/PERF.md "
                            "buffer-ownership rules)"
                        ),
                        key=f"{module}:{qual}:{hit.id}",
                    ))
    return findings


# ===========================================================================
# rule: unladdered-jit-shape


def _in_ladder_scope(relpath: str) -> bool:
    parts = relpath.split("/")
    return any(p in LADDER_SCOPE_COMPONENTS for p in parts[:-1])


# laddered-ness lattice verdicts
_LADDERED = "laddered"
_OK = "ok"              # attribute-held / None / unresolvable: trusted
_RAW = "raw"            # provably not ladder-derived


class _LadderAnalysis:
    def __init__(self, idx: _Index, jits_by_file: dict):
        self.idx = idx
        self.files = idx.files
        self.graph = idx.graph
        # local env per function: classify() never reads fixpoint
        # state (shape_params feeds shape_positions only), so the env
        # is iteration-invariant and memoizes per def
        self._env_cache: dict[int, dict] = {}
        # shape-determining param names per (relpath, qualname)
        self.shape_params: dict[tuple, set] = {}
        # jit objects per (relpath, name) -> static argnums / argnames
        self.jit_statics: dict[tuple, tuple] = {}
        self.jit_static_names: dict[tuple, tuple] = {}
        self.jit_names: dict[str, set] = {}     # relpath -> names
        self.factories: set[tuple] = set()      # jit factory functions
        for src in self.files:
            for jit in jits_by_file.get(src.relpath, ()):
                self.jit_statics[(jit.relpath, jit.name)] = (
                    jit.static_argnums)
                self.jit_static_names[(jit.relpath, jit.name)] = (
                    jit.static_argnames)
                self.jit_names.setdefault(jit.relpath, set()).add(
                    jit.name)
                if jit.scope is not None:
                    self.factories.add((jit.relpath, jit.scope))

    def _is_source_call(self, call: ast.Call, src: SourceFile,
                        caller_info, aliases: dict) -> bool:
        # BucketLadder itself (constructor, classmethod, or a method
        # on an imported/aliased name)
        dotted = _dotted(call.func, aliases)
        if dotted is not None and "BucketLadder" in dotted.split("."):
            return True
        for target in self.graph.resolve_call(call, caller_info, src):
            for suffix, qual in LADDER_SOURCES:
                if target.relpath.endswith(suffix) and \
                        target.qualname == qual:
                    return True
            # a registered jit entry's OUTPUT is kernel-shaped
            if target.relpath in self.jit_names and \
                    target.name in self.jit_names[target.relpath]:
                return True
        for name in _call_target_names(call):
            if any(qual == name for _, qual in LADDER_SOURCES):
                # bare-name fallback for fixture trees the graph
                # cannot resolve module paths for
                if isinstance(call.func, ast.Name):
                    return True
            if (src.relpath, name) in self.jit_statics:
                return True
        return False

    def classify(self, expr: ast.expr, src: SourceFile, fn,
                 caller_info, aliases: dict,
                 env: dict) -> tuple[str, set]:
        """-> (verdict, param-names the expr derives from)."""
        params: set = set()
        found = {"laddered": False, "raw_leaf": False}

        fn_params = set(_param_names(fn))

        def walk(node, bound: frozenset = frozenset()) -> None:
            if isinstance(node, ast.Call):
                if self._is_source_call(node, src, caller_info,
                                        aliases):
                    found["laddered"] = True
                    return
                for sub in list(node.args) + [
                        k.value for k in node.keywords]:
                    walk(sub, bound)
                if isinstance(node.func, ast.Call):
                    walk(node.func, bound)
                elif isinstance(node.func, ast.Attribute):
                    # a method call's result derives from its
                    # receiver: ``state.items()`` is as laddered as
                    # ``state``.
                    # NOT so for module-attr calls (``jnp.asarray``):
                    # the base is an import alias, not a value
                    root = node.func.value
                    while isinstance(root, ast.Attribute):
                        root = root.value
                    if not (isinstance(root, ast.Name)
                            and root.id in aliases):
                        walk(node.func.value, bound)
                return
            if isinstance(node, ast.Attribute):
                return          # attribute-held state: trusted (FN)
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Load):
                    if node.id in bound:
                        # comprehension variable: the generator's
                        # iterable was walked and already contributed
                        # its verdict
                        pass
                    elif node.id in fn_params:
                        params.add(node.id)
                    elif node.id in env:
                        verdict, p = env[node.id]
                        if verdict == _LADDERED:
                            found["laddered"] = True
                        elif verdict == _RAW:
                            found["raw_leaf"] = True
                        params.update(p)
                    else:
                        found["raw_leaf"] = True
                return
            if isinstance(node, ast.Constant):
                return
            if isinstance(node, (ast.ListComp, ast.SetComp,
                                 ast.GeneratorExp, ast.DictComp)):
                # bind each generator's targets to its iterable, then
                # classify the element under those bindings
                inner = set(bound)
                for gen in node.generators:
                    walk(gen.iter, frozenset(inner))
                    inner |= {
                        n.id for n in ast.walk(gen.target)
                        if isinstance(n, ast.Name)
                    }
                    for cond in gen.ifs:
                        walk(cond, frozenset(inner))
                if isinstance(node, ast.DictComp):
                    walk(node.key, frozenset(inner))
                    walk(node.value, frozenset(inner))
                else:
                    walk(node.elt, frozenset(inner))
                return
            if isinstance(node, (ast.Tuple, ast.List, ast.Dict,
                                 ast.Set, ast.IfExp, ast.BinOp,
                                 ast.Subscript, ast.Starred,
                                 ast.Compare,
                                 ast.BoolOp, ast.UnaryOp,
                                 ast.FormattedValue, ast.JoinedStr,
                                 ast.Slice)):
                for child in ast.iter_child_nodes(node):
                    walk(child, bound)
                return
            # anything else: trusted rather than misflagged
            return

        walk(expr)
        if found["laddered"]:
            return _LADDERED, set()
        if params:
            return "param", params
        if found["raw_leaf"]:
            return _RAW, set()
        return _OK, set()

    def _local_env(self, fn, src: SourceFile, caller_info,
                   aliases: dict) -> dict:
        """name -> (verdict, params) from straight-line assignments in
        statement order (last assignment wins; good enough for the
        kernel wrappers this rule audits). Memoized per def — see
        ``_env_cache``."""
        cached = self._env_cache.get(id(fn))
        if cached is not None:
            return cached
        env: dict = {}
        # textual order, NOT ast.walk's breadth-first order — BFS
        # visits every top-level assignment before any nested one, so
        # a branch-local rebinding would always override a LATER
        # top-level one (and vice versa for the laddered verdict)
        assigns = sorted(
            (node for node in ast.walk(fn)
             if isinstance(node, ast.Assign)),
            key=lambda n: (n.lineno, n.col_offset),
        )
        for node in assigns:
            verdict, params = self.classify(
                node.value, src, fn, caller_info, aliases, env)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    env[tgt.id] = (verdict, params)
        self._env_cache[id(fn)] = env
        return env

    def shape_positions(self, call: ast.Call, src: SourceFile,
                        caller_info
                        ) -> tuple[tuple, frozenset, Optional[str]]:
        """(positions, keyword-names, display-name) of
        shape-determining args at one call site; ((), frozenset(),
        None) when the target is not shape-constrained. Keyword args
        are shape-determining by NAME — a recompile-storm call site
        must not pass the gate just by switching an argument to
        keyword form."""
        all_kws = frozenset(
            kw.arg for kw in call.keywords if kw.arg is not None)
        # direct jit-object call (or via module attr): static_argnums
        # slots are positional, static_argnames exempt keywords —
        # every OTHER keyword is a traced, shape-determining argument
        for name in _call_target_names(call):
            statics = self.jit_statics.get((src.relpath, name))
            if statics is not None:
                statnames = self.jit_static_names.get(
                    (src.relpath, name), ())
                n = len(call.args)
                return (tuple(i for i in range(n) if i not in statics),
                        all_kws - frozenset(statnames), name)
        # call-of-call through a jit factory
        if isinstance(call.func, ast.Call):
            inner = call.func
            hit = False
            for target in self.graph.resolve_call(inner, caller_info,
                                                  src):
                if target.key in self.factories:
                    hit = True
            for name in _call_target_names(inner):
                if (src.relpath, name) in self.factories:
                    hit = True
            if hit:
                return tuple(range(len(call.args))), all_kws, \
                    _call_target_names(inner)[0] \
                    if _call_target_names(inner) else "<factory>"
        # resolved call to a function with shape-determining params
        out: list[int] = []
        kws: set[str] = set()
        display = None
        for target in self.graph.resolve_call(call, caller_info, src):
            names = self.shape_params.get(target.key)
            if not names:
                continue
            display = target.name
            tparams = _param_names(target.node)
            offset = 1 if tparams[:1] in (["self"], ["cls"]) else 0
            for i, p in enumerate(tparams):
                if p in names and i - offset >= 0:
                    out.append(i - offset)
            for kw in call.keywords:
                if kw.arg in names:
                    kws.add(kw.arg)
        return tuple(sorted(set(out))), frozenset(kws), display

    def solve(self) -> None:
        changed = True
        while changed:
            changed = False
            for src in self.files:
                if src.tree is None:
                    continue
                aliases = self.idx.aliases[src.relpath]
                for qual, fn in self.idx.functions[src.relpath]:
                    info = self.graph.info_for_node(fn)
                    env = self._local_env(fn, src, info, aliases)
                    for node in self.idx.calls[id(fn)]:
                        positions, kw_names, _ = self.shape_positions(
                            node, src, info)
                        if not positions and not kw_names:
                            continue
                        for i, arg in enumerate(node.args):
                            if i not in positions:
                                continue
                            verdict, params = self.classify(
                                arg, src, fn, info, aliases, env)
                            if verdict != "param":
                                continue
                            key = (src.relpath, qual)
                            have = self.shape_params.setdefault(
                                key, set())
                            if not params <= have:
                                have |= params
                                changed = True
                        for kw in node.keywords:
                            if kw.arg not in kw_names:
                                continue
                            verdict, params = self.classify(
                                kw.value, src, fn, info, aliases, env)
                            if verdict != "param":
                                continue
                            key = (src.relpath, qual)
                            have = self.shape_params.setdefault(
                                key, set())
                            if not params <= have:
                                have |= params
                                changed = True


def _check_unladdered(idx: _Index, jits_by_file: dict) -> list[Finding]:
    ana = _LadderAnalysis(idx, jits_by_file)
    ana.solve()
    findings: list[Finding] = []
    graph = idx.graph
    for src in idx.files:
        if src.tree is None or not _in_ladder_scope(src.relpath):
            continue
        aliases = idx.aliases[src.relpath]
        module = src.relpath.rsplit("/", 1)[-1]
        for qual, fn in idx.functions[src.relpath]:
            info = graph.info_for_node(fn)
            env = ana._local_env(fn, src, info, aliases)
            for node in idx.calls[id(fn)]:
                positions, kw_names, display = ana.shape_positions(
                    node, src, info)
                if not positions and not kw_names:
                    continue
                entry = display or "<jit>"
                # (arg-expression, display slot) pairs: positional
                # indices and shape-determining keywords alike — a
                # raw shape must not pass just by switching the
                # argument to keyword form
                slots = [
                    (arg, str(i)) for i, arg in enumerate(node.args)
                    if i in positions
                ] + [
                    (kw.value, kw.arg) for kw in node.keywords
                    if kw.arg in kw_names
                ]
                for arg, slot in slots:
                    verdict, _p = ana.classify(
                        arg, src, fn, info, aliases, env)
                    if verdict != _RAW:
                        continue
                    key = f"{module}:{qual}:{entry}[{slot}]"
                    if (module, qual, f"{entry}[{slot}]") in \
                            LADDERED_CALLS:
                        continue
                    findings.append(Finding(
                        rule="unladdered-jit-shape",
                        path=src.relpath, line=arg.lineno,
                        message=(
                            f"argument {slot} of jit-dispatched "
                            f"{entry}() does not flow from the "
                            "BucketLadder (or a static_argnums "
                            "slot): every distinct shape here is a "
                            "20-40s XLA compile mid-serve — pack "
                            "through _pack_rows or a "
                            "BucketLadder bucket, or register a "
                            "reviewed exception in "
                            "shapecheck.LADDERED_CALLS"
                        ),
                        key=key,
                    ))
    return findings


# ===========================================================================
# rules: kernel-dtype-widen + shape-mismatch (jit-reachable bodies)


def _jit_reachable_functions(files: list[SourceFile],
                             graph: CallGraph):
    """(src, fn, aliases) for every function reachable from a jit
    root, local bare-name walk + cross-module graph edges (the
    jaxhazards recipe, shared)."""
    from .jaxhazards import _find_roots, _reachable

    seen: dict[int, tuple] = {}
    foreign: dict[int, object] = {}
    by_rel = {src.relpath: src for src in files}
    for src in files:
        if src.tree is None:
            continue
        aliases = import_aliases(src.tree, relative="skip")
        roots = _find_roots(src.tree, aliases)
        if not roots:
            continue
        local = _reachable(roots, src.tree)
        for fn in local:
            seen.setdefault(id(fn), (src, fn, aliases))
        for fn in local:
            caller = graph.info_for_node(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                for target in graph.resolve_call(node, caller, src):
                    if target.relpath != src.relpath:
                        foreign[id(target.node)] = target
    for info in graph.reachable(foreign.values()):
        src = by_rel.get(info.relpath)
        if src is None:
            continue
        aliases = import_aliases(src.tree, relative="skip")
        seen.setdefault(id(info.node), (src, info.node, aliases))
    return list(seen.values())


def _wide_dtype_of(node: ast.expr, aliases: dict,
                   builtins: bool = False) -> Optional[str]:
    """The 64-bit dtype a node denotes, if any. The bare ``int`` /
    ``float`` builtins only count in DTYPE POSITIONS (``astype(int)``,
    ``dtype=float``; ``builtins=True``) — a plain ``int(x)`` call is
    host-side scalar arithmetic, not an array widen."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value if node.value in WIDE_DTYPE_STRINGS else None
    if isinstance(node, ast.Name) and node.id in WIDE_BUILTINS:
        return node.id if builtins else None
    dotted = _dotted(node, aliases)
    if dotted is not None and \
            dotted.rsplit(".", 1)[-1] in WIDE_DTYPE_SUFFIXES:
        return dotted
    return None


def _qual_index(files: list[SourceFile]) -> dict[str, dict]:
    """relpath -> {id(fn-node): qualname}: the dtype/shape rules key
    findings on qualified names (same-named methods of two classes in
    one module must not collapse onto one dedup/allowlist key)."""
    out: dict[str, dict] = {}
    for src in files:
        if src.tree is None:
            continue
        out[src.relpath] = {
            id(fn): qual for qual, fn in _functions(src.tree)
        }
    return out


def _check_dtype_widen(reachable: list, quals: dict) -> list[Finding]:
    findings: list[Finding] = []
    emitted: set = set()
    for src, fn, aliases in reachable:
        module = src.relpath.rsplit("/", 1)[-1]
        qual = quals.get(src.relpath, {}).get(id(fn), fn.name)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            hits: list[str] = []
            # jnp.int64(x) / np.float64(x) cast-call forms (NOT the
            # bare int()/float() builtins — those are host scalars)
            wide = _wide_dtype_of(node.func, aliases)
            if wide is not None:
                hits.append(wide)
            # x.astype(<wide>) and dtype=<wide> keyword/positional
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "astype" and node.args:
                wide = _wide_dtype_of(node.args[0], aliases,
                                      builtins=True)
                if wide is not None:
                    hits.append(wide)
            for kw in node.keywords:
                if kw.arg == "dtype":
                    wide = _wide_dtype_of(kw.value, aliases,
                                          builtins=True)
                    if wide is not None:
                        hits.append(wide)
            for wide in hits:
                short = wide.rsplit(".", 1)[-1]
                key = f"{module}:{qual}:{short}"
                if key in emitted:
                    continue
                emitted.add(key)
                findings.append(Finding(
                    rule="kernel-dtype-widen",
                    path=src.relpath, line=node.lineno,
                    message=(
                        f"64-bit dtype {wide} inside jit-reachable "
                        f"{qual}(): a widened table field doubles "
                        "HBM traffic for every dispatch that touches "
                        "it (and silently upcasts whatever mixes "
                        "with it) — keep kernel state int32/float32"
                    ),
                    key=key,
                ))
    return findings


# -- shape-mismatch ---------------------------------------------------------

_SHAPE_CTORS = ("zeros", "ones", "full", "empty")


def _dim_desc(node: ast.expr):
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return ("lit", node.value)
    return ("sym", ast.dump(node))


def _shape_of_call(call: ast.Call, aliases: dict) -> Optional[tuple]:
    dotted = _dotted(call.func, aliases)
    if dotted is None:
        return None
    leaf = dotted.rsplit(".", 1)[-1]
    shape_arg = None
    if leaf in _SHAPE_CTORS and call.args:
        shape_arg = call.args[0]
    elif leaf == "broadcasted_iota" and len(call.args) >= 2:
        shape_arg = call.args[1]
    elif leaf == "arange" and call.args:
        return (_dim_desc(call.args[0]),)
    if shape_arg is None:
        return None
    if isinstance(shape_arg, (ast.Tuple, ast.List)):
        return tuple(_dim_desc(e) for e in shape_arg.elts)
    return (_dim_desc(shape_arg),)


def _lit_conflict(a, b) -> bool:
    return a[0] == "lit" and b[0] == "lit" and a[1] != b[1]


def _check_shape_mismatch(reachable: list,
                          quals: dict) -> list[Finding]:
    findings: list[Finding] = []
    for src, fn, aliases in reachable:
        module = src.relpath.rsplit("/", 1)[-1]
        qual = quals.get(src.relpath, {}).get(id(fn), fn.name)
        env: dict[str, tuple] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                shape = _shape_of_call(node.value, aliases)
                if shape is not None:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            env[tgt.id] = shape

        def known(e: ast.expr) -> Optional[tuple]:
            if isinstance(e, ast.Call):
                return _shape_of_call(e, aliases)
            if isinstance(e, ast.Name):
                return env.get(e.id)
            return None

        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func, aliases)
            leaf = dotted.rsplit(".", 1)[-1] if dotted else None
            if leaf in ("concatenate", "stack") and node.args and \
                    isinstance(node.args[0], (ast.Tuple, ast.List)):
                # axis arrives by keyword OR positionally
                # (jnp.concatenate(ops, 1)); a non-literal axis means
                # we cannot know which dim the concat exempts, so the
                # per-axis comparison is skipped (rank check stands)
                axis_expr = next(
                    (k.value for k in node.keywords
                     if k.arg == "axis"),
                    node.args[1] if len(node.args) > 1 else None)
                axis = 0 if axis_expr is None else _literal(axis_expr)
                shapes = [(e, known(e)) for e in node.args[0].elts]
                shapes = [(e, s) for e, s in shapes if s is not None]
                for (e1, s1), (e2, s2) in zip(shapes, shapes[1:]):
                    if len(s1) != len(s2):
                        findings.append(Finding(
                            rule="shape-mismatch",
                            path=src.relpath, line=node.lineno,
                            message=(
                                f"{leaf}() operands have rank "
                                f"{len(s1)} vs {len(s2)}: inferred "
                                "operand shapes disagree"
                            ),
                            key=(f"{module}:{qual}:{leaf}:"
                                 f"rank{len(s1)}v{len(s2)}"),
                        ))
                        break
                    if leaf == "concatenate" and \
                            not isinstance(axis, int):
                        continue
                    norm = axis % max(len(s1), 1) \
                        if s1 and isinstance(axis, int) else 0
                    for d, (da, db) in enumerate(zip(s1, s2)):
                        if leaf == "concatenate" and d == norm:
                            continue
                        if _lit_conflict(da, db):
                            findings.append(Finding(
                                rule="shape-mismatch",
                                path=src.relpath, line=node.lineno,
                                message=(
                                    f"{leaf}() operands disagree on "
                                    f"axis {d}: {da[1]} vs {db[1]} "
                                    "(inferred from their "
                                    "constructors)"
                                ),
                                key=(f"{module}:{qual}:{leaf}:"
                                     f"ax{d}:{da[1]}v{db[1]}"),
                            ))
                            break
            elif leaf == "where" and len(node.args) == 3:
                s2, s3 = known(node.args[1]), known(node.args[2])
                if s2 is None or s3 is None:
                    continue
                # broadcast: align trailing dims; lits conflict when
                # different and neither is 1
                for off in range(1, min(len(s2), len(s3)) + 1):
                    da, db = s2[-off], s3[-off]
                    if _lit_conflict(da, db) and \
                            1 not in (da[1], db[1]):
                        findings.append(Finding(
                            rule="shape-mismatch",
                            path=src.relpath, line=node.lineno,
                            message=(
                                "where() branches do not broadcast: "
                                f"trailing axis -{off} is {da[1]} vs "
                                f"{db[1]}"
                            ),
                            key=(f"{module}:{qual}:where:"
                                 f"{da[1]}v{db[1]}"),
                        ))
                        break
    return findings


# ===========================================================================
# rule: prewarm-coverage


def _reachable_jit_entries(files: list[SourceFile], graph: CallGraph,
                           jits_by_file: dict,
                           roots_registry: dict,
                           indirect: dict) -> set[tuple]:
    """(relpath, jit-name) entries whose compile a path from the
    registry roots can trigger. Traversal: the shared call graph,
    plus calls to jit-object names (edge to the jit AND into its
    wrapped function), plus declared indirect edges."""
    by_rel = {src.relpath: src for src in files}
    # qualname index for roots/indirect targets
    fn_index: dict[tuple, object] = {}
    for info in graph.functions():
        fn_index[(info.relpath, info.qualname)] = info

    def lookup(suffix: str, qual: str):
        for (rel, q), info in fn_index.items():
            if q == qual and rel.endswith(suffix):
                yield info

    queue = []
    for suffix, quals in roots_registry.items():
        for qual in quals:
            queue.extend(lookup(suffix, qual))
    # name -> jit maps, built ONCE per traversal (not per visited
    # function — the BFS below touches these for every popped node)
    local_jits_by_rel = {
        rel: {j.name: j for j in jits}
        for rel, jits in jits_by_file.items()
    }
    imported: dict[str, list] = {}
    for jits in jits_by_file.values():
        for j in jits:
            imported.setdefault(j.name, []).append(j)
    entries: set[tuple] = set()
    seen: set[int] = set()
    while queue:
        info = queue.pop()
        if id(info.node) in seen:
            continue
        seen.add(id(info.node))
        src = by_rel.get(info.relpath)
        # resolved call-graph edges
        queue.extend(graph.callees(info))
        # declared indirect edges
        for (suffix, qual), targets in indirect.items():
            if info.relpath.endswith(suffix) and \
                    info.qualname == qual:
                for (tsuffix, tqual) in targets:
                    queue.extend(lookup(tsuffix, tqual))
        # jit-object call edges (by bare or module-attr name, local or
        # imported)
        if src is None:
            continue
        local_jits = local_jits_by_rel.get(info.relpath, {})
        for node in ast.walk(info.node):
            if not isinstance(node, ast.Call):
                continue
            for name in _call_target_names(node):
                jit = local_jits.get(name)
                cands = [jit] if jit else imported.get(name, [])
                for j in cands:
                    if j is None:
                        continue
                    if j.scope is not None and j.scope != \
                            info.qualname:
                        # factory-cached jits reach through their
                        # factory call, handled below
                        continue
                    entries.add((j.relpath, j.name))
                    _enter_wrapped(j, by_rel, graph, queue)
            # factory call (direct or call-of-call): entering the
            # factory function marks its nested jits
        # a function that IS a jit factory contributes its entries
        for j in jits_by_file.get(info.relpath, ()):
            if j.scope == info.qualname:
                entries.add((j.relpath, j.name))
                _enter_wrapped(j, by_rel, graph, queue)
    return entries


def _enter_wrapped(jit: JitObject, by_rel: dict, graph: CallGraph,
                   queue: list) -> None:
    src = by_rel.get(jit.relpath)
    if src is None or src.tree is None:
        return
    names = ([jit.wrapped] if jit.wrapped else []) + \
        list(jit.lambda_callees)
    for qual, fn in _functions(src.tree):
        if fn.name in names:
            info = graph.info_for_node(fn)
            if info is not None:
                queue.append(info)


def _check_prewarm_coverage(files: list[SourceFile], graph: CallGraph,
                            jits_by_file: dict) -> list[Finding]:
    # only run when a registered dispatch-root module is in the scan
    has_roots = any(
        src.relpath.endswith(suffix)
        for src in files for suffix in DISPATCH_ROOTS
    )
    if not has_roots:
        return []
    dispatch = _reachable_jit_entries(
        files, graph, jits_by_file, DISPATCH_ROOTS, PREWARM_INDIRECT)
    warmed = _reachable_jit_entries(
        files, graph, jits_by_file, PREWARM_ROOTS, PREWARM_INDIRECT)
    findings: list[Finding] = []
    for relpath, name in sorted(dispatch - warmed):
        module = relpath.rsplit("/", 1)[-1]
        line = next(
            (j.line for j in jits_by_file.get(relpath, ())
             if j.name == name), 1)
        findings.append(Finding(
            rule="prewarm-coverage",
            path=relpath, line=line,
            message=(
                f"jit root {name!r} is reachable from the sidecar "
                "dispatch loop but NOT from BucketLadder prewarm: "
                "its first dispatch pays a mid-serve XLA compile "
                "(20-40s on the real chip) — walk it in prewarm or "
                "route it through an already-warmed entry"
            ),
            key=f"{module}:{name}",
        ))
    return findings


# ===========================================================================
# the pure-python derivations the jitsan differentials pin
# (NO jax imports — (shape, dtype) descriptors only)


def _pow2_span(lo: int, hi: int) -> int:
    """How many doubling steps lie in [lo, hi] (inclusive), i.e. the
    rung count of a pow2 ladder — the same arithmetic BucketLadder
    enumerates, kept import-free here and cross-checked by
    tests/test_jitsan.py against the real enumeration."""
    if lo <= 0:
        # 0 never doubles past hi: the loop below would spin forever
        raise ValueError(f"pow2 ladder needs a positive floor: {lo}")
    n = 0
    v = lo
    while v <= hi:
        n += 1
        v *= 2
    return max(n, 1)


def ladder_bounds(window_floor: int, max_bucket: int,
                  capacity: int, max_capacity: int,
                  executor: str = "scan",
                  donate: bool = False,
                  pool_capacity: Optional[int] = None,
                  pool_rows: int = 1) -> dict[str, int]:
    """Static per-root compile-count bounds for a sidecar configured
    with this ladder: the number of distinct (window-bucket,
    capacity-rung) shapes each jit root can legally see when every
    dispatch rides the ladder. jitsan's observed signature counts
    must stay <= these — more means an unladdered call site
    compiled a shape the ladder does not contain (the recompile
    storm this family exists to stop)."""
    n_buckets = _pow2_span(window_floor, max_bucket)
    n_rungs = _pow2_span(capacity, max_capacity)
    shapes = n_buckets * n_rungs
    bounds = {
        # one program per (window bucket x capacity rung)
        "apply_window": shapes,
        "apply_window_pingpong": shapes if donate else 0,
        "egwalker": shapes,
        "egwalker_pingpong": shapes if donate else 0,
        "egwalker_suffix": shapes,
        # one per capacity rung
        "compact": n_rungs,
        # one per rung TRANSITION
        "pad_capacity": max(n_rungs - 1, 0),
    }
    if executor == "scan":
        bounds["egwalker"] = 0
        bounds["egwalker_pingpong"] = 0
        bounds["egwalker_suffix"] = 0
    else:
        # the walker covers critical prefixes; concurrent SUFFIXES
        # dispatch the route's own plain scan jit per rung x bucket
        # (never a donating form — the suffix input is the walker
        # stage's live output), and prewarm walks both programs; the
        # primary window never reaches apply_window
        bounds["apply_window"] = 0
        bounds["apply_window_pingpong"] = 0
    if pool_capacity is not None:
        # MeshShardedPool jit roots (per-shard ladder x sharding
        # signatures): ``pool_rows`` is the largest per-shard row
        # bucket the run may reach, so the doc-shape ladder is the
        # pow2 span 1..pool_rows. Window buckets are the sidecar
        # ladder's span (pool tails come from the same serving
        # windows) plus the replay chunk bucket when it lies outside
        # it. Every shape compiles at most TWICE: once with fresh
        # NamedSharding placement (a rebuild's make_table) and once
        # with the committed sharding a pool-dispatch output carries
        # — the two input-sharding signatures prewarm walks.
        chunk = max(16, min(256, pool_capacity // 4))
        rb = _pow2_span(1, max(pool_rows, 1))
        n_windows = _pow2_span(window_floor, max_bucket)
        if not (window_floor <= chunk <= max_bucket):
            n_windows += 1
        bounds["mesh_pool"] = rb * n_windows * 2
        # on a one-device mesh BOTH pool tiers apply the plain scan
        # jit, whatever the sidecar's route: those programs ride the
        # shared apply_window cache at the pool's own (row bucket x
        # window/replay-chunk x sharding signature) shapes, ON TOP of
        # whatever the primary route compiles there
        bounds["apply_window"] += rb * n_windows * 2
        # one gather program per pool table shape (x2 sharding sigs).
        # The migration handoff ALWAYS donates on backends that
        # support it (shard_moves.migrate_rows routes on the backend,
        # NOT on the sidecar donate flag — the handoff contract is
        # unconditional), so the donating form's bound must hold
        # regardless of `donate`: on CPU it stays cold (observed 0 <=
        # bound), on TPU it is the form every migration compiles
        bounds["mesh_move"] = rb * 2
        bounds["mesh_move_pingpong"] = rb * 2
        # compact follows every pool dispatch: one extra signature
        # per pool table shape rides the shared compact root
        bounds["compact"] += rb * 2
    return bounds


def infer_kernel_output(root: str, spec: dict,
                        new_capacity: Optional[int] = None) -> dict:
    """Abstract output signature of one kernel root.

    ``spec`` maps field name -> (shape tuple, dtype string) for the
    root's table/state input; the return value is the same structure
    for its output. The merge kernels are SHAPE- AND DTYPE-PRESERVING
    maps over the table by contract — the one exception is
    ``pad_capacity``, which widens the slot axis (axis 1) to
    ``new_capacity``. tests/test_jitsan.py asserts this against
    ``jax.eval_shape`` across every ladder rung, so an executor that
    silently stops preserving a shape or widens a dtype fails there
    BY NAME."""
    identity_roots = {
        "apply_window", "apply_window_pingpong", "egwalker",
        "egwalker_pingpong", "egwalker_suffix", "compact", "seq_shard",
    }
    if root in identity_roots:
        return {f: (tuple(shape), dtype)
                for f, (shape, dtype) in spec.items()}
    if root == "pad_capacity":
        if new_capacity is None:
            raise ValueError("pad_capacity needs new_capacity")
        old = spec["length"][0][1]
        out = {}
        for f, (shape, dtype) in spec.items():
            shape = tuple(shape)
            if len(shape) >= 2 and shape[1] == old:
                shape = shape[:1] + (new_capacity,) + shape[2:]
            out[f] = (shape, dtype)
        return out
    raise ValueError(f"unknown kernel root {root!r}")


# ===========================================================================
# entry point


def check(files: list[SourceFile], graph=None) -> list[Finding]:
    graph = graph or build_callgraph(files)
    idx = _Index(files, graph)
    jits_by_file: dict[str, list[JitObject]] = {}
    for src in files:
        if src.tree is None:
            continue
        aliases = import_aliases(src.tree, relative="skip")
        jits = collect_jit_objects(src, aliases)
        if jits:
            jits_by_file[src.relpath] = jits
    # one jit-reachability sweep shared by the dtype and shape rules
    reach = _jit_reachable_functions(files, graph)
    quals = _qual_index(files)
    findings = []
    findings += _check_donated(idx, jits_by_file)
    findings += _check_unladdered(idx, jits_by_file)
    findings += _check_dtype_widen(reach, quals)
    findings += _check_shape_mismatch(reach, quals)
    findings += _check_prewarm_coverage(files, graph, jits_by_file)
    return findings
