"""jitsan — a runtime compile-count & donation sanitizer for the
kernel layer.

The dynamic half of the shapecheck static pass
(analysis/shapecheck.py), mirroring the concheck<->fluidsan pattern:
the static analyzer proves properties about shapes it never runs,
jitsan observes the shapes that actually run, and two differential
tests pin them to each other (tests/test_jitsan.py) so an
abstract-interpreter gap fails BY NAME instead of rotting silently:

- **compile counts**: every jit root in ``ops/merge_kernel.py``,
  ``ops/event_graph.py``, ``ops/shard_moves.py``, ``ops/tree_apply.py``
  and ``parallel/`` caches one executable per input
  signature; jitsan reads those caches (``_cache_size()`` — the
  number of distinct signatures XLA actually compiled) per ROOT.
  Differential (a): observed counts must stay <= the per-root bounds
  ``shapecheck.ladder_bounds`` derives from the BucketLadder — one
  extra means an unladdered call site compiled a shape the ladder
  does not contain (the recompile storm ``unladdered-jit-shape``
  exists to stop).
- **donation traps**: the ping-pong dispatch wrappers
  (``apply_window_pingpong`` / ``apply_window_egwalker_pingpong``)
  consume their ``dead`` argument — reading it afterwards is the
  ``donated-buffer-reuse`` invariant. On TPU, XLA enforces this by
  reusing the buffers (garbage reads, silently). On CPU, donation is
  IGNORED, so a violation passes every test and detonates on the
  real chip. jitsan closes that gap: after a donating dispatch it
  ``delete()``s the donated arrays, so any read on any backend
  raises ``RuntimeError: Array has been deleted`` at the exact read
  site. A donated array that is ALSO a live argument of the same
  dispatch (the aliasing bug XLA cannot survive) records a trip
  instead — the conftest guard fails the test that caused it.

Enable for a test session with ``FFTPU_SANITIZE=1`` (the same
conftest guard that installs fluidsan) or per-test via
``install()``/``uninstall()``.

The ``jax_compiles_total{root}`` registry counter is fed from here in
BOTH modes: installed, every ``publish_compiles()`` call advances it
from the live cache watermarks; uninstalled, the same call is a cheap
cache-size probe any harness can take.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import sys
import threading
from typing import Optional

from ..obs import metrics as obs_metrics

_M_COMPILES = obs_metrics.REGISTRY.counter(
    "jax_compiles_total",
    "XLA compilations per kernel jit root (distinct input "
    "signatures entering the root's jit cache)",
    labelnames=("root",),
)

_LOCK = threading.Lock()

# ---------------------------------------------------------------------------
# root registry: where each jit root's compilation cache lives.
# Names match shapecheck.ladder_bounds keys (plus "seq_shard", whose
# per-mesh programs the ladder does not bound — the pool replays
# history, it does not serve steady windows).

# module-scope jit objects: root -> (module, attribute)
_JIT_ATTRS = {
    "apply_window": (
        "fluidframework_tpu.ops.merge_kernel", "_apply_window_xla"),
    "apply_window_pingpong": (
        "fluidframework_tpu.ops.merge_kernel", "_apply_window_pingpong"),
    "pad_capacity": (
        "fluidframework_tpu.ops.merge_kernel", "pad_capacity"),
    "compact": (
        "fluidframework_tpu.ops.merge_kernel", "compact"),
    # the egwalker route's concurrent-suffix scan (one packed buffer)
    "egwalker_suffix": (
        "fluidframework_tpu.ops.event_graph", "_jit_suffix"),
    # the mesh pool's migration gather (ops/shard_moves.py): plain
    # form (CPU / prewarm) and the donating handoff form (TPU)
    "mesh_move": (
        "fluidframework_tpu.ops.shard_moves", "_take_rows_jit"),
    "mesh_move_pingpong": (
        "fluidframework_tpu.ops.shard_moves", "_migrate_rows_donating"),
    # the tree serving plane's capacity-ladder pad step
    "tree_pad": (
        "fluidframework_tpu.ops.tree_apply", "pad_tree_capacity"),
}

# factory caches of jit objects (dict -> jit): root -> (module, attr)
_JIT_CACHES = {
    "egwalker": (
        "fluidframework_tpu.ops.event_graph", "_jit_cache"),
    "egwalker_pingpong": (
        "fluidframework_tpu.ops.event_graph", "_jit_pingpong_cache"),
    "seq_shard": (
        "fluidframework_tpu.parallel.seq_shard", "_compiled_cache"),
    "mesh_pool": (
        "fluidframework_tpu.parallel.mesh_pool", "_compiled_cache"),
    # the tree serving plane's window root (both tree routes share
    # one route-keyed cache of jitted window programs)
    "tree_window": (
        "fluidframework_tpu.ops.tree_apply", "_jit_cache"),
}

ROOTS = tuple(sorted((*_JIT_ATTRS, *_JIT_CACHES)))

# donating entry points to wrap: (module, attribute, root). Position 0
# is the donated argument in both (jax donation is positional).
_DONATING_WRAPPERS = (
    ("fluidframework_tpu.ops.merge_kernel",
     "apply_window_pingpong", "apply_window_pingpong"),
    ("fluidframework_tpu.ops.event_graph",
     "apply_window_egwalker_pingpong", "egwalker_pingpong"),
    # the migration handoff consumes its SOURCE table (position 0) —
    # the pool must never read the pre-move table after the gather
    ("fluidframework_tpu.ops.shard_moves",
     "migrate_rows", "mesh_move_pingpong"),
)


@dataclasses.dataclass
class DonationEvent:
    """One donating dispatch jitsan consumed: ``deleted`` arrays are
    now read-traps."""

    root: str
    deleted: int


@dataclasses.dataclass
class Trip:
    """A donated value that was ALSO a live input of the same
    dispatch: XLA may back the output with buffers the kernel still
    reads — the immediate aliasing form of donated-buffer-reuse."""

    root: str
    description: str

    def describe(self) -> str:
        return (
            f"jitsan: donated argument of {self.root} aliases a live "
            f"input of the same dispatch ({self.description}) — XLA "
            "may reuse its buffers for the output while the kernel "
            "still reads them"
        )


class _State:
    def __init__(self) -> None:
        self.installed = 0
        self.baseline: dict[str, int] = {}
        self.published: dict[str, int] = {}
        self.donations: list[DonationEvent] = []
        self.trips: list[Trip] = []
        self.originals: list[tuple] = []  # (module, attr, original)


_STATE = _State()


# ---------------------------------------------------------------------------
# compile counting (cache-size reads; no call interception needed)


def _cache_size(jitted) -> int:
    probe = getattr(jitted, "_cache_size", None)
    if probe is None:  # pragma: no cover - future jax surface change
        return 0
    return int(probe())


def probe_cache_sizes() -> dict[str, int]:
    """Absolute compiled-signature counts per root, read from the jit
    caches of modules ALREADY imported (``sys.modules`` lookups only
    — the probe never imports kernel code, so a stage that never
    touched the device pays nothing for it). Roots whose module is
    not loaded report 0."""
    out: dict[str, int] = {}
    for root, (mod_name, attr) in _JIT_ATTRS.items():
        mod = sys.modules.get(mod_name)
        obj = getattr(mod, attr, None) if mod else None
        # the donation wrapper may sit over the original jit
        obj = getattr(obj, "__jitsan_wrapped__", obj)
        out[root] = _cache_size(obj) if obj is not None else 0
    for root, (mod_name, attr) in _JIT_CACHES.items():
        mod = sys.modules.get(mod_name)
        cache = getattr(mod, attr, None) if mod else None
        out[root] = sum(
            _cache_size(v) for v in cache.values()
        ) if cache else 0
    return out


def compile_counts() -> dict[str, int]:
    """Compilations observed per root since ``install()``/``reset()``
    — current cache sizes minus the install-time baseline (jit caches
    only grow, so the delta is exactly the signatures compiled in the
    window)."""
    sizes = probe_cache_sizes()
    with _LOCK:
        base = dict(_STATE.baseline)
    return {
        root: max(0, n - base.get(root, 0))
        for root, n in sizes.items()
    }


def publish_compiles() -> dict[str, int]:
    """Advance ``jax_compiles_total{root}`` to the current absolute
    cache sizes (monotone per-root watermarks, so repeated calls
    never double-count) and return the sizes. This is the ONE feed
    for both modes: jitsan-active sessions call it after driving
    traffic, a harness calls it as the cheap probe."""
    sizes = probe_cache_sizes()
    with _LOCK:
        published = _STATE.published
        deltas = {
            root: n - published.get(root, 0)
            for root, n in sizes.items()
            if n > published.get(root, 0)
        }
        published.update(
            {root: sizes[root] for root in deltas}
        )
    for root, delta in deltas.items():
        _M_COMPILES.labels(root=root).inc(delta)
    return sizes


# ---------------------------------------------------------------------------
# donation traps


def _array_leaves(tree) -> list:
    import jax

    return [
        leaf for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)
    ]


def _trap_donated(root: str, donated, live_args) -> None:
    donated_leaves = _array_leaves(donated)
    live_ids = {
        id(leaf) for arg in live_args
        for leaf in _array_leaves(arg)
    }
    deleted = 0
    trips: list[Trip] = []
    for leaf in donated_leaves:
        if id(leaf) in live_ids:
            trips.append(Trip(
                root=root,
                description=(
                    f"shape {tuple(leaf.shape)} dtype {leaf.dtype}"
                ),
            ))
            continue  # deleting it would corrupt the live input too
        if not leaf.is_deleted():
            # emulate XLA's donation on every backend: the buffer is
            # consumed, any later read raises at the read site
            leaf.delete()
            deleted += 1
    with _LOCK:
        _STATE.trips.extend(trips)
        if deleted or trips:
            _STATE.donations.append(DonationEvent(root, deleted))
    for trip in trips:
        print(f"jitsan: {trip.describe()}", file=sys.stderr,
              flush=True)


def _wrap_donating(fn, root: str):
    @functools.wraps(fn)
    def run(*args, **kwargs):
        out = fn(*args, **kwargs)
        # position 0 is the donated slot in both wrappers; None means
        # the caller opted into the plain (non-donating) fallback
        dead = args[0] if args else kwargs.get("dead")
        if dead is not None:
            # live inputs arrive positionally OR by keyword — missing
            # the keyword ones would delete() a live-aliased buffer
            # instead of recording the aliasing trip
            live = args[1:] + tuple(
                v for k, v in kwargs.items() if k != "dead")
            _trap_donated(root, dead, live)
        return out

    run.__jitsan_wrapped__ = fn
    return run


def _patch_everywhere(mod_name: str, attr: str, wrapper) -> None:
    """Replace ``mod_name.attr`` AND every same-object re-import of
    it across loaded modules (``from ..ops.merge_kernel import
    apply_window_pingpong`` holds the function by value — patching
    only the defining module would miss the sidecar's copy)."""
    defining = sys.modules[mod_name]
    original = getattr(defining, attr)
    for mod in list(sys.modules.values()):
        if mod is None or not getattr(mod, "__name__", "").startswith(
                "fluidframework_tpu"):
            continue
        if getattr(mod, attr, None) is original:
            setattr(mod, attr, wrapper)
            with _LOCK:
                _STATE.originals.append((mod, attr, original))


# ---------------------------------------------------------------------------
# lifecycle


def install() -> None:
    """Arm the sanitizer: import the kernel modules, baseline their
    compile caches, and wrap the donating entry points. Refcounted
    like fluidsan (nested install/uninstall pairs are safe)."""
    with _LOCK:
        _STATE.installed += 1
        if _STATE.installed > 1:
            return
    for mod_name in sorted({
        m for m, _ in _JIT_ATTRS.values()
    } | {m for m, _ in _JIT_CACHES.values()}):
        importlib.import_module(mod_name)
    for mod_name, attr, root in _DONATING_WRAPPERS:
        fn = getattr(sys.modules[mod_name], attr)
        _patch_everywhere(mod_name, attr, _wrap_donating(fn, root))
    reset()


def uninstall() -> None:
    with _LOCK:
        if _STATE.installed == 0:
            return
        _STATE.installed -= 1
        if _STATE.installed:
            return
        originals = list(_STATE.originals)
        _STATE.originals.clear()
    for mod, attr, original in originals:
        setattr(mod, attr, original)
    # a module first-imported AFTER install() bound the WRAPPER
    # (``from ..ops.merge_kernel import apply_window_pingpong`` holds
    # by value) and was never recorded above — sweep for copies or
    # its dispatches keep delete()ing donated tables with the
    # sanitizer nominally off
    by_attr = {attr: original for _, attr, original in originals}
    for mod in list(sys.modules.values()):
        if mod is None or not getattr(mod, "__name__", "").startswith(
                "fluidframework_tpu"):
            continue
        for attr, original in by_attr.items():
            cur = getattr(mod, attr, None)
            if cur is not None and \
                    getattr(cur, "__jitsan_wrapped__", None) \
                    is original:
                setattr(mod, attr, original)


def installed() -> bool:
    return _STATE.installed > 0


def reset() -> None:
    """Re-baseline compile counts and drop recorded donation
    events/trips (already-deleted buffers stay deleted — they are
    live traps, not history)."""
    sizes = probe_cache_sizes()
    with _LOCK:
        _STATE.baseline = dict(sizes)
        _STATE.donations.clear()
        _STATE.trips.clear()


def trips() -> list[Trip]:
    with _LOCK:
        return list(_STATE.trips)


def donation_events() -> list[DonationEvent]:
    with _LOCK:
        return list(_STATE.donations)
