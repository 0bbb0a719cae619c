"""Continuous profiling: an always-on sampling host profiler plus
opt-in device-trace hooks.

The host half is a classic wall-clock thread sampler: a daemon
thread wakes every ``interval_s`` (default 10ms), snapshots
``sys._current_frames()``, and attributes each thread's top-of-stack
frame to a COMPONENT derived from the thread's name — the serving
plane already names its threads (``socket-recv-*`` /
``socket-dispatch-*`` pumps, the ``ingress-loop`` event loop, the
``serve-bench`` harness driver), so "where is the process spending
its time, per component" costs one dict walk per sample and no
instrumentation on any hot path. Aggregates ride the metrics
registry (``profiler_samples_total{component}``,
``profiler_overhead_pct``); the newest samples sit in a bounded ring
for full dumps, which the SLO engine triggers automatically on a
breach (``SloEngine.add_dump_target``).

Overhead is measured, not asserted: the sampler accounts every
second it spends sampling against the wall clock it ran for
(:attr:`ContinuousProfiler.overhead_fraction`), and the serving
harness (tools/serve_bench.py / bench config9) pins the end-to-end
cost under 2% by timing the same run with the profiler on and off.

The device half is opt-in (:func:`set_device_trace` or
``FFTPU_DEVICE_TRACE=1``): :func:`device_trace` wraps a round stage of
the sidecar in a ``jax.profiler.TraceAnnotation`` so a profiler trace
shows serving rounds by stage and round on the device planes' clock.
Whoever starts the trace (``jax.profiler.start_trace``) owns it; the
hooks only annotate. Switched off they import nothing — profiling must
never add a host<->device sync or an import tax to the dispatch loop.
"""
from __future__ import annotations

import functools
import os
import sys
import threading
import time
from collections import Counter, deque
from contextlib import contextmanager
from typing import IO, Optional, Sequence

from . import metrics as obs_metrics

_M_SAMPLES = obs_metrics.REGISTRY.counter(
    "profiler_samples_total",
    "host profiler stack samples per component",
    labelnames=("component",))
_M_OVERHEAD = obs_metrics.REGISTRY.gauge(
    "profiler_overhead_pct",
    "measured sampler overhead (time sampling / wall), percent")

# thread-name prefix -> component. First match wins; names are
# code-chosen (docs/OBSERVABILITY.md) so the label set stays bounded.
DEFAULT_COMPONENTS = (
    ("socket-recv", "driver-recv"),
    ("socket-dispatch", "driver-dispatch"),
    ("ingress-loop", "ingress"),
    ("serve-bench", "harness"),
    ("obs-profiler", "profiler"),
    ("MainThread", "main"),
)


def component_of(thread_name: str,
                 components: Sequence[tuple] = DEFAULT_COMPONENTS
                 ) -> str:
    for prefix, component in components:
        if thread_name.startswith(prefix):
            return component
    return "other"


class ContinuousProfiler:
    """The sampling host profiler. ``start()``/``stop()`` or use as a
    context manager; safe to leave always-on."""

    def __init__(self, interval_s: float = 0.01,
                 capacity: int = 8192,
                 components: Sequence[tuple] = DEFAULT_COMPONENTS,
                 name: str = "host"):
        if interval_s <= 0:
            raise ValueError("interval_s must be > 0")
        self.interval_s = interval_s
        self.components = tuple(components)
        self.name = name
        # newest samples, oldest dropped: (t, component, frame_key)
        self._ring: deque = deque(maxlen=capacity)
        self._counts: Counter = Counter()  # (component, frame_key)
        # registry flush bookkeeping: samples are counted locally in
        # the sampling loop and flushed to profiler_samples_total in
        # batches (stop()/summary()), NEVER per sample — a
        # per-sample inc would contend on the process-wide metrics
        # lock with the very serving threads being profiled, and the
        # contention would show up as profiler overhead
        self._flushed: Counter = Counter()
        self._lock = threading.Lock()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.samples = 0
        self._sampling_s = 0.0   # time spent inside _sample_once
        self._started_at: Optional[float] = None
        self._wall_s = 0.0       # accumulated across start/stop spans

    # ------------------------------------------------------------------

    def start(self) -> "ContinuousProfiler":
        if self._thread is not None:
            return self
        self._stop_evt.clear()
        self._started_at = time.perf_counter()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"obs-profiler-{self.name}",
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop_evt.set()
        self._thread.join(timeout=5.0)
        self._thread = None
        if self._started_at is not None:
            self._wall_s += time.perf_counter() - self._started_at
            self._started_at = None
        self._flush_registry()
        _M_OVERHEAD.set(round(100.0 * self.overhead_fraction, 4))

    def __enter__(self) -> "ContinuousProfiler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    @property
    def running(self) -> bool:
        return self._thread is not None

    # ------------------------------------------------------------------

    def _loop(self) -> None:
        me = threading.get_ident()
        while not self._stop_evt.wait(self.interval_s):
            self._sample_once(skip_ident=me)

    def _sample_once(self, skip_ident: Optional[int] = None) -> None:
        t0 = time.perf_counter()
        names = {t.ident: t.name for t in threading.enumerate()}
        frames = sys._current_frames()
        now = time.time()
        with self._lock:
            self.samples += 1
            for ident, frame in frames.items():
                if ident == skip_ident:
                    continue
                component = component_of(
                    names.get(ident, "?"), self.components
                )
                code = frame.f_code
                key = (
                    f"{code.co_name} "
                    f"({os.path.basename(code.co_filename)}:"
                    f"{frame.f_lineno})"
                )
                self._counts[(component, key)] += 1
                self._ring.append((now, component, key))
        self._sampling_s += time.perf_counter() - t0

    def _flush_registry(self) -> None:
        """Push the locally-accumulated per-component sample counts
        into ``profiler_samples_total`` (delta against what was
        already flushed). Called from the batch entry points, off
        the sampling loop."""
        current = self.by_component()
        for component, count in current.items():
            delta = count - self._flushed[component]
            if delta > 0:
                self._flushed[component] = count
                _M_SAMPLES.labels(component=component).inc(delta)

    # ------------------------------------------------------------------

    @property
    def overhead_fraction(self) -> float:
        """Time spent sampling / wall time profiled (own-cost only;
        the end-to-end figure — including scheduler noise from the
        extra thread — is what serve_bench measures on/off)."""
        wall = self._wall_s
        if self._started_at is not None:
            wall += time.perf_counter() - self._started_at
        return self._sampling_s / wall if wall > 0 else 0.0

    def top(self, n: int = 10,
            component: Optional[str] = None) -> list[dict]:
        """Top-of-stack aggregate, most-sampled first."""
        with self._lock:
            items = list(self._counts.items())
        if component is not None:
            items = [it for it in items if it[0][0] == component]
        items.sort(key=lambda it: (-it[1], it[0]))
        return [
            {"component": comp, "frame": key, "samples": count}
            for (comp, key), count in items[:n]
        ]

    def by_component(self) -> dict[str, int]:
        with self._lock:
            out: dict[str, int] = {}
            for (comp, _key), count in self._counts.items():
                out[comp] = out.get(comp, 0) + count
        return dict(sorted(out.items()))

    def summary(self) -> dict:
        # an always-on profiler is scraped via summary() without ever
        # stopping: flush here too so the registry aggregates track
        self._flush_registry()
        return {
            "samples": self.samples,
            "interval_s": self.interval_s,
            "by_component": self.by_component(),
            "top": self.top(10),
            "overhead_pct": round(100.0 * self.overhead_fraction, 4),
        }

    # ------------------------------------------------------------------

    def dump(self, reason: str = "", last: Optional[int] = None
             ) -> str:
        """Human-readable profile dump (the SLO breach postmortem)."""
        head = (
            f"profiler[{self.name}] dump ({reason or 'requested'}): "
            f"{self.samples} sample(s), "
            f"overhead {100.0 * self.overhead_fraction:.3f}%"
        )
        lines = [head]
        for comp, count in self.by_component().items():
            lines.append(f"  component {comp}: {count} samples")
        for row in self.top(last or 15):
            lines.append(
                f"    {row['samples']:6d}  [{row['component']}] "
                f"{row['frame']}"
            )
        return "\n".join(lines)

    def dump_to(self, reason: str = "",
                stream: Optional[IO[str]] = None,
                last: Optional[int] = None) -> str:
        text = self.dump(reason, last)
        print(text, file=stream or sys.stderr, flush=True)
        return text


# ======================================================================
# always-on self-time counters for per-op methods


# One call in this many is timed: two clock reads around every call
# cost about a tenth of a ticket on a TPU v5e host; a count per call and
# a timed sample keep the counters near a hundredth.
SELF_TIME_EVERY = 16


def self_timed(seconds_key: str, calls_key: str):
    """Decorate a method of two positional arguments (the per-op
    ``DocumentSequencer.ticket`` and ``TpuMergeSidecar.ingest``) with
    always-on counters in ``self.stats``: every call adds one to
    ``calls_key``; one call in ``SELF_TIME_EVERY`` is timed, and its
    host seconds times ``SELF_TIME_EVERY`` go to ``seconds_key``. So
    ``seconds / calls`` over a window estimates the mean self time per
    call, and ``seconds`` the total. Plain adds, no lock, no registry
    call. Observability only: nothing the method orders or returns
    reads the counters."""
    every = SELF_TIME_EVERY

    def wrap(method):
        @functools.wraps(method)
        def timed(self, a, b):
            stats = self.stats
            n = stats[calls_key] = stats[calls_key] + 1
            if n % every:
                return method(self, a, b)
            t0 = time.perf_counter()
            try:
                return method(self, a, b)
            finally:
                stats[seconds_key] += (time.perf_counter() - t0) * every
        return timed
    return wrap


# ======================================================================
# device-trace hooks (opt-in; never on the dispatch path by default)

DEVICE_TRACE_ENV = "FFTPU_DEVICE_TRACE"
_device_trace_on = False


def set_device_trace(on: bool) -> None:
    """Switch the round annotations on or off for this process, with no
    need for the process environment (``FFTPU_DEVICE_TRACE=1`` still
    switches them on as well)."""
    global _device_trace_on
    _device_trace_on = bool(on)


def device_trace_enabled() -> bool:
    """The switch: the module flag or ``FFTPU_DEVICE_TRACE=1``. The
    sidecar reads it once per ``apply``/``sync`` and hands each stage's
    span that one boolean."""
    return _device_trace_on or os.environ.get(DEVICE_TRACE_ENV) == "1"


@contextmanager
def device_trace(name: str):
    """Annotate a host span (a round stage) in the jax profiler trace.
    Off, it is one read of the switch and imports nothing; on, it
    forces no host<->device sync either. A loop that opens several
    spans per round reads the switch once and skips this call when it
    is off (``TpuMergeSidecar._span``)."""
    if not device_trace_enabled():
        yield
        return
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001 - profiler absent: still serve
        yield
        return
    with TraceAnnotation(name):
        yield
