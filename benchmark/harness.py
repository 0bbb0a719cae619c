"""Pieces every traffic kind shares: the sidecar as a configuration
states it, compile counting, host spans, and the traced slice."""
from __future__ import annotations

import contextlib
import os
import sys
import time


def build_sidecar(sizes: dict):
    """The system under test, as the configuration states it: route,
    donation and pipeline at the backend's defaults."""
    from fluidframework_tpu.ops.bucket_ladder import BucketLadder
    from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar

    return TpuMergeSidecar(
        max_docs=sizes["docs"], capacity=sizes["capacity"],
        max_capacity=sizes["max_capacity"],
        compact_every=sizes["compact_every"],
        ladder=BucketLadder(window_floor=sizes["window_floor"],
                            max_bucket=sizes["max_bucket"]))


class CompileMeter:
    """XLA compile requests (JAX's backend-compile event, which also
    wraps the ones the persistent cache serves) and cache hits; copied
    from the program's ``chip_smoke.CompileMeter``."""

    def __init__(self):
        import jax

        self.requests = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_requests": self.requests,
                "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}


class Spans:
    """Host-clock seconds and calls per span name, taken by the
    benchmark around its calls into each layer. When a trace is on, each
    span is also a profiler annotation, so idle gaps on the device can
    be put down to what the host was doing."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.calls: dict[str, int] = {}
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            from jax.profiler import TraceAnnotation

            ann = TraceAnnotation(f"bench:{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name] = (self.seconds.get(name, 0.0)
                              + time.perf_counter() - t0)
        self.calls[name] = self.calls.get(name, 0) + 1


class SliceTracer:
    """With ``--trace 1``: one profiler trace of a steady slice of the
    window, opened and closed at round boundaries with the pipeline
    drained (``sync``), so every device event in it belongs to the
    rounds inside. Opens once ``start_frac`` of the window has passed,
    closes after ``min_rounds`` rounds and ``min_s`` seconds."""

    def __init__(self, enabled: bool, logdir: str, spans: Spans,
                 start_frac: float, min_s: float, min_rounds: int):
        self.enabled = enabled
        self.logdir = logdir
        self.spans = spans
        self.start_frac, self.min_s = start_frac, min_s
        self.min_rounds = min_rounds
        self.state = "before"
        self.rounds: list = []     # per traced round: the harness's record
        self.t_open = self.t_close = 0.0

    def at_round_boundary(self, sidecar, elapsed: float,
                          seconds: float) -> None:
        if not self.enabled:
            return
        if self.state == "before" and elapsed >= self.start_frac * seconds:
            import jax

            sidecar.sync()
            os.environ["FFTPU_DEVICE_TRACE"] = "1"
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0    # spans, not every call
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.logdir, profiler_options=options)
            self.spans.annotate = True
            self.state = "open"
            self.t_open = time.perf_counter()
        elif (self.state == "open" and len(self.rounds) >= self.min_rounds
              and time.perf_counter() - self.t_open >= self.min_s):
            self.close(sidecar)

    @property
    def wants_rounds(self) -> bool:
        """The slice is open and has not yet held its rounds: a closed
        loop keeps going past the window's end until it has."""
        return self.state == "open"

    def note_round(self, record) -> None:
        if self.state == "open":
            self.rounds.append(record)

    def close(self, sidecar) -> None:
        if self.state != "open":
            return
        import jax

        with self.spans("sync"):
            sidecar.sync()
        self.t_close = time.perf_counter()
        self.spans.annotate = False
        jax.profiler.stop_trace()
        os.environ.pop("FFTPU_DEVICE_TRACE", None)
        self.state = "closed"


def envelope(op: tuple) -> dict:
    """A recorded op as the program's channel envelope: the
    SharedString op of channel ``s`` in datastore ``d``."""
    from fluidframework_tpu.models.mergetree.ops import (
        AnnotateOp,
        InsertOp,
        RemoveOp,
    )

    kind, p1, p2, text, props = op
    if kind == "ins":
        inner = (InsertOp(pos1=p1, text=text, props=props) if text
                 else InsertOp(pos1=p1, marker={"refType": 0}, props=props))
    elif kind == "rem":
        inner = RemoveOp(pos1=p1, pos2=p2)
    else:
        inner = AnnotateOp(pos1=p1, pos2=p2, props=dict(props))
    return {"kind": "op", "address": "d", "channel": "s", "contents": inner}


def merge_ops(op: tuple) -> int:
    """Merge-level ops one recorded op makes: an insert and one set per
    insert-time key, a remove, one set per annotated key."""
    kind, _p1, _p2, _text, props = op
    if kind == "ins":
        return 1 + sum(v is not None for v in (props or {}).values())
    return 1 if kind == "rem" else len(props)


def read_served(sidecar, n_docs: int) -> tuple[list, list]:
    texts, sigs = [], []
    for d in range(n_docs):
        texts.append(sidecar.text(f"doc-{d}", "d", "s"))
        sigs.append(sidecar.signature(f"doc-{d}", "d", "s"))
    return texts, sigs


def compare(expected: list, texts: list, sigs: list) -> dict:
    """``expected``: per document, the reference's (text, signature)."""
    from .reference import props_agree

    text_bad = [d for d, (t, e) in enumerate(zip(texts, expected))
                if t != e[0]]
    sig_bad = [d for d, (s, e) in enumerate(zip(sigs, expected))
               if not props_agree(e[1], s)]
    for d in text_bad[:4]:
        t, e = texts[d], expected[d][0]
        at = next((i for i, (a, b) in enumerate(zip(t, e)) if a != b),
                  min(len(t), len(e)))
        print(f"doc-{d}: served text differs from the reference at {at} "
              f"(lengths {len(t)} / {len(e)}): served "
              f"{t[max(0, at - 20):at + 20]!r}, reference "
              f"{e[max(0, at - 20):at + 20]!r}", file=sys.stderr)
    return {"text_mismatch_docs": len(text_bad),
            "signature_mismatch_docs": len(sig_bad)}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def intern_signature(sig: list) -> list:
    """A reference signature in the served form (a control put in the
    program's place): one channel per key and one id per value, in the
    order first seen."""
    keys: dict = {}
    vals: dict = {}
    for _, props in sig:
        for k, v in props.items():
            keys.setdefault(k, len(keys))
            vals.setdefault(repr(v), len(vals) + 1)
    width = max(len(keys), 4)
    out = []
    for ch, props in sig:
        ids = [0] * width
        for k, v in props.items():
            ids[keys[k]] = vals[repr(v)]
        out.append((ch, tuple(ids)))
    return out
