"""From a profiler trace of one slice to the numbers the metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` wrote, through
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``;
on each, the ``XLA Ops`` line holds every operation the device ran and
the ``XLA Modules`` line the program (XLA module) each belongs to. The
host plane holds the benchmark's own spans (``bench:<layer>``) and the
sidecar's ``sidecar:dispatch:r<round>`` annotations.

The slice is the span from the first to the last ``bench:`` span on
the host. Within it:
- busy: the union of the intervals in which an operation ran, averaged
  over the device planes; idle share = 1 - busy / slice;
- module time: device seconds per XLA module (name without its id);
- device ops: the innermost operations that took most time, by module
  and HLO instruction name;
- idle gaps: device-idle time put down to the innermost host span that
  covers it (``host:none`` where none does), summed per span name.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(found, key=os.path.getmtime)


def module_name(event_name: str) -> str:
    """``jit_compact(123)`` -> ``jit_compact``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union(intervals: list, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def read_planes(path: str) -> tuple[dict, list]:
    """Device planes -> {"ops": [...], "modules": [...]} of (name, start,
    end) in seconds; host spans as (name, start, end)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ev in lines[name].events] if name in lines else []
                for key, name in (("ops", OPS_LINE),
                                  ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench:", "sidecar:")):
                        name = re.sub(r":r\d+$", "", ev.name)
                        host.append((name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return devices, host


def reduce_slice(logdir: str) -> dict:
    devices, host = read_planes(newest_xplane(logdir))
    return reduce_events(devices, host)


def reduce_events(devices: dict, host: list) -> dict:
    bench = [h for h in host if h[0].startswith("bench:")]
    if not devices or not bench:
        raise ValueError("the trace holds no device plane or no bench span")
    lo = min(h[1] for h in bench)
    hi = max(h[2] for h in bench)
    window = hi - lo
    busy_total = 0.0
    module_s: dict = {}
    op_s: dict = {}
    gaps_by: dict = {}
    for dev in devices.values():
        busy = union([(s, e) for _, s, e in dev["ops"]], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for name, s, e in dev["modules"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = module_name(name)
                module_s[key] = module_s.get(key, 0.0) + e - s
        owner = _owner_of(dev["modules"])
        for name, s, e in _leaves(dev["ops"]):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = f"{owner(s)}/{name.split(' = ')[0]}"
                op_s[key] = op_s.get(key, 0.0) + e - s
        _idle_gaps(busy, lo, hi, host, gaps_by)
    n_dev = len(devices)
    return {
        "window_s": window,
        "busy_s": busy_total / n_dev,
        "module_s": {k: v / n_dev for k, v in module_s.items()},
        "device_ops": sorted(([k, v / n_dev] for k, v in op_s.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v / n_dev] for k, v in gaps_by.items()),
                            key=lambda kv: -kv[1]),
    }


def _leaves(ops: list) -> list:
    """Operations that enclose no other: a while loop's events nest its
    body's, and counting both would count that time twice."""
    ops = sorted(ops, key=lambda op: (op[1], -op[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[2]]


def _owner_of(modules: list):
    spans = sorted((s, e, module_name(n)) for n, s, e in modules)
    starts = [sp[0] for sp in spans]

    def owner(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < spans[i][1]:
            return spans[i][2]
        return "?"
    return owner


def _host_labels(host: list) -> list:
    """The host timeline cut where any span starts or ends, each piece
    named by the innermost (shortest) span covering it."""
    cuts = sorted({t for _, s, e in host for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(e - s, n) for n, s, e in host if s <= mid < e]
        if cover:
            out.append((a, b, min(cover)[1]))
    return out


def _idle_gaps(busy: list, lo: float, hi: float, host: list,
               into: dict) -> None:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
    labels = _host_labels(host)
    j = 0
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(labels) and labels[j][1] <= g0:
            j += 1
        k = j
        while k < len(labels) and labels[k][0] < g1:
            a, b, name = labels[k]
            part = min(b, g1) - max(a, g0)
            if part > 0:
                into[name] = into.get(name, 0.0) + part
                covered += part
            k += 1
        if g1 - g0 - covered > 0:
            into["host:none"] = (into.get("host:none", 0.0)
                                 + g1 - g0 - covered)
