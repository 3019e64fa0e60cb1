"""From a profiler trace to device busy time, the step programs' spans,
the top device operations and the longest idle gaps.

The trace is the ``.xplane.pb`` the JAX profiler writes, read with
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``; on
each, the ``XLA Ops`` line holds one event per operation run (a loop's event
spans its body's) and the ``XLA Modules`` line one event per program run
(``jit_<name>(<id>)``). Device operations are named
``<program>:<instruction>``.
The host plane ``/host:CPU`` holds the harness's ``TraceAnnotation``
spans, which name what the host was doing in each idle gap. Device
timestamps are on the host's clock in this file, so the window is the
span of the harness's ``bench.trace_window`` annotation.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.trace_window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def find(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(plane, line_name: str) -> list:
    for line in plane.lines:
        if line.name == line_name:
            return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]
    return []


def _host_spans(profile) -> list:
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for line in plane.lines for ev in line.events
                    if ev.name == WINDOW or ev.name.startswith("client")]
    return []


def reduce(path: str, programs: tuple = ()) -> dict:
    """``busy_s`` (averaged over the chips that ran anything), ``window_s``,
    per-program ``{name: [runs, device seconds]}`` for each name in
    ``programs`` (averaged over chips), ``device_ops`` and ``idle_gaps``
    (each at most 10 ``[name, seconds]``, longest first)."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    host = _host_spans(profile)
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = windows[0]
    devices = [p for p in profile.planes if _DEVICE.match(p.name)]
    busy, ops, progs, merged0 = [], {}, {}, None
    for plane in devices:
        op_events = _clip_events(_events(plane, "XLA Ops"), lo, hi)
        if not op_events:
            continue
        merged = _merge([(s, e) for _, s, e in op_events])
        busy.append(sum(e - s for s, e in merged))
        if merged0 is None:
            merged0 = merged
        modules = _clip_events(_events(plane, "XLA Modules"), lo, hi)
        for name, s, e in _leaves(op_events):
            label = f"{_module_at(modules, s)}:{name.split(' = ')[0]}"
            ops[label] = ops.get(label, 0.0) + (e - s)
        for name, s, e in modules:
            for p in programs:
                if p in name:
                    runs, t = progs.get(p, (0, 0.0))
                    progs[p] = (runs + 1, t + (e - s))
    n = max(len(busy), 1)
    gaps = []
    if merged0:
        edges = [lo] + [x for iv in merged0 for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_label(host, (s + e) / 2), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": {p: [runs / n, t / n / 1e9]
                     for p, (runs, t) in progs.items()},
        "device_ops": [[name, t / n / 1e9] for name, t in top],
        "idle_gaps": [[name, s] for name, s in gaps[:10]],
    }


def _leaves(events: list) -> list:
    """The events that hold no other: a loop's own event spans its body's
    operations, which the line lists as well."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = []
    for i, (name, s, e) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or not (nxt[1] < e and nxt[2] <= e):
            out.append((name, s, e))
    return out


def _module_at(modules: list, t: float) -> str:
    for name, s, e in modules:
        if s <= t < e:
            return name.split("(")[0]
    return "?"


def _clip_events(events: list, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _label(host: list, t: float) -> str:
    """The harness spans open at host time ``t``."""
    names = sorted({n for n, s, e in host if s <= t < e and n != WINDOW})
    return "+".join(names) or "outside any client span"
