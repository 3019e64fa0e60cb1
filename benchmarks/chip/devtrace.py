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

Named scopes: the plane ``/host:metadata`` holds each program's
optimized HLO (stat ``Hlo Proto``, keyed by the module's name as the
``XLA Modules`` line gives it), and each instruction's ``op_name``
metadata holds the ``jax.named_scope`` stack it was traced under. An op
of the ``XLA Ops`` line is joined to its instruction by name; a fusion
takes the scope of its costliest instruction (its largest ``dot`` or
``convolution``, else its root), so that a matmul fused with the next
residual add still counts where the matmul was written. ``ProfileData``
shows neither metadata plane stats nor event metadata stats, so the file
is read here field by field (``XSpace``, ``XPlane``, ``XEventMetadata``,
``XStat``; ``HloProto``, ``HloModuleProto``, ``HloComputationProto``,
``HloInstructionProto``, ``OpMetadata``, ``ShapeProto``).
"""
from __future__ import annotations

import bisect
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


def reduce(path: str, programs: tuple = (), scopes=()) -> dict:
    """``busy_s`` (averaged over the chips that ran anything), ``window_s``,
    per-program ``{name: [runs, device seconds]}`` for each name in
    ``programs`` (averaged over chips), ``device_ops`` and ``idle_gaps``
    (each at most 10 ``[name, seconds]``, longest first). With
    ``scopes`` (the named scopes to look for), also ``scopes``:
    ``{program: {"runs": n, "seconds": {scope: s}}}`` over the runs of
    each program that lie wholly inside the window, each op's seconds
    under the innermost of ``scopes`` it ran under, else ``other``
    (averaged over chips)."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    host = _host_spans(profile)
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = windows[0]
    devices = [p for p in profile.planes if _DEVICE.match(p.name)]
    busy, ops, progs, merged0 = [], {}, {}, None
    per_scope: dict = {}
    hlo = _program_scopes(path, programs, scopes) if scopes and devices else {}
    for plane in devices:
        op_events = _clip_events(_events(plane, "XLA Ops"), lo, hi)
        if not op_events:
            continue
        if scopes:
            _add_scope_seconds(per_scope, plane, programs, hlo, lo, hi)
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
    out = {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": {p: [runs / n, t / n / 1e9]
                     for p, (runs, t) in progs.items()},
        "device_ops": [[name, t / n / 1e9] for name, t in top],
        "idle_gaps": [[name, s] for name, s in gaps[:10]],
    }
    if scopes:
        out["scopes"] = {p: {"runs": r["runs"] / n,
                             "seconds": {k: t / n / 1e9
                                         for k, t in r["seconds"].items()}}
                         for p, r in per_scope.items()}
    return out


def _add_scope_seconds(acc: dict, plane, programs: tuple, hlo: dict,
                       lo: float, hi: float) -> None:
    """Add one chip's op seconds per program and scope to ``acc``, over
    the program runs wholly inside ``[lo, hi]``."""
    runs = sorted((s, e, name) for name, s, e in _events(plane, "XLA Modules")
                  if lo <= s and e <= hi
                  and any(p in name for p in programs))
    starts = [s for s, _, _ in runs]
    for s, _, name in runs:
        p = next(p for p in programs if p in name)
        acc.setdefault(p, {"runs": 0, "seconds": {}})["runs"] += 1
    for op, s, e in _leaves(_events(plane, "XLA Ops")):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue
        module = runs[i][2]
        p = next(p for p in programs if p in module)
        instr = op.split(" = ")[0].lstrip("%")
        scope = hlo.get(module, {}).get(instr) or "other"
        secs = acc[p]["seconds"]
        secs[scope] = secs.get(scope, 0.0) + (e - s)


_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")


def scope_of(op_name: str, scopes) -> str | None:
    """The innermost of ``scopes`` in a name stack: components are split
    on ``/``, and a transform's wrapper (``jvp(mlp)``,
    ``transpose(jvp(attention))``) names the scope inside it. A ``;``
    joins the stacks of instructions merged into one."""
    for stack in op_name.split(";"):
        for part in reversed(stack.rstrip(":").split("/")):
            while (m := _WRAPPED.match(part)):
                part = m.group(1)
            if part in scopes:
                return part
    return None


# --- the trace file, field by field -------------------------------------

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field, bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _program_scopes(path: str, programs: tuple, scopes) -> dict:
    """``{module name: {instruction: scope}}`` of the modules of
    ``programs``, from the HLO the trace's metadata plane holds."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:                      # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for g, v in _fields(plane):
            if g == 2:                      # XPlane.name, before the rest
                name = _text(v)
                if name != "/host:metadata":
                    break
            elif g == 4:                    # event_metadata map entry
                metas.append(dict(_fields(v)).get(2))
            elif g == 5:                    # stat_metadata map entry
                sm = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[sm.get(1)] = _text(sm.get(2, b""))
        if name != "/host:metadata":
            continue
        for meta in metas:
            module = ""
            for g, v in _fields(meta or b""):
                if g == 2:                  # XEventMetadata.name
                    module = _text(v)
                elif g == 5:                # XEventMetadata.stats
                    st = dict(_fields(v))
                    if (stat_names.get(st.get(1)) == "Hlo Proto"
                            and 6 in st
                            and any(p in module for p in programs)):
                        out[module] = hlo_scopes(
                            dict(_fields(st[6])).get(1, b""), scopes)
    return out


def hlo_scopes(module: bytes, scopes) -> dict:
    """``{instruction name: scope}`` of a serialized ``HloModuleProto``
    for every instruction whose scope is one of ``scopes``."""
    comps, by_name = {}, {}
    for g, v in _fields(module):
        if g != 3:                          # HloModuleProto.computations
            continue
        cid, root, instrs = None, None, []
        for h, w in _fields(v):
            if h == 5:
                cid = w
            elif h == 6:
                root = w
            elif h == 2:
                instrs.append(_instruction(w))
        comps[cid] = (instrs, root)
    for instrs, _ in comps.values():
        for ins in instrs:
            op_name = ins["op_name"]
            if ins["opcode"] == "fusion":
                op_name = _costliest(ins, comps, 4) or op_name
            scope = scope_of(op_name, scopes)
            if scope is not None:
                by_name[ins["name"]] = scope
    return by_name


def _instruction(buf) -> dict:
    ins = {"name": "", "opcode": "", "op_name": "", "size": 1, "calls": [],
           "id": None}
    for f, v in _fields(buf):
        if f == 1:
            ins["name"] = _text(v)
        elif f == 2:
            ins["opcode"] = _text(v)
        elif f == 3:                        # ShapeProto: its element count
            for g, w in _fields(v):
                if g == 3:                  # dimensions
                    for d in _ints(w):
                        ins["size"] *= max(d, 1)
        elif f == 7:                        # OpMetadata.op_name
            ins["op_name"] = _text(dict(_fields(v)).get(2, b""))
        elif f == 35:
            ins["id"] = v
        elif f == 38:                       # called_computation_ids
            ins["calls"] += _ints(v)
    return ins


def _ints(v) -> list:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, j = [], 0
    while j < len(v):
        x, j = _varint(v, j)
        out.append(x)
    return out


def _costliest(fusion: dict, comps: dict, depth: int) -> str:
    """The ``op_name`` of a fusion's largest ``dot`` or ``convolution``
    (by result elements), looking into the computations it calls, else of
    the root of its fused computation."""
    best, root = None, None
    stack = [(c, depth) for c in fusion["calls"]]
    while stack:
        cid, d = stack.pop()
        instrs, root_id = comps.get(cid, ([], None))
        for ins in instrs:
            if ins["opcode"] in ("dot", "convolution"):
                if best is None or ins["size"] > best["size"]:
                    best = ins
            elif ins["calls"] and d > 0:
                stack += [(c, d - 1) for c in ins["calls"]]
            if root is None and cid == fusion["calls"][0] \
                    and ins["id"] == root_id:
                root = ins
    if best is not None:
        return best["op_name"]
    if root is not None and root["opcode"] == "fusion" and depth > 0:
        return _costliest(root, comps, depth - 1)
    return root["op_name"] if root is not None else ""


def _leaves(events: list) -> list:
    """The events that hold no other: a loop's own event spans its body's
    operations, which the line lists as well. Only an event of some
    length makes a holder: the line also has zero-length markers (an
    async copy's start or done, an empty custom call) that start inside
    an operation, and that operation's time is its own."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = []
    for i, (name, s, e) in enumerate(events):
        j = i + 1
        while (j < len(events) and events[j][1] < e
               and (events[j][2] > e or events[j][2] <= events[j][1])):
            j += 1
        if j == len(events) or events[j][1] >= e:
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
