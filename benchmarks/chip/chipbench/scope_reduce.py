"""Split a profiler trace by the program's named scopes and host spans,
which ``trace_reduce`` does not read.

* ``scopes``: ``{program: {scope: device seconds}}``. For each program, the
  union of the intervals of the device ops whose scope path has that scope
  as a component, clipped to the window and averaged over the devices that
  ran anything. An enclosing op (a layer scan's ``while``) carries no scope
  of its own, and the union keeps ops that nest from counting twice.
* ``outside``: ``{program: {op: device seconds}}``, the same unions for
  the ops whose path has none of those scopes, keyed by the path's last
  component (``dynamic_slice``, ``dot_general``, ...).
* ``trace``: ``trace_reduce.summarize`` with the program's host spans
  (``engine.*``) beside the harness's, so that each idle gap is labelled
  by the innermost of either. Busy time, programs and ops are unchanged.
* ``host_s``: seconds of each program span inside the window (not
  ``engine.step``, which is also the harness's name for its call).

A scope path is the ``tf_op`` stat that the TPU profiler puts in each
device op's event metadata, from the op's ``op_name``:
``jit(decode_step)/while/body/closed_call/attention/dot_general``; the
backward pass wraps components as ``transpose(jvp(...))``.
``jax.profiler.ProfileData`` gives no event metadata, so ``op_paths``
reads it from the ``.xplane.pb`` file's protobuf encoding.

The path is the metadata of the compiled program that ran. The persistent
compilation cache leaves metadata out of its key by default, so a program
loaded from an entry that another version of the code compiled carries
that version's paths: read scopes from a run with
``jax_compilation_cache_include_metadata_in_key`` set.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

from chipbench import trace_reduce
from chipbench.trace_reduce import (HARNESS_SPANS, WINDOW, Event,
                                    TraceSummary, program_name, union_length)

PROGRAM_SPAN_PREFIX = "engine."
_WRAPPED = re.compile(r"^(?:[\w.-]+\()*([^()]*?)\)*$")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


@dataclasses.dataclass
class ScopeSummary:
    trace: TraceSummary                      # idle labelled by engine.* too
    scopes: dict[str, dict[str, float]]      # program -> scope -> seconds
    outside: dict[str, dict[str, float]]     # program -> op -> seconds
    host_s: dict[str, float]                 # program span -> seconds
    unscoped_ops: int                        # ops with no path at all


def scope_parts(path: str) -> set[str]:
    """The scopes a path passes through, wrappers stripped."""
    return {_WRAPPED.match(c).group(1) for c in path.split("/") if c}


def summarize(window: tuple[float, float], device_ops: list[list[Event]],
              device_modules: list[list[Event]], host_spans: list[Event],
              paths: list[dict[tuple[int, str], str]],
              scopes: tuple[str, ...]) -> ScopeSummary:
    """The reduction on events already read, one list per device;
    ``paths[d]`` maps (program id, op name) to the op's scope path."""
    w0, w1 = window
    trace = trace_reduce.summarize(window, device_ops, device_modules,
                                   host_spans)
    wanted = set(scopes)
    per: dict[str, dict[str, float]] = {}
    outside: dict[str, dict[str, float]] = {}
    unscoped = 0
    for ops, mods, dev_paths in zip(device_ops, device_modules, paths):
        mods = sorted(mods, key=lambda m: m.start)
        starts = [m.start for m in mods]
        groups = collections.defaultdict(list)
        for o in ops:
            if o.end <= w0 or o.start >= w1:
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            mod = mods[i].name if i >= 0 and mods[i].end >= o.start else ""
            hit = _PROGRAM_ID.search(mod)
            path = dev_paths.get((int(hit.group(1)), o.name), "") if hit else ""
            unscoped += not path
            iv = (max(o.start, w0), min(o.end, w1))
            found = scope_parts(path) & wanted
            for s in found:
                groups[0, program_name(mod), s].append(iv)
            if path and not found:
                groups[1, program_name(mod),
                       path.rsplit("/", 1)[-1]].append(iv)
        for (k, prog, s), ivs in groups.items():
            d = (per, outside)[k].setdefault(prog, {})
            d[s] = d.get(s, 0.0) + union_length(ivs)[0] * 1e-9
    n = max(trace.devices, 1)
    host: dict[str, float] = {}
    for h in host_spans:
        if (h.name.startswith(PROGRAM_SPAN_PREFIX)
                and h.name not in HARNESS_SPANS):
            d = min(h.end, w1) - max(h.start, w0)
            if d > 0:
                host[h.name] = host.get(h.name, 0.0) + d * 1e-9
    return ScopeSummary(
        trace=trace,
        scopes={p: {s: v / n for s, v in d.items()} for p, d in per.items()},
        outside={p: {s: v / n for s, v in d.items()}
                 for p, d in outside.items()},
        host_s=host, unscoped_ops=unscoped)


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
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
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _multi(buf) -> dict[int, list]:
    out = collections.defaultdict(list)
    for f, v in _fields(buf):
        out[f].append(v)
    return out


def op_paths(xplane_path: str) -> dict[str, dict[tuple[int, str], str]]:
    """``{device plane: {(program id, op name): scope path}}`` from
    the ``program_id`` and ``tf_op`` stats of each device plane's event
    metadata. Field numbers are those of ``tsl/profiler/protobuf/
    xplane.proto``: XSpace.planes 1; XPlane name 2, event_metadata 4,
    stat_metadata 5 (maps: key 1, value 2); XEventMetadata name 2, stats 5;
    XStatMetadata name 2; XStat metadata_id 1, uint64 3, str 5, ref 7 (a
    string interned as a stat metadata name)."""
    with open(xplane_path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        p = _multi(plane)
        name = bytes(p[2][0]).decode() if p[2] else ""
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for entry in p[5]:
            e = _multi(entry)
            if e[2]:
                stat_names[e[1][0] if e[1] else 0] = bytes(
                    _multi(e[2][0])[2][0]).decode()
        paths = {}
        for entry in p[4]:
            e = _multi(entry)
            if not e[2]:
                continue
            meta = _multi(e[2][0])
            stats = {}
            for s in meta[5]:
                st = _multi(s)
                key = stat_names.get(st[1][0] if st[1] else 0)
                stats[key] = (st[5] or st[3] or [stat_names.get(r)
                                                 for r in st[7]] or [None])[0]
            if "tf_op" in stats and "program_id" in stats and meta[2]:
                tf_op = str(stats["tf_op"], "utf-8") if isinstance(
                    stats["tf_op"], memoryview) else stats["tf_op"]
                op = bytes(meta[2][0]).decode().split(" = ", 1)[0]
                paths[stats["program_id"], op] = (
                    tf_op[:-1] if tf_op.endswith(":") else tf_op)
        out[name] = paths
    return out


def read_scopes(trace: str, scopes: tuple[str, ...]) -> ScopeSummary:
    """Reduce an ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData

    if not os.path.isfile(trace):
        files = sorted(glob.glob(os.path.join(trace, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace}")
        trace = files[-1]
    by_plane = op_paths(trace)
    window = None
    host: list[Event] = []
    dev_ops, dev_mods, paths = [], [], []
    for plane in ProfileData.from_file(trace).planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            dev_ops.append([trace_reduce.op_event(e) for e in
                            getattr(lines.get("XLA Ops"), "events", ())])
            dev_mods.append([Event(e.name, e.start_ns, e.end_ns) for e in
                             getattr(lines.get("XLA Modules"), "events", ())])
            paths.append(by_plane.get(plane.name, {}))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif (e.name in HARNESS_SPANS
                          or e.name.startswith(PROGRAM_SPAN_PREFIX)):
                        host.append(Event(e.name, e.start_ns, e.end_ns))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return summarize(window, dev_ops, dev_mods, host, paths, scopes)
