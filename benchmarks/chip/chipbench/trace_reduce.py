"""Reduce a profiler trace to busy time, idle gaps and per-program device
time.

The traced window is the harness's own ``bench.window`` annotation on the
host. Device work is the events of the ``XLA Ops`` line of every
``/device:...`` plane; programs are the events of its ``XLA Modules`` line,
named after the jitted function (``jit_decode_step(12)`` ->
``decode_step``). Busy time is the union of op intervals inside the window,
averaged over the devices that ran anything; an idle gap is a stretch of
the window with no op on the device, labelled by the innermost harness
annotation (``engine.step``, ``train.sync``, ...) that covers its midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
HARNESS_SPANS = ("traffic.submit", "engine.step", "client.collect",
                 "data.batch", "train.step", "train.sync")


@dataclasses.dataclass
class Event:
    name: str
    start: float             # ns, trace clock
    end: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    programs: dict[str, float]           # program -> device seconds
    program_calls: dict[str, int]
    ops: dict[str, float]                # op name -> device seconds
    idle_by_span: dict[str, float]       # host span -> idle seconds
    devices: int


def program_name(module: str) -> str:
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def union_length(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total length of the union, and the merged intervals in order."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _clip(evs: list[Event], w0: float, w1: float) -> list[tuple[float, float]]:
    return [(max(e.start, w0), min(e.end, w1)) for e in evs
            if e.end > w0 and e.start < w1]


def _innermost(spans: list[Event], starts: list[float], t: float) -> str:
    """Name of the latest-starting span that covers ``t`` (the harness's
    spans nest, so that is the innermost one)."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i].end >= t:
            return spans[i].name
        i -= 1
    return "host.other"


def summarize(window: tuple[float, float], device_ops: list[list[Event]],
              device_modules: list[list[Event]],
              host_spans: list[Event]) -> TraceSummary:
    """The reduction proper, on events already read from a trace (one list
    per device)."""
    w0, w1 = window
    spans = sorted(host_spans, key=lambda h: h.start)
    starts = [h.start for h in spans]
    busy, gaps_idle, ops, programs, calls = [], {}, {}, {}, {}
    used = 0
    for dev_ops, dev_mods in zip(device_ops, device_modules):
        clipped = _clip(dev_ops, w0, w1)
        if not clipped:
            continue
        used += 1
        length, merged = union_length(clipped)
        busy.append(length)
        for e in dev_ops:
            d = min(e.end, w1) - max(e.start, w0)
            if d > 0:
                ops[e.name] = ops.get(e.name, 0.0) + d * 1e-9
        for e in dev_mods:
            d = min(e.end, w1) - max(e.start, w0)
            if d > 0:
                p = program_name(e.name)
                programs[p] = programs.get(p, 0.0) + d * 1e-9
                calls[p] = calls.get(p, 0) + 1
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = _innermost(spans, starts, 0.5 * (s + e))
                gaps_idle[label] = gaps_idle.get(label, 0.0) + (e - s) * 1e-9
    n = max(used, 1)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) * 1e-9 / n,
        programs={k: v / n for k, v in programs.items()},
        program_calls=calls, ops={k: v / n for k, v in ops.items()},
        idle_by_span={k: v / n for k, v in gaps_idle.items()}, devices=used)


def read_trace(trace_dir: str) -> TraceSummary:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` and reduce it."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    window = None
    host: list[Event] = []
    dev_ops: list[list[Event]] = []
    dev_mods: list[list[Event]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":       # "%fusion.3 = f32[...] ..." -> "%fusion.3"
                    ops = [Event(e.name.split(" = ", 1)[0], e.start_ns, e.end_ns)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [Event(e.name, e.start_ns, e.end_ns) for e in line.events]
            dev_ops.append(ops)
            dev_mods.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name in HARNESS_SPANS:
                        host.append(Event(e.name, e.start_ns, e.end_ns))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return summarize(window, dev_ops, dev_mods, host)


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
