"""Engine host / scheduler: the median, over the engine steps inside the
window, of each step's host work, that is its ``engine.step`` span less the
``engine.sync`` spans inside it, where the host waits for the chip (ms).
Read from the engine tracer's scope events; a program that records none
reads nothing."""

import statistics


def read(run):
    scopes = [ev for ev in run.engine_events or () if ev["kind"] == "scope"]
    t0, t1 = run.window
    host = {ev["id"]: ev["dur"] for ev in scopes
            if ev["name"] == "engine.step"
            and t0 <= ev["t"] and ev["t"] + ev["dur"] <= t1}
    if not host:
        return None
    parent = {ev["id"]: ev["parent"] for ev in scopes}
    for ev in scopes:
        if ev["name"] != "engine.sync":
            continue
        up = ev["parent"]
        while up >= 0 and up not in host:
            up = parent.get(up, -1)
        if up >= 0:
            host[up] -= ev["dur"]
    return 1e3 * statistics.median(host.values())
