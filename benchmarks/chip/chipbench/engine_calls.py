"""Model calls of a serving run, read from the engine tracer's step spans:
each decode and prefill call with its host interval and the FLOPs and bytes
it needs (``flops.py``)."""

from __future__ import annotations

import dataclasses

from chipbench import flops


@dataclasses.dataclass
class Call:
    kind: str            # "decode" | "prefill"
    start: float         # host clock
    end: float
    flops: float
    nbytes: float


def model_calls(run) -> list[Call]:
    """Every decode / prefill call the engine traced. A prefill span is
    followed by one ``chunk`` marker per chunk, whose ``last`` flag says
    whether its final row's logits were used."""
    D = run.dims
    out: list[Call] = []
    evs = run.engine_events or []
    for i, ev in enumerate(evs):
        if ev["kind"] != "step":
            continue
        t0, t1 = ev["t"], ev["t"] + ev["dur"]
        if ev["name"] == "decode":
            n, keys = ev["tokens"], ev["kv_rows"] + ev["tokens"]
            out.append(Call("decode", t0, t1, flops.decode_flops(D, n, keys),
                            flops.decode_bytes(D, n, keys)))
        elif ev["name"].startswith("prefill"):
            chunks = [tuple(c) for c in ev["chunks"]]
            marks = [e for e in evs[i + 1:i + 1 + len(chunks)]
                     if e["kind"] == "req" and e["name"] == "chunk"]
            lasts = [bool(m["last"]) for m in marks] + [False] * len(chunks)
            f = sum(flops.prefill_flops(D, s, n, int(last))
                    for (s, n), last in zip(chunks, lasts))
            out.append(Call("prefill", t0, t1, f,
                            flops.prefill_bytes(D, chunks)))
    return out


def inside(calls: list[Call], lo: float, hi: float) -> list[Call]:
    return [c for c in calls if c.start >= lo and c.end <= hi]
