"""What the serving and training cells share: the run record, the
program's model, host spans, the traced sub-window and device memory."""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax

from chipbench.flops import Dims


@dataclasses.dataclass
class Run:
    """Everything one run measured; the metric readers read it."""
    cell: Any
    seed: int
    seconds: float
    peaks: dict                             # one chip's
    chips: int = 1
    setup_s: float = 0.0
    window: tuple[float, float] = (0.0, 0.0)
    attempted: int = 0
    failed: int = 0
    device: dict = dataclasses.field(default_factory=dict)
    dims: Dims | None = None
    notes: dict = dataclasses.field(default_factory=dict)
    # serving
    requests: list = dataclasses.field(default_factory=list)
    # (t_start, t_end, prefill_tokens, generated, active lanes) per step
    steps: list = dataclasses.field(default_factory=list)
    engine_events: list | None = None
    # training: (t_start, t_end, tokens, flops, loss) per window step
    train_steps: list = dataclasses.field(default_factory=list)
    # tracing
    trace: Any = None                       # trace_reduce.TraceSummary
    trace_window: tuple[float, float] | None = None
    # correctness
    check_inputs: Any = None
    params: Any = None
    check: dict = dataclasses.field(default_factory=dict)


def program_model(config: dict):
    """The program's model for a configuration file's ``program`` block:
    the registry's config with the listed overrides."""
    from repro.configs import get_config
    from repro.models import build_model

    p = config["program"]
    cfg = dataclasses.replace(get_config(p["arch"]), **p.get("overrides", {}))
    return build_model(cfg)


def dims_of(cfg) -> Dims:
    return Dims(layers=cfg.num_layers, d_model=cfg.d_model,
                heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                mlp_mats=3 if cfg.mlp_type in ("swiglu", "geglu") else 2,
                elt_bytes=2 if cfg.dtype == "bfloat16" else 4)


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


def device_peak_bytes() -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class profile_window:
    """Trace ``trace_s`` seconds in the middle of the window ``[t0, t0 +
    seconds)``. ``tick(now)`` starts and stops the profiler between steps;
    the traced stretch is the ``bench.window`` host span."""

    def __init__(self, trace_dir: str, t0: float, seconds: float,
                 trace_s: float):
        self.dir = trace_dir
        span = min(trace_s, seconds)
        self.start = t0 + 0.5 * (seconds - span)
        self.stop = self.start + span
        self.state = "before"
        self.host = None
        self._ann = None

    def tick(self, now: float) -> None:
        if self.state == "before" and now >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = annotate("bench.window")
            self._ann.__enter__()
            self.host = [time.perf_counter(), None]
            self.state = "on"
        elif self.state == "on" and now >= self.stop:
            self.close()

    def close(self) -> None:
        if self.state == "on":
            self.host[1] = time.perf_counter()
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

