"""One traced run of a cell, split by the program's named scopes and host
spans, for the per-cell time breakdowns in PERF.md.

    python3 benchmarks/chip/scope_breakdown.py --workload gpt2-small.train-4k \\
        --seed 7 --seconds 51 [--keep DIR]

Drives the cell as ``run.py --trace 1`` does (TPU only) and prints one JSON
line: the cell's per-layer and end-to-end metrics read from this traced
run (to set against untraced runs of the same seed, for what tracing
costs), then the traced window's device seconds per program and per scope
(``chipbench.scope_reduce``), each scope's share of its program, the time
outside every scope by op, the idle seconds labelled by the innermost
harness or ``engine.*`` span, and the program spans' host seconds. It does
not run the reference check. ``--keep`` leaves the trace in DIR, to be
reduced again off the chip with ``chipbench.scope_reduce.read_scopes``.

The compile cache key includes the programs' metadata here, so the scope
paths are this tree's even where the cache holds the same programs
compiled from other code (the first such run compiles afresh).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    import jax

    from chipbench import manifest, scope_reduce, trace_reduce
    from chipbench.common import Run
    from chipbench.peaks import peaks_for
    from repro.launch.compile_cache import enable_compile_cache
    from repro.telemetry.scopes import DEVICE_SCOPES

    cell = manifest.load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"JAX found no TPU (platform {dev.platform!r})")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              peaks=peaks_for(dev.device_kind), chips=cell.chips)
    trace_dir = args.keep or tempfile.mkdtemp(prefix="scope_breakdown_")
    if cell.mix["kind"] == "serve":
        from chipbench.serve_cell import run_serve as drive
    else:
        from chipbench.train_cell import run_train as drive
    drive(cell, args.seed, args.seconds, True, run, trace_dir)
    run.setup_s = run.window[0] - T_START
    run.trace = trace_reduce.read_trace(trace_dir)
    metrics = {}
    for m in cell.per_layer + cell.end_to_end:
        v = manifest.metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = float(v)

    s = scope_reduce.read_scopes(trace_dir, DEVICE_SCOPES)
    progs = s.trace.programs
    print(json.dumps({
        "metrics": metrics, "window_s": s.trace.window_s,
        "busy_s": s.trace.busy_s, "programs": progs, "scopes": s.scopes,
        "scope_share_pct": {p: {k: 100.0 * v / progs[p] for k, v in d.items()}
                            for p, d in s.scopes.items() if progs.get(p)},
        "outside": {p: dict(trace_reduce.top(d, 8))
                    for p, d in s.outside.items()},
        "idle_by_span": s.trace.idle_by_span,
        "idle_s": sum(s.trace.idle_by_span.values()), "host_s": s.host_s,
        "unscoped_ops": s.unscoped_ops}), flush=True)


if __name__ == "__main__":
    main()
