"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload granite-3-2b.chat --seed 7 \\
        --seconds 51 --trace 0

Set-up (weights from the seed, the engine or trainer, compilation of every
shape the cell uses, warm-up traffic) is timed as ``setup_s``; then the
cell's traffic runs for ``--seconds``. With ``--trace 0`` the result holds
the cell's end-to-end metrics; with ``--trace 1`` a few seconds in the
middle of the window are traced with the JAX profiler and the result holds
the per-layer metrics, the device's busy and window seconds, and a
breakdown. Every run then compares what the timed path produced with the
plain float32 reference and prints each number compared beside its limit,
as the last lines of standard error and under ``check`` in the result.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``), then ``check``. Without a TPU, with fewer chips than the
cell asks for, or with a device kind missing from ``peaks.json``, it exits
non-zero before any work and prints no result. Compiled programs go to the
persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``, else
``.jax_cache/`` in the checkout).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def fail(msg: str, code: int = 3):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no program under {ROOT}/src; nothing was run", 2)
    from chipbench import manifest
    cell = manifest.load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r}); nothing was run")
    if len(devs) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, {len(devs)} visible")
    from chipbench.peaks import peaks_for
    try:
        peaks = peaks_for(devs[0].device_kind)
    except KeyError as e:
        fail(str(e))

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks)
    d = devs[0]
    result["device"] = {"platform": d.platform, "kind": d.device_kind,
                        "count": len(devs), **result["device"]}
    check = result.pop("check")
    for name, row in check.items():
        print(f"check {name} = {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    result["check"] = check
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool, peaks: dict,
             control: str | None = None) -> dict:
    """Run one cell and return the result object (``check`` last). With
    ``control`` the check also reads the reference computed in that lower
    precision (``check_low`` in the result)."""
    import jax

    from chipbench import check as chk
    from chipbench import manifest, trace_reduce
    from chipbench.common import Run
    from chipbench.reference import arch_from_config

    run = Run(cell=cell, seed=seed, seconds=seconds, peaks=peaks)
    compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: compiles.append(time.perf_counter())
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    kind = cell.mix["kind"]
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        if kind == "serve":
            from chipbench.serve_cell import run_serve
            run_serve(cell, seed, seconds, trace, run, trace_dir)
        else:
            from chipbench.train_cell import run_train
            run_train(cell, seed, seconds, trace, run, trace_dir)
        run.setup_s = run.window[0] - T_START
        if trace and run.trace_window is not None:
            run.trace = trace_reduce.read_trace(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = manifest.metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    arch = arch_from_config(cell.config)
    if kind == "serve":
        prog, low = chk.serve_numbers(run, arch, control)
    else:
        prog, low = chk.train_numbers(run, arch, cell.mix["optimizer"], control)
    correct, table = chk.judge(prog, cell.limits)
    out = {"correct": bool(correct), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": dict(run.device)}
    if trace and run.trace is not None:
        t = run.trace
        out["device"].update(busy_s=t.busy_s, window_s=t.window_s)
        progs = [[f"program:{k}", v] for k, v in trace_reduce.top(t.programs, 5)]
        out["breakdown"] = {
            "device_ops": progs + trace_reduce.top(t.ops, 10 - len(progs)),
            "idle_gaps": trace_reduce.top(t.idle_by_span, 10)}
    t0, t1 = run.window
    out["notes"] = dict(run.notes, readings=prog,
                        compiles_in_setup=sum(t < t0 for t in compiles),
                        compiles_in_window=sum(t0 <= t <= t1 for t in compiles))
    if control is not None:
        out["check_low"] = low
    out["check"] = table
    return out


if __name__ == "__main__":
    main()
