"""Benchmark aggregator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all (CSV to stdout)
    PYTHONPATH=src python -m benchmarks.run --only fig2
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI: cheap subset

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is the benchmark's
primary scalar; unit given in the name). ``--smoke`` runs a reduced subset
(scripts/ci.sh) so harness regressions — e.g. from layout-compiler changes —
fail CI instead of rotting silently; modules whose ``run`` accepts a
``smoke`` keyword shrink their sweeps.

Every run also persists ``benchmarks/results/BENCH_<n>.json`` (next free
index; override the directory with ``--results-dir``): one record per bench
row with name/value/units plus run metadata, so the perf trajectory is
machine-trackable across PRs instead of living in scrollback."""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import re
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

MODULES = [
    "benchmarks.bench_fig2",            # Fig. 2 left/middle/right
    "benchmarks.bench_table1_bert",     # Table 1
    "benchmarks.bench_table2_gpt2",     # Tables 2 & 4
    "benchmarks.bench_table3_lra",      # Table 3 (+ Fig. 3 memory)
    "benchmarks.bench_table7_kernel",   # Table 7
    "benchmarks.bench_attention_sweep", # Tables 9-21 (+ layout skip rates)
    "benchmarks.bench_io_model",        # Theorem 2 / Props. 3-4
    "benchmarks.bench_serve_throughput",  # paged vs dense KV cache serving
]

SMOKE_MODULES = [
    "benchmarks.bench_attention_sweep",
    "benchmarks.bench_io_model",
    "benchmarks.bench_serve_throughput",
]


def _next_results_path(results_dir: str) -> str:
    """BENCH_<n>.json with the next free index (trajectory across PRs)."""
    os.makedirs(results_dir, exist_ok=True)
    taken = [int(m.group(1)) for f in os.listdir(results_dir)
             if (m := re.fullmatch(r"BENCH_(\d+)\.json", f))]
    return os.path.join(results_dir, f"BENCH_{max(taken, default=-1) + 1}.json")


def _units_of(name: str) -> str:
    """Benchmarks encode units in the row name suffix (``_us``, ``_MB``,
    ...); everything else is a dimensionless ratio/count."""
    m = re.search(r"_(us|ms|s|MB|GB|bytes|toks|frac|pct|x)$", name)
    return m.group(1) if m else "ratio"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="cheap CI subset with reduced sweep sizes")
    ap.add_argument("--results-dir", default=RESULTS_DIR,
                    help="where BENCH_<n>.json lands")
    args = ap.parse_args()
    enable_compile_cache()
    modules = SMOKE_MODULES if args.smoke else MODULES
    if args.only:
        modules = [m for m in modules if args.only in m]
        if not modules:
            pool = "SMOKE_MODULES" if args.smoke else "MODULES"
            print(f"--only {args.only!r} matches nothing in {pool}",
                  file=sys.stderr)
            raise SystemExit(1)
    print("name,us_per_call,derived")
    failed = []
    records = []
    for mod_name in modules:
        try:
            mod = importlib.import_module(mod_name)
            kwargs = {}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            for name, val, derived in mod.run(**kwargs):
                print(f"{name},{val:.6g},{derived}")
                records.append({"name": name, "value": float(val),
                                "units": _units_of(name),
                                "derived": str(derived),
                                "module": mod_name})
            sys.stdout.flush()
        except Exception:
            traceback.print_exc()
            failed.append(mod_name)
    path = _next_results_path(args.results_dir)
    with open(path, "w") as f:
        json.dump({"version": 1,
                   "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
                   "argv": sys.argv[1:], "smoke": args.smoke,
                   "failed_modules": failed, "benches": records}, f,
                  indent=1)
    print(f"wrote {path} ({len(records)} rows)", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
