"""Find the knee of an open-loop serving cell: run it at a list of fixed
arrival rates, in one process, and print per rate the cell's end-to-end
metrics and the queue left at the window's end (a queue that grows through the
window means the rate is past what the system sustains).

    python3 benchmarks/chip/sweep.py --workload granite-3-2b.chat \\
        --rates 1.5,2,2.5,3 --seconds 30 --seed 5

The cell's rate is then written into its mix file by hand, at about four
fifths of the highest sustained rate. The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402  (puts src/ on the path)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()

    import jax
    from chipbench import manifest
    from chipbench.peaks import peaks_for
    from repro.launch.compile_cache import enable_compile_cache

    d = jax.devices()[0]
    if d.platform != "tpu":
        bench.fail("JAX found no TPU; nothing was run")
    peaks = peaks_for(d.device_kind)
    enable_compile_cache()
    cell = manifest.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix["arrivals"]["rate_per_s"] = rate
        out = bench.run_cell(cell, args.seed, args.seconds, False, peaks)
        print(json.dumps({"rate_per_s": rate, "metrics": {
            k: v["value"] for k, v in out["metrics"].items()},
            "attempted": out["attempted"], "failed": out["failed"],
            "queued_at_window_end": out["notes"].get("queued_at_window_end"),
            "correct": out["correct"]}), flush=True)


if __name__ == "__main__":
    main()
