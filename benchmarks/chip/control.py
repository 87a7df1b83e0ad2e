"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload gpt2-small.train-4k \\
        --seeds 11,12,13 --seconds 3 [--faults]

For each seed, in one process: one run of the cell at its own size and
load (``--seconds`` of window), the program's numbers against the float32
reference, and the control's: the reference computed one precision below
the configuration's (``control_precision`` in its file), judged against the
cell's limits as a run's own numbers are. With ``--faults``,
each planted fault of ``chipbench/faults.py`` that the cell can have is run
too. One JSON line per seed and reading goes to standard output. The
benchmark's own runs never run this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args()

    import jax
    from chipbench import check as chk
    from chipbench import faults, manifest
    from chipbench.peaks import peaks_for
    from repro.launch.compile_cache import enable_compile_cache

    d = jax.devices()[0]
    if d.platform != "tpu":
        bench.fail("JAX found no TPU; nothing was run")
    peaks = peaks_for(d.device_kind)
    enable_compile_cache()
    cell = manifest.load_cell(args.workload)
    low = cell.config["control_precision"]
    planted = faults.SERVE if cell.mix["kind"] == "serve" else faults.TRAIN
    for seed in (int(s) for s in args.seeds.split(",")):
        out = bench.run_cell(cell, seed, args.seconds, False, peaks, control=low)
        print(json.dumps({"seed": seed, "reading": "program",
                          "numbers": out["notes"]["readings"],
                          "correct": out["correct"], "failed": out["failed"],
                          "attempted": out["attempted"]}), flush=True)
        ctrl_ok, _ = chk.judge(out["check_low"] or {}, cell.limits)
        print(json.dumps({"seed": seed, "reading": f"control_{low}",
                          "numbers": out["check_low"],
                          "correct": ctrl_ok}), flush=True)
        if args.faults:
            for name, fault in planted.items():
                with fault():
                    f = bench.run_cell(cell, seed, args.seconds, False, peaks)
                print(json.dumps({"seed": seed, "reading": f"fault_{name}",
                                  "numbers": f["notes"]["readings"],
                                  "correct": f["correct"]}), flush=True)


if __name__ == "__main__":
    main()
