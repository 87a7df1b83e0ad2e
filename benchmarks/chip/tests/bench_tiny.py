"""Tiny versions of the benchmark's cells for CPU tests: the manifest's
cells with the model cut to two layers of width 64 and the traffic cut to
a few short requests or a few small steps. Nothing here is a measurement."""

from __future__ import annotations

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import importlib.util  # noqa: E402

from chipbench import manifest  # noqa: E402


def runner():
    """``benchmarks/chip/run.py`` as a module (under a name of its own)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

TINY_MODEL = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  head_dim=16, d_ff=128, vocab_size=256)
FAKE_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def tiny_cell(name: str):
    """The manifest cell ``name`` at test size."""
    cell = copy.deepcopy(manifest.load_cell(name))
    over = dict(TINY_MODEL)
    if cell.config["program"]["arch"] == "gpt2-small":
        over.update(num_heads=4, num_kv_heads=4)
    cell.config["program"]["overrides"] = dict(
        cell.config["program"].get("overrides", {}), **over)
    ref = cell.config["reference"]
    ref.update(layers=over["num_layers"], d_model=over["d_model"],
               heads=over["num_heads"], kv_heads=over["num_kv_heads"],
               head_dim=over["head_dim"], d_ff=over["d_ff"],
               vocab=over["vocab_size"])
    mix = cell.mix
    if mix["kind"] == "serve":
        mix["engine"].update(lanes=4, capacity=256, chunk_size=64,
                             prefill_bucket=64, num_pages=None,
                             warm_max_kv=256, warm_max_rows=3)
        for key in ("prompt_len", "output_len"):
            spec = mix[key]
            spec.update(min=4, max=40 if key == "prompt_len" else 8)
            if "median" in spec:
                spec["median"] = 16 if key == "prompt_len" else 4
        mix["arrivals"].update(rate_per_s=20.0, warmup_s=0.2, tail_s=0.5)
        mix["drain_cap_s"] = 20
        mix["trace_s"] = 0.3
        mix["check"] = {"served_tokens": 16, "max_requests": 2}
    else:
        mix.update(seq_len=64, tokens_per_step=256, mean_doc_len=32,
                   trace_s=0.3)
    return cell
