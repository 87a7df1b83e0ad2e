"""A configuration, a traffic mix and a per-layer metric are added as new
files plus manifest entries, without editing any file that is there."""

import hashlib
import json
import os
import shutil

import bench_tiny
from chipbench import manifest


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(bench_tiny.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(bench)

    cfg = json.loads((bench / "configs" / "granite-3-2b.json").read_text())
    (bench / "configs" / "granite-3-2b-short.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "chat.json").read_text())
    mix["prompt_len"].update(median=128, max=256)
    (bench / "traffic" / "chat-short.json").write_text(json.dumps(mix))
    (bench / "limits" / "granite-3-2b-short.chat-short.json").write_text(
        json.dumps({"max_logit_gap": {"limit": 1.0}}))
    (bench / "metrics" / "requests_in_window.py").write_text(
        "def read(run):\n    return float(run.attempted)\n")

    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    man["configs"].append(dict(man["configs"][0], name="granite-3-2b-short",
                               file="benchmarks/chip/configs/granite-3-2b-short.json"))
    man["workloads"].append({"name": "granite-3-2b-short.chat-short",
                             "config": "granite-3-2b-short",
                             "traffic": "chat-short", "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "requests_in_window.short", "unit": "1",
                             "better": "higher", "source": "host_clock",
                             "layer": "load generator", "moves": "itl_p50_ms",
                             "workloads": ["granite-3-2b-short.chat-short"]})
    for m in man["end_to_end"]:
        if "workloads" in m and "granite-3-2b.chat" in m["workloads"]:
            m["workloads"].append("granite-3-2b-short.chat-short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    cell = manifest.load_cell("granite-3-2b-short.chat-short", root=str(tmp_path))
    assert cell.mix["prompt_len"]["max"] == 256
    assert [m["name"] for m in cell.per_layer] == ["requests_in_window.short"]
    assert {m["name"] for m in cell.end_to_end} == {"itl_p50_ms", "itl_p99_ms", "setup_s"}
    read = manifest.metric_reader("requests_in_window.short", root=str(tmp_path))

    class R:
        attempted = 7
    assert read(R()) == 7.0
    after = digest(bench)
    assert {k: after[k] for k in before} == before


def test_every_manifest_metric_has_a_reader():
    man = json.load(open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")))
    for m in man["end_to_end"] + man["per_layer"]:
        assert callable(manifest.metric_reader(m["name"]))
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert cell.limits and cell.end_to_end and cell.per_layer
