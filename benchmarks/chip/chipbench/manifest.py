"""Find a cell's configuration, traffic mix, limits and per-layer metric
readers by the names in ``BENCHMARK.json``.

Layout under ``benchmarks/chip/``:

* ``configs/<config>.json``  - the configuration as it is run;
* ``traffic/<traffic>.json`` - the mix's parameters, read by
  ``chipbench.traffic`` (serving) or ``chipbench.train_cell`` (training);
* ``limits/<cell>.json``     - the limits of the numbers ``correct`` compares;
* ``metrics/<name>.py``      - one reader per metric, looked up by
  the metric's full name first and then by the part before its first dot
  (``idle_share.chat`` -> ``metrics/idle_share.py``).

A new configuration, mix or metric is new files plus manifest entries; no
existing file changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list[dict]     # manifest entries this cell reports
    per_layer: list[dict]


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:                    # per-layer: follows its e2e metric
        return metric["moves"] in e2e_names
    return True


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    manifest_path = os.path.join(root, "BENCHMARK.json")
    bench_dir = os.path.join(root, "benchmarks", "chip")
    man = _load_json(manifest_path)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {manifest_path}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    mix = _load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    limits = _load_json(os.path.join(bench_dir, "limits", name + ".json"))
    e2e = [m for m in man["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                limits=limits, end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of metric ``name``."""
    base = name.split(".", 1)[0]
    for stem in (name, base):
        path = os.path.join(root, "benchmarks", "chip", "metrics", stem + ".py")
        if os.path.exists(path):
            mod_name = "chipbench_metric_" + stem.replace(".", "_").replace("-", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader metrics/{name}.py or metrics/{base}.py")
