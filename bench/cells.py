"""Find a cell's pieces by name: ``BENCHMARK.json`` at the root of the
checkout names the cell, its configuration file and its traffic mix;
``bench/traffic/<mix>.json`` holds the mix and ``bench/metrics/<name>.py``
the reader of each per-layer metric. Adding a cell, a mix or a metric
adds files and entries; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """Everything one run of workload ``name`` needs: the workload entry,
    its configuration file and mix, and the metrics it reports."""
    bench = benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(work)}")
    w = work[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    return {"workload": w, "config_entry": conf,
            "config": load_json(root / conf["file"]),
            "mix": load_json(root / "bench" / "traffic"
                             / f"{w['traffic']}.json"),
            "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
