"""Find a cell's pieces by name: ``BENCHMARK.json`` at the root of the
checkout names the cell, its configuration file and its traffic mix;
``bench/traffic/<mix>.json`` holds the mix and ``bench/metrics/<name>.py``
the reader of each per-layer metric. A configuration may name the plain
reference that decides ``correct`` for it (its ``"reference"`` key, a
path from the root of the checkout; ``bench/reference.py`` without it),
and a mix of a kind other than ``poisson`` is drawn by
``bench/kinds/<kind>.py`` (``bench/gen.py``). Adding a cell, a mix, a
kind, a reference or a metric adds files and entries; nothing here
changes."""
from __future__ import annotations

import functools
import hashlib
import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
REFERENCE_FUNCTIONS = ("priority_allocation", "simulate", "slowdown_hist")
KIND = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_-]{0,63}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@functools.cache
def _exec(path: Path):
    """The module at ``path``, registered in ``sys.modules`` while and
    after it runs, under a name of its own path, so that what looks its
    module up there (a dataclass under ``from __future__ import
    annotations``, pickle) finds it."""
    digest = hashlib.sha256(bytes(path)).hexdigest()[:12]
    name = f"bench_{path.stem}_{digest}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def load_module(path: Path, needs: tuple[str, ...]):
    """The module in the file at ``path``, loaded once per process, which
    has to define the functions ``needs``. A missing file or function
    stops the run with the path in the message; nothing falls back."""
    path = Path(path).resolve()
    if not path.is_file():
        raise SystemExit(f"{path} does not exist")
    mod = _exec(path)
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"{path} lacks {', '.join(missing)}")
    return mod


def reference_module(config: dict, root: Path = ROOT):
    """The plain reference of a configuration: the module at its
    ``"reference"`` path under ``root``, or ``bench/reference.py``. It
    has ``bench/reference.py``'s ``priority_allocation``, ``simulate``
    (with the ``strict_priority`` keyword of the control) and
    ``slowdown_hist``, and imports nothing of the program."""
    rel = config.get("reference")
    if rel is None:
        from bench import reference
        return reference
    if Path(rel).is_absolute() or ".." in Path(rel).parts:
        raise SystemExit(f"reference {rel!r} is not a path inside the "
                         f"checkout")
    return load_module(Path(root) / rel, REFERENCE_FUNCTIONS)


def kind_table(kind: str, root: Path = ROOT):
    """The ``table(mix, n_hosts, slot_bytes, gen)`` function of
    ``bench/kinds/<kind>.py`` under ``root``."""
    if not KIND.match(kind):
        raise SystemExit(f"traffic kind {kind!r} is not a name")
    return load_module(Path(root) / "bench" / "kinds" / f"{kind}.py",
                       ("table",)).table


def cell(name: str, root: Path = ROOT) -> dict:
    """Everything one run of workload ``name`` needs: the workload entry,
    its configuration file and mix, the table generator of the mix, the
    plain reference of the configuration, and the metrics it reports."""
    from bench import gen
    bench = benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(work)}")
    w = work[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    config = load_json(root / conf["file"])
    mix = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return {"workload": w, "config_entry": conf, "config": config,
            "mix": mix, "table": gen.table_fn(mix, root),
            "reference": reference_module(config, root),
            "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       ("read",)).read
