"""Checks of a checkout's ``BENCHMARK.json`` and the files it names, each
taking the root of the checkout. The tests run them on the real tree
and on a copy of it with a deployment added as files
(``runs.addition_root``), so that what holds for the cells there holds
for an added one too."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

from bench import cells
from bench.tests.runs import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
EMPTY = {"trace": {"summary": {"module_s": {}, "idle_share_by_device": {},
                               "busy_s_by_device": {}}},
         "record": {"prepare_s": [], "entry": "simulate", "runs": 0,
                    "slots_per_run": 8192}}


def names_units_and_entries(root: Path) -> None:
    bench = cells.benchmark(root)
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names.append(w["name"])
    assert {c["name"] for c in bench["configs"]} \
        == {w["config"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 2)


def cell_pieces_are_found_by_name(root: Path, workload: str) -> None:
    c = cells.cell(workload, root)
    assert c["mix"]["entry"] in ("simulate", "run_sweep")
    assert {m["name"] for m in c["end_to_end"]} >= {"run_slots_per_s",
                                                    "setup_s"}
    assert "backend" not in c["config"]["sim"]
    assert "backend" not in json.dumps(c["mix"])
    for m in c["per_layer"]:
        assert callable(cells.metric_reader(m["name"], root))


def reader_with_nothing_to_read_returns_none(root: Path,
                                             metric: str) -> None:
    assert cells.metric_reader(metric, root)(EMPTY) is None


def config_resolves_to_its_reference(root: Path, config: Path) -> None:
    """A configuration without a ``"reference"`` key is judged by
    ``bench/reference.py``; one with the key by that file under
    ``root``, which stays inside the checkout and defines
    ``cells.REFERENCE_FUNCTIONS``."""
    conf = cells.load_json(config)
    mod = cells.reference_module(conf, root)
    got = Path(mod.__file__).resolve()
    if "reference" not in conf:
        assert got == cells.BENCH / "reference.py"
        return
    assert got == (root / conf["reference"]).resolve()
    assert got.is_relative_to(root.resolve())
    assert all(callable(getattr(mod, f, None))
               for f in cells.REFERENCE_FUNCTIONS)


def repro_modules(config: Path, root: Path) -> list[str]:
    """The modules of the program loaded by loading the configuration's
    reference in a fresh process."""
    code = ("import json, sys; from pathlib import Path; "
            "from bench import cells; "
            "cells.reference_module(json.load(open(sys.argv[1])), "
            "Path(sys.argv[2])); "
            "print(json.dumps(sorted(m for m in sys.modules "
            "if m.split('.')[0] == 'repro')))")
    p = subprocess.run([sys.executable, "-c", code, str(config), str(root)],
                       capture_output=True, text=True, timeout=300,
                       cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu",
                                      "PYTHONPATH": str(REPO / "src")})
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def reference_imports_nothing_of_the_program(root: Path,
                                            config: Path) -> None:
    assert repro_modules(config, root) == []
