"""Helpers for whole benchmark runs on the CPU at test size
(``bench/tests/data/tiny``, with the fixtures of ``data/hooks``). Each
run is its own process, as on the chip, with JAX's compile cache in a
temporary directory."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
TINY = BENCH / "tests" / "data" / "tiny"
HOOKS = BENCH / "tests" / "data" / "hooks"
SEED = "3000000019"                # past 32 signed bits


def tiny_root(tmp_path_factory) -> Path:
    """A checkout-like root holding the tiny cells and the real readers."""
    r = tmp_path_factory.mktemp("tiny")
    shutil.copytree(TINY, r, dirs_exist_ok=True)
    shutil.copytree(BENCH / "metrics", r / "bench" / "metrics")
    return r


def hooks_root(tmp_path_factory) -> Path:
    """``tiny_root`` with the fixtures of ``bench/tests/data/hooks``: the
    references in ``bench/references/``, each named by a configuration
    ``tiny16_homa_<ref>`` with a cell ``homa_tiny_<ref>`` (``missing``
    names a file that is not there); the kind in ``bench/kinds/``, with
    the mix ``tiny_shift`` and its cell ``homa_tiny_shift``; and the mix
    ``tiny_nokind`` of a kind without a file, cell ``homa_tiny_nokind``."""
    r = tiny_root(tmp_path_factory)
    for d in ("references", "kinds"):
        shutil.copytree(HOOKS / d, r / "bench" / d)
    bench = json.loads((r / "BENCHMARK.json").read_text())
    base = json.loads((r / "bench/configs/tiny16_homa.json").read_text())
    refs = [p.stem for p in sorted((HOOKS / "references").glob("*.py"))]
    for ref in refs + ["missing"]:
        name = f"tiny16_homa_{ref}"
        path = f"bench/configs/{name}.json"
        (r / path).write_text(json.dumps(
            {**base, "reference": f"bench/references/{ref}.py"}))
        bench["configs"].append({**bench["configs"][0], "name": name,
                                 "file": path})
        bench["workloads"].append({"name": f"homa_tiny_{ref}",
                                   "config": name, "traffic": "tiny_single",
                                   "chips": 1, "why": "test"})
    mix = json.loads((r / "bench/traffic/tiny_single.json").read_text())
    for kind in ("shift", "nokind"):
        (r / f"bench/traffic/tiny_{kind}.json").write_text(
            json.dumps({**mix, "kind": kind}))
        bench["workloads"].append({"name": f"homa_tiny_{kind}",
                                   "config": "tiny16_homa",
                                   "traffic": f"tiny_{kind}", "chips": 1,
                                   "why": "test"})
    (r / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return r


def launch(root: Path, workload: str, fault: str = "none",
           trace: str = "0", devices: int = 1):
    """The finished process of one run (``drive.py``)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(root / "jax_cache"),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.run([sys.executable,
                           str(BENCH / "tests" / "drive.py"), str(root),
                           workload, SEED, "1", trace, fault],
                          env=env, capture_output=True, text=True,
                          timeout=600, cwd=REPO)


def drive(root: Path, workload: str, fault: str = "none",
          trace: str = "0", devices: int = 1):
    """Result line and standard error of one run (``drive.py``)."""
    p = launch(root, workload, fault, trace, devices)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr
