"""Helpers for whole benchmark runs on the CPU at test size
(``bench/tests/data/tiny``, with the fixtures of ``data/hooks`` and
``data/addition``). Each run is its own process, as on the chip, with
JAX's compile cache in a temporary directory."""
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
ADDITION = BENCH / "tests" / "data" / "addition"
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


# What ``addition_root`` appends to each list of ``BENCHMARK.json``.
ADDED = {"configs": ["dry16_homa_torfail", "dry16_homa"],
         "workloads": ["homa_dry_torfail", "homa_dry"],
         "per_layer": ["dry_call_ms"]}
# Rack 0's TOR dark for slots [512, 1024), with the paper's section 3.7
# recovery timers, as a configuration file states it.
TOR_FAULTS = {"tor_fail": [[0, 512, 1024]], "resend_slots": 300,
              "sender_timeout_slots": 760}


def addition_root(tmp_path_factory) -> Path:
    """A copy of the real ``BENCHMARK.json`` and ``bench/`` with one
    deployment added as a ``model_config`` change adds it: new files and
    entries appended to ``BENCHMARK.json``, nothing else edited. The
    configuration ``dry16_homa_torfail`` (the tiny 16-host Homa with a
    ``fabric.faults`` block: rack 0's TOR fails) names its own reference,
    ``bench/references/dry_same.py`` (the fixture ``same``: a re-export
    of ``bench/reference.py``, which models no fault); its fault-free
    twin ``dry16_homa`` names the same file and is the one a whole run
    can judge. Each has a cell (``homa_dry_torfail``, ``homa_dry``) on
    the new mix ``dry_single``, and the new reader
    ``bench/metrics/dry_call_ms.py`` is listed for those two cells
    alone."""
    r = tmp_path_factory.mktemp("addition")
    shutil.copy(REPO / "BENCHMARK.json", r)
    shutil.copytree(BENCH, r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (r / "bench" / "references").mkdir(exist_ok=True)
    shutil.copy(HOOKS / "references" / "same.py",
                r / "bench" / "references" / "dry_same.py")
    shutil.copy(ADDITION / "metrics" / "dry_call_ms.py",
                r / "bench" / "metrics" / "dry_call_ms.py")
    shutil.copy(TINY / "bench" / "traffic" / "tiny_single.json",
                r / "bench" / "traffic" / "dry_single.json")
    base = json.loads((TINY / "bench/configs/tiny16_homa.json").read_text())
    twin = {**base, "reference": "bench/references/dry_same.py"}
    confs = {"dry16_homa_torfail": {**twin, "fabric": {
        **base["fabric"], "faults": TOR_FAULTS}}, "dry16_homa": twin}
    bench = json.loads((r / "BENCHMARK.json").read_text())
    for name in ADDED["configs"]:
        path = f"bench/configs/{name}.json"
        (r / path).write_text(json.dumps(confs[name], indent=1))
        bench["configs"].append({
            "name": name, "source": "https://arxiv.org/abs/1803.09615",
            "file": path, "reduced": base["reduced"], "why": "test"})
    for name, conf in zip(ADDED["workloads"], ADDED["configs"]):
        bench["workloads"].append({"name": name, "config": conf,
                                   "traffic": "dry_single", "chips": 1,
                                   "why": "test"})
    bench["per_layer"].append({
        "name": "dry_call_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "entry (host)",
        "moves": "run_slots_per_s", "workloads": ADDED["workloads"]})
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
