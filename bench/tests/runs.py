"""Helpers for whole benchmark runs on the CPU at test size
(``bench/tests/data/tiny``). Each run is its own process, as on the chip,
with JAX's compile cache in a temporary directory."""
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
SEED = "3000000019"                # past 32 signed bits


def tiny_root(tmp_path_factory) -> Path:
    """A checkout-like root holding the tiny cells and the real readers."""
    r = tmp_path_factory.mktemp("tiny")
    shutil.copytree(TINY, r, dirs_exist_ok=True)
    shutil.copytree(BENCH / "metrics", r / "bench" / "metrics")
    return r


def drive(root: Path, workload: str, fault: str = "none",
          trace: str = "0", devices: int = 1):
    """Result line and standard error of one run (``drive.py``)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(root / "jax_cache"),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    p = subprocess.run([sys.executable, str(BENCH / "tests" / "drive.py"),
                        str(root), workload, SEED, "1", trace, fault],
                       env=env, capture_output=True, text=True, timeout=600,
                       cwd=REPO)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr
