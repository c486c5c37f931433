"""Whole single-run benchmark runs on the CPU at test size: the sound
program comes out correct, an altered answer and the control come out
not correct, and a run without a chip or without the program prints no
result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.runs import BENCH, REPO, SEED, drive, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory)


@pytest.mark.parametrize("workload", ["homa_tiny", "pfabric_tiny"])
def test_sound_single_run_is_correct(root, workload):
    res, err = drive(root, workload)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["completion_mismatch"] == {"value": 0, "limit": 0}
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert set(res["metrics"]) == {"run_slots_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith(
        "[bench] check compiles_in_window: 0 (limit 0)")


@pytest.mark.parametrize("workload", ["homa_tiny", "pfabric_tiny"])
def test_answer_altered_is_not_correct(root, workload):
    res, _ = drive(root, workload, "answer")
    assert res["correct"] is False
    assert res["checks"]["completion_mismatch"]["value"] >= 1
    assert res["failed"] == 1


@pytest.mark.parametrize("workload", ["homa_tiny", "pfabric_tiny"])
def test_control_is_not_correct(root, workload):
    """The reference with strict priority switched off, in the program's
    place, fails the comparison."""
    res, _ = drive(root, workload, "control")
    assert res["correct"] is False
    assert res["checks"]["completion_mismatch"]["value"] >= 1


def test_traced_run_reports_per_layer_metrics(root):
    """On the CPU the trace holds no device plane: the device metrics
    find nothing to read and are left out, never reported as 0."""
    res, _ = drive(root, "homa_tiny", trace="1")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"prepare_ms"}
    assert res["metrics"]["prepare_ms"]["value"] > 0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_exits_nonzero_without_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "homa_w4", "--seed", SEED, "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300, cwd=REPO)
    assert p.returncode != 0
    lines = p.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith("{")
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ cannot run."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "homa_w4", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env={**env, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300,
                       cwd=tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
