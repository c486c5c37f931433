"""A whole sweep run on two, and on four, virtual CPU devices at test
size: the sound program comes out correct, and each fault a sweep cell
can have (an answer altered, half of the batch left out, the other
devices' rows left out of the gather), and on four devices the control,
come out not correct."""
from __future__ import annotations

import json

import pytest

from bench.tests.runs import drive, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory)


def test_sweep_on_two_devices(root):
    res, _ = drive(root, "homa_tiny_sweep", devices=2)
    assert res["correct"] is True, res
    assert res["checks"]["hist_off"]["value"] == 0
    assert res["checks"]["n_complete_off"]["value"] == 0
    assert res["attempted"] % 4 == 0


@pytest.mark.parametrize("fault", ["answer", "half", "exchange"])
def test_sweep_faults_are_not_correct(root, fault):
    res, _ = drive(root, "homa_tiny_sweep", fault, devices=2)
    assert res["correct"] is False
    assert res["checks"]["hist_off"]["value"] >= 1


@pytest.fixture(scope="module")
def root4(tmp_path_factory):
    """The tiny root with ``homa_tiny_sweep4``: the shape of the
    ``homa_w4_sweep4`` cell (8 runs a call, sharded over 4 devices) at
    test size."""
    r = tiny_root(tmp_path_factory)
    mix = json.loads((r / "bench/traffic/tiny_sweep.json").read_text())
    (r / "bench/traffic/tiny_sweep8x4.json").write_text(
        json.dumps({**mix, "runs_per_call": 8, "shard": 4}))
    bench = json.loads((r / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "homa_tiny_sweep4",
                               "config": "tiny16_homa",
                               "traffic": "tiny_sweep8x4", "chips": 4,
                               "why": "test"})
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    return r


@pytest.mark.parametrize("fault", ["none", "answer", "half", "exchange",
                                   "control"])
def test_sweep_on_four_devices(root4, fault):
    res, _ = drive(root4, "homa_tiny_sweep4", fault, devices=4)
    assert res["attempted"] % 8 == 0 and res["device"]["count"] == 4
    assert res["correct"] is (fault == "none")
    assert (res["checks"]["hist_off"]["value"] == 0) is (fault == "none")
