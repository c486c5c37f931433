"""A whole sweep run on two virtual CPU devices at test size: the sound
program comes out correct, and each fault a sweep cell can have (an
answer altered, half of the batch left out, the other device's rows left
out of the gather) comes out not correct."""
from __future__ import annotations

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
