"""``BENCHMARK.json`` and the files it names: every cell's configuration,
mix and metric readers are found by name, the file keeps to the
benchmark's format, and the traffic generator draws what the repository's
own generator draws for the same seed."""
from __future__ import annotations

import numpy as np
import pytest

from bench import cells, gen
from bench.tests import checks

BENCH = cells.benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((cells.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_entries():
    checks.names_units_and_entries(cells.ROOT)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_pieces_are_found_by_name(workload):
    checks.cell_pieces_are_found_by_name(cells.ROOT, workload)


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="unknown workload"):
        cells.cell("no_such_cell")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_reader_with_nothing_to_read_returns_none(metric):
    checks.reader_with_nothing_to_read_returns_none(cells.ROOT, metric)


def test_readers_compute_their_metrics():
    run = {"trace": {"summary": {
        "module_s": {"jit__run(12)": 2.0, "jit__run": 1.0,
                     "jit_other": 5.0},
        "idle_share_by_device": {"/device:TPU:0": 0.1,
                                 "/device:TPU:1": 0.3},
        "busy_s_by_device": {"/device:TPU:0": 9.0, "/device:TPU:1": 7.0}}},
        "record": {"prepare_s": [0.01, 0.03], "entry": "simulate",
                   "runs": 3, "slots_per_run": 1000}}
    assert cells.metric_reader("prepare_ms")(run) == pytest.approx(20.0)
    assert cells.metric_reader("scan_us_per_slot")(run) == \
        pytest.approx(1000.0)
    assert cells.metric_reader("device_idle_share")(run) == 0.3


def test_device_trace_without_the_scan_program_raises():
    """A trace with devices but no module of the scan's name fails the
    run rather than leaving the metric out."""
    run = {"trace": {"summary": {
        "module_s": {"jit_other": 5.0},
        "idle_share_by_device": {"/device:TPU:0": 0.1},
        "busy_s_by_device": {"/device:TPU:0": 9.0}}},
        "record": {"prepare_s": [], "entry": "run_sweep", "runs": 8,
                   "slots_per_run": 1000}}
    with pytest.raises(RuntimeError, match="jit__sweep_batch"):
        cells.metric_reader("scan_us_per_slot")(run)


@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 + 11])
def test_generator_matches_repository_generator(seed):
    """Drawn with the repository's own stream, the copy gives the table
    ``make_messages`` gives."""
    from repro.core import make_messages
    mix = cells.load_json(cells.BENCH / "traffic" / "w4_load80_single.json")
    mix = {**mix, "n_messages": 500}
    got = gen.poisson_table(mix, 144, 256, np.random.default_rng(seed))
    want = make_messages("W4", n_hosts=144, load=0.8, n_messages=500,
                         slot_bytes=256, seed=seed)
    for k, w in (("src", want.src), ("dst", want.dst), ("size", want.size),
                 ("arrival", want.arrival_slot)):
        np.testing.assert_array_equal(got[k], w)


def test_same_seed_same_tables_and_calls_differ():
    mix = cells.load_json(cells.BENCH / "traffic" / "w4_load80_sweep8x4.json")
    a = gen.call_tables(mix, 144, 256, 2 ** 31 + 3, 0)
    b = gen.call_tables(mix, 144, 256, 2 ** 31 + 3, 0)
    c = gen.call_tables(mix, 144, 256, 2 ** 31 + 3, 1)
    assert len(a) == mix["runs_per_call"]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["arrival"], y["arrival"])
    assert not np.array_equal(a[0]["size"], a[1]["size"])
    assert not np.array_equal(a[0]["size"], c[0]["size"])
