"""A deployment added only as files and appended ``BENCHMARK.json``
entries (PERF.md §4, "Adding a deployment") passes every check that the
real tree passes, and runs end to end. ``runs.addition_root`` copies the
real ``BENCHMARK.json`` and ``bench/`` and adds a configuration that
names its own reference and states a TOR failure, its fault-free twin,
a mix, a cell for each, and a reader listed for those cells alone."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import cells, entries
from bench.tests import checks
from bench.tests.runs import ADDED, REPO, TOR_FAULTS, addition_root, drive

REAL = cells.benchmark()
CONFIGS = sorted(p.name for p in (cells.BENCH / "configs").glob("*.json")) \
    + [f"{n}.json" for n in ADDED["configs"]]
WORKLOADS = [w["name"] for w in REAL["workloads"]] + ADDED["workloads"]
METRICS = [m["name"] for m in REAL["per_layer"]] + ADDED["per_layer"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return addition_root(tmp_path_factory)


def _files(d: Path) -> dict[str, bytes]:
    return {str(p.relative_to(d)): p.read_bytes() for p in d.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_addition_only_appends(root):
    """The copy's ``BENCHMARK.json`` is the real one with entries
    appended, and its ``bench/`` the real one with files added."""
    got = cells.benchmark(root)
    assert set(got) == set(REAL)
    for key, real in REAL.items():
        if key in ADDED:
            assert got[key][:len(real)] == real
            assert [e["name"] for e in got[key][len(real):]] == ADDED[key]
        else:
            assert got[key] == real
    real_files, files = _files(REPO / "bench"), _files(root / "bench")
    assert {k: files[k] for k in real_files} == real_files
    assert sorted(set(files) - set(real_files)) == sorted(
        [f"configs/{n}.json" for n in ADDED["configs"]]
        + ["metrics/dry_call_ms.py", "references/dry_same.py",
           "traffic/dry_single.json"])
    for n in ADDED["configs"]:
        conf = cells.load_json(root / "bench" / "configs" / f"{n}.json")
        assert conf["reference"] == "bench/references/dry_same.py"


def test_names_units_and_entries_with_an_addition(root):
    checks.names_units_and_entries(root)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_pieces_are_found_by_name_with_an_addition(root, workload):
    checks.cell_pieces_are_found_by_name(root, workload)


@pytest.mark.parametrize("metric", METRICS)
def test_reader_with_nothing_to_read_returns_none_with_an_addition(
        root, metric):
    checks.reader_with_nothing_to_read_returns_none(root, metric)


@pytest.mark.parametrize("name", CONFIGS, ids=lambda n: n[:-5])
def test_configs_resolve_to_their_reference_with_an_addition(root, name):
    checks.config_resolves_to_its_reference(
        root, root / "bench" / "configs" / name)


@pytest.mark.parametrize("name", CONFIGS, ids=lambda n: n[:-5])
def test_references_import_nothing_of_the_program_with_an_addition(
        root, name):
    checks.reference_imports_nothing_of_the_program(
        root, root / "bench" / "configs" / name)


def test_fault_block_reaches_the_program(root):
    """The ``fabric.faults`` block, lists as JSON holds them, becomes the
    program's fault layer with its windows as tuples; the twin has
    none."""
    c = cells.cell("homa_dry_torfail", root)
    raw = json.loads((root / "bench/configs/dry16_homa_torfail.json")
                     .read_text())["fabric"]["faults"]
    assert raw["tor_fail"] == [[0, 512, 1024]]
    cfg = entries.sim_config(c["config"], c["mix"])
    assert cfg.faults_on
    faults = cfg.fabric.faults
    assert faults.tor_fail == ((0, 512, 1024),)
    assert all(type(w) is tuple for w in faults.tor_fail)
    assert faults.resend_slots == TOR_FAULTS["resend_slots"] == 300
    assert faults.sender_timeout_slots \
        == TOR_FAULTS["sender_timeout_slots"] == 760
    assert faults.link_fail == () and faults.up_loss == 0.0
    twin = cells.cell("homa_dry", root)
    assert not entries.sim_config(twin["config"], twin["mix"]).faults_on


def test_whole_run_of_the_added_cell(root):
    """A traced CPU run of the added cell is judged by the reference its
    configuration names, comes out correct, and reports the new
    reader's metric."""
    res, _ = drive(root, "homa_dry", trace="1")
    assert res["correct"] is True and res["attempted"] >= 1
    assert res["checks"]["completion_mismatch"] == {"value": 0, "limit": 0}
    assert res["metrics"]["dry_call_ms"]["unit"] == "ms"
    assert res["metrics"]["dry_call_ms"]["value"] > 0
