"""The two hooks by which a deployment is added as files: a configuration
names the plain reference that decides ``correct`` for it (``bench/
reference.py`` when it names none), and a mix of a kind other than
``poisson`` is drawn by ``bench/kinds/<kind>.py``. A reference or kind
that cannot be loaded stops the run; nothing falls back. The Poisson
mixes draw the tables they drew before the hooks."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import cells, entries, gen
from bench.tests import checks
from bench.tests.runs import SEED, drive, hooks_root, launch

CONFIGS = sorted((cells.BENCH / "configs").glob("*.json"))
MIXES = ["w4_load80_single", "w4_load80_sweep8x4"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return hooks_root(tmp_path_factory)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_configs_resolve_to_the_default_reference(path):
    """Each configuration resolves to its reference: ``bench/
    reference.py`` without a ``"reference"`` key, the named file with
    one."""
    checks.config_resolves_to_its_reference(cells.ROOT, path)


@pytest.mark.parametrize("ref, correct", [("same", True),
                                          ("strict_off", False)])
def test_named_reference_decides_correct(root, ref, correct):
    """A whole run of the sound program is judged by the reference its
    configuration names: one that re-exports ``bench/reference.py`` finds
    it correct, one that answers with strict priority off does not."""
    c = cells.cell(f"homa_tiny_{ref}", root)
    assert Path(c["reference"].__file__).resolve() == (
        root / "bench" / "references" / f"{ref}.py").resolve()
    res, _ = drive(root, f"homa_tiny_{ref}")
    assert res["correct"] is correct
    mismatch = res["checks"]["completion_mismatch"]["value"]
    assert mismatch == 0 if correct else mismatch >= 1


@pytest.mark.parametrize("ref, says", [("missing", "does not exist"),
                                       ("lacks_simulate", "lacks simulate")])
def test_broken_reference_stops_the_run_before_setup(root, ref, says):
    with pytest.raises(SystemExit, match=says):
        cells.cell(f"homa_tiny_{ref}", root)
    p = launch(root, f"homa_tiny_{ref}")
    assert p.returncode != 0 and not p.stdout.strip()
    assert f"bench/references/{ref}.py {says}" in p.stderr
    assert "[bench]" not in p.stderr


DATACLASS_REFERENCE = """from __future__ import annotations
import dataclasses
from bench.reference import priority_allocation, simulate, slowdown_hist


@dataclasses.dataclass(frozen=True)
class Window:
    rack: int
    start: int
    end: int
"""


def test_reference_defining_a_dataclass_loads_by_path(tmp_path):
    """A reference loaded by path may define a dataclass under ``from
    __future__ import annotations`` (which looks its module up in
    ``sys.modules``); files of one name in two directories load as two
    modules."""
    for d in ("references", "other"):
        (tmp_path / "bench" / d).mkdir(parents=True)
        (tmp_path / "bench" / d / "windows.py").write_text(
            DATACLASS_REFERENCE)
    mods = [cells.reference_module(
        {"reference": f"bench/{d}/windows.py"}, tmp_path)
        for d in ("references", "other")]
    assert mods[0].Window(0, 2048, 4096).end == 4096
    assert mods[0] is not mods[1]
    assert mods[0].__name__ != mods[1].__name__


def test_reference_path_outside_the_checkout_is_refused():
    with pytest.raises(SystemExit, match="not a path inside"):
        cells.reference_module({"reference": "../reference.py"})


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_references_of_the_configurations_import_nothing_of_the_program(
        path):
    checks.reference_imports_nothing_of_the_program(cells.ROOT, path)


@pytest.mark.parametrize("ref", ["same", "strict_off"])
def test_fixture_references_import_nothing_of_the_program(root, ref):
    path = root / "bench" / "configs" / f"tiny16_homa_{ref}.json"
    checks.reference_imports_nothing_of_the_program(root, path)


def test_kind_tables_reach_program_and_reference(root):
    """The fixture kind ``shift`` draws the tables of the cell; the
    program and the reference both run them, and agree."""
    c = cells.cell("homa_tiny_shift", root)
    cfg, mix = c["config"], c["mix"]
    H, sb = cfg["sim"]["n_hosts"], cfg["sim"]["slot_bytes"]
    tables = gen.call_tables(mix, H, sb, int(SEED), 0, c["table"])
    poisson = gen.call_tables({**mix, "kind": "poisson"}, H, sb, int(SEED),
                              0)
    shift = (tables[0]["dst"] - tables[0]["src"]) % H
    assert len(set(shift.tolist())) == 1 and shift[0] != 0
    assert not np.array_equal(tables[0]["dst"], poisson[0]["dst"])
    sizes = gen.alloc_sample(mix, int(SEED))
    got = entries.Program(cfg, mix, sizes).call(tables)
    want = entries.reference_answers(cfg, mix, sizes, tables, [None],
                                     ref=c["reference"])
    assert entries.compare("simulate", got, want)[0][
        "completion_mismatch"]["value"] == 0
    done = want[0]["completion"] >= 0
    assert done.sum() > 10
    assert set(((tables[0]["dst"] - tables[0]["src"]) % H)[done]) \
        == {int(shift[0])}


def test_whole_run_of_a_kind_is_correct(root):
    res, _ = drive(root, "homa_tiny_shift")
    assert res["correct"] is True and res["attempted"] >= 1
    assert res["checks"]["completion_mismatch"] == {"value": 0, "limit": 0}


def test_unknown_kind_without_a_file_is_an_error(root):
    want = str(root / "bench" / "kinds" / "nokind.py") + " does not exist"
    with pytest.raises(SystemExit, match=want):
        cells.cell("homa_tiny_nokind", root)
    with pytest.raises(SystemExit, match="does not exist"):
        gen.call_tables({"kind": "nokind"}, 16, 256, 1, 0)
    with pytest.raises(SystemExit, match="not a name"):
        gen.table_fn({"kind": "../kinds/shift"}, root)


# bench/gen.py's Poisson arithmetic as it stood before the kind hook, kept
# here so that a change to it shows.
def _frozen_sizes(bins, n, g):
    ps = np.array([b[0] for b in bins], np.float64)
    ps = ps / ps.sum()
    which = g.choice(len(bins), size=n, p=ps)
    lo = np.array([b[1] for b in bins])[which].astype(np.float64)
    hi = np.array([b[2] for b in bins])[which].astype(np.float64)
    u = g.random(n)
    sizes = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    return np.maximum(sizes.astype(np.int64), 1)


def _frozen_table(mix, n_hosts, slot_bytes, g):
    n = int(mix["n_messages"])
    sizes = _frozen_sizes(mix["size_bins"], n, g)
    slots = np.maximum((sizes + slot_bytes - 1) // slot_bytes, 1)
    mean_gap = slots.mean() / (float(mix["load"]) * n_hosts)
    gaps = g.exponential(mean_gap, n)
    arrivals = np.floor(np.cumsum(gaps)).astype(np.int64)
    src = g.integers(0, n_hosts, n)
    dst = g.integers(0, n_hosts - 1, n)
    dst = np.where(dst >= src, dst + 1, dst)
    return {"src": src.astype(np.int32), "dst": dst.astype(np.int32),
            "size": sizes, "arrival": arrivals.astype(np.int32)}


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
@pytest.mark.parametrize("mix", MIXES)
def test_call_tables_equal_the_poisson_code(mix, seed):
    m = cells.load_json(cells.BENCH / "traffic" / f"{mix}.json")
    for call in (0, 1, gen.WARMUP_CALL):
        got = gen.call_tables(m, 144, 256, seed, call)
        assert len(got) == m["runs_per_call"]
        for i, t in enumerate(got):
            want = _frozen_table(m, 144, 256, np.random.default_rng(
                [seed % (1 << 64), call, i]))
            for k, w in want.items():
                assert t[k].dtype == w.dtype
                np.testing.assert_array_equal(t[k], w)
