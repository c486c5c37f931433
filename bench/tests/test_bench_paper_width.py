"""The plain reference against the program at the cells' own width on the
CPU: the configurations of ``bench/configs`` (144 hosts, 9 racks of 16
with 16 uplinks per TOR, rings of 4,096) and the full 6,000-message W4
table of ``w4_load80_single``, over the first 512 slots of its horizon.
Completion slots agree exactly; the control (strict priority off) does
not."""
from __future__ import annotations

import pytest

from bench import cells, entries, gen

SLOTS = 512
SEED = 1


@pytest.mark.parametrize("config", ["paper144_homa", "paper144_pfabric"])
def test_paper_width_reference_matches_program(config):
    cfg = cells.load_json(cells.BENCH / "configs" / f"{config}.json")
    mix = {**cells.load_json(cells.BENCH / "traffic"
                             / "w4_load80_single.json"), "max_slots": SLOTS}
    H, sb = cfg["sim"]["n_hosts"], cfg["sim"]["slot_bytes"]
    tables = gen.call_tables(mix, H, sb, SEED, 0)
    assert len(tables[0]["size"]) == 6000 and H == 144
    sizes = gen.alloc_sample(mix, SEED)
    prog = entries.Program(cfg, mix, sizes)
    assert prog.cfg.ring_cap == 4096 and prog.cfg.fabric.up_cap == 4096
    got = prog.call(tables)
    want = entries.reference_answers(cfg, mix, sizes, tables, [None])
    ctl = entries.reference_answers(cfg, mix, sizes, tables, [None],
                                    strict_priority=False)
    assert entries.compare("simulate", got, want)[0][
        "completion_mismatch"]["value"] == 0
    assert (want[0]["completion"] >= 0).sum() > 50
    assert entries.compare("simulate", ctl, want)[0][
        "completion_mismatch"]["value"] >= 1
