"""The plain reference against the program on the CPU at small sizes:
completion slots agree exactly, with and without the leaf-spine tier,
with queues that overflow; the control (strict priority off) does not
agree; the reference's allocation and ECMP hash agree with the
program's."""
from __future__ import annotations

import numpy as np
import pytest

from bench import entries, gen, reference

W4 = [[0.10, 30, 300], [0.25, 300, 3000], [0.30, 3000, 30000],
      [0.25, 30000, 300000], [0.10, 300000, 3000000]]
W1 = [[0.55, 10, 100], [0.40, 100, 1000], [0.048, 1000, 10000],
      [0.002, 10000, 30000]]
# Messages of up to 20 MB (78,125 slots): pFabric's wire priorities pass
# 65,536 and the horizon passes 32,768 slots.
LONG = [[0.9, 1000, 50000], [0.1, 5000000, 20000000]]


def config(protocol, n_hosts=16, ring_cap=1024, racks=4, oversub=1.0,
           up_cap=1024):
    return {"sim": {"protocol": protocol, "n_hosts": n_hosts,
                    "slot_bytes": 256, "n_prios": 8, "rtt_slots": 38,
                    "net_delay_slots": 12, "grant_delay_slots": 19,
                    "ring_cap": ring_cap},
            "fabric": None if racks is None else {
                "racks": racks, "oversub": oversub, "up_cap": up_cap,
                "leaf_delay_slots": 6, "spine_delay_slots": 6, "seed": 0,
                "routing": "ecmp"}}


def mix(bins=W4, n=300, slots=1500, load=0.8):
    return {"entry": "simulate", "size_bins": bins, "n_messages": n,
            "alloc_messages": 4 * n, "load": load, "max_slots": slots}


def both(cfg, mx, seed):
    t = gen.call_tables(mx, cfg["sim"]["n_hosts"], 256, seed, 0)
    sizes = gen.alloc_sample(mx, seed)
    prog = entries.Program(cfg, mx, sizes).call(t)
    ref = entries.reference_answers(cfg, mx, sizes, t, [None])
    return prog, ref, t, sizes


CASES = {
    "leafspine": (config("homa"), mix(), 1),
    "overflow": (config("homa", ring_cap=16, up_cap=8, oversub=2.0),
                 mix(load=0.9), 2),
    "single_switch": (config("homa", ring_cap=24, racks=None),
                      mix(load=0.9), 3),
    "w1_32_hosts": (config("homa", n_hosts=32, ring_cap=64, up_cap=64),
                    mix(W1, n=600, slots=400), 4),
    "long_horizon": (config("homa", n_hosts=4, ring_cap=32, racks=None),
                     mix(LONG, n=200, slots=40000, load=0.9), 5),
}


@pytest.mark.parametrize("protocol", ["homa", "pfabric"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_program(case, protocol):
    cfg, mx, seed = CASES[case]
    cfg = {**cfg, "sim": {**cfg["sim"], "protocol": protocol}}
    prog, ref, _, _ = both(cfg, mx, seed)
    checks, failed = entries.compare("simulate", prog, ref)
    assert checks["completion_mismatch"]["value"] == 0 and failed == 0
    assert (ref[0]["completion"] >= 0).sum() > 10


@pytest.mark.parametrize("protocol", ["homa", "pfabric"])
def test_control_differs(protocol):
    cfg, mx, seed = CASES["leafspine"]
    cfg = {**cfg, "sim": {**cfg["sim"], "protocol": protocol}}
    t = gen.call_tables(mx, 16, 256, seed, 0)
    sizes = gen.alloc_sample(mx, seed)
    prog = entries.Program(cfg, mx, sizes).call(t)
    ctl = entries.reference_answers(cfg, mx, sizes, t, [None],
                                    strict_priority=False)
    assert entries.compare("simulate", prog, ctl)[0][
        "completion_mismatch"]["value"] > 0


@pytest.mark.parametrize("bins", [W4, W1], ids=["W4", "W1"])
def test_allocation_matches_program(bins):
    from repro.core import allocate_priorities
    sizes = gen.sample_sizes(bins, 5000, gen.rng(7, 0))
    for limit in (9728, 2000, 100):
        want = allocate_priorities(sizes, unsched_limit=limit, n_prios=8)
        got = reference.priority_allocation(sizes, limit, 8)
        assert got["n_sched"] == want.n_sched
        assert tuple(got["cutoffs"]) == want.cutoffs
        np.testing.assert_array_equal(
            reference.unsched_level(sizes, got, 8),
            want.unsched_prio(sizes))


def test_ecmp_hash_matches_program():
    from repro.core.fabric import spine_hash
    r = np.random.default_rng(0)
    src, dst = r.integers(0, 144, 1000), r.integers(0, 144, 1000)
    for seed, n_up in ((0, 16), (5, 3), (2 ** 31 + 7, 8)):
        np.testing.assert_array_equal(
            reference.ecmp_spine(src, dst, np.arange(1000), seed, n_up),
            spine_hash(src, dst, np.arange(1000), seed, n_up))


def test_sweep_answers_match_program_histograms():
    """The reference's (size, slowdown) histogram and completed count
    equal ``run_sweep(streaming=True)``'s for each run."""
    cfg, mx, seed = CASES["leafspine"]
    mx = {**mx, "entry": "run_sweep", "runs_per_call": 2,
          "chunk_slots": 512, "shard": False,
          "stream": {"n_buckets": 512, "max_slowdown": 10000.0,
                     "size_edges": [256, 1000, 4096, 16384, 65536,
                                    262144, 1048576],
                     "small_bytes": 1000, "warmup_frac": 0.0}}
    t = gen.call_tables(mx, 16, 256, seed, 0)
    sizes = gen.alloc_sample(mx, seed)
    prog = entries.Program(cfg, mx, sizes).call(t)
    ref = entries.reference_answers(cfg, mx, sizes, t, [None, None])
    checks, failed = entries.compare("run_sweep", prog, ref)
    assert failed == 0, checks
    assert all(r["n_complete"] > 0 for r in ref)


def test_bounds_are_checked():
    """Sizes the int32 SRPT key cannot hold are refused and the tables
    drawn here pass. The horizon has no cap (``long_horizon`` runs 40,000
    slots)."""
    cfg, mx, seed = CASES["leafspine"]
    t = gen.call_tables(mx, 16, 256, seed, 0)[0]
    alloc = reference.priority_allocation(t["size"], 9728, 8)
    huge = {**t, "size": np.where(np.arange(len(t["size"])) == 0,
                                  2 ** 31 - 1, t["size"])}
    with pytest.raises(ValueError, match="SRPT key"):
        reference.simulate(cfg, huge, alloc, 100)
    reference.check_bounds(cfg, reference.static_inputs(cfg, t, alloc))
