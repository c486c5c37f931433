"""Leaf-spine fabric subsystem + scenario library tests (DESIGN.md §5).

The bit-identity tests compare against ``tests/golden/fabric_disabled.json``,
a snapshot of the pre-fabric simulator's outputs: the fabric tier must be
invisible unless explicitly enabled.
"""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fabric as fab
from repro.core import (SimConfig, FabricConfig, SweepSpec, simulate,
                        run_sweep, make_messages, scenarios)

GOLDEN = Path(__file__).parent / "golden" / "fabric_disabled.json"
ALL_PROTOS = ["homa", "basic", "phost", "pias", "pfabric", "ndp"]


@pytest.fixture(scope="module")
def golden():
    return json.loads(GOLDEN.read_text())


def _golden_table(meta):
    return make_messages(meta["workload"], n_hosts=meta["n_hosts"],
                         load=meta["load"], n_messages=meta["n_messages"],
                         slot_bytes=meta["slot_bytes"], seed=meta["seed"])


def _golden_cfg(meta, proto, **kw):
    return SimConfig(protocol=proto, n_hosts=meta["n_hosts"],
                     max_slots=meta["max_slots"], ring_cap=meta["ring_cap"],
                     **kw)


# ------------------------------------------------- disabled = bit-identical

@pytest.mark.parametrize("proto", ALL_PROTOS)
def test_fabric_disabled_bit_identical_to_golden(golden, proto):
    """With fabric disabled (the default), every protocol reproduces the
    pre-fabric simulator bit-for-bit."""
    meta, want = golden["meta"], golden["protocols"][proto]
    r = simulate(_golden_cfg(meta, proto), _golden_table(meta))
    assert [int(x) for x in r.completion] == want["completion"]
    assert r.lost_chunks == want["lost_chunks"]
    assert [int(x) for x in r.q_max_bytes] == want["q_max_bytes"]
    assert [int(x) for x in r.prio_drained_bytes] \
        == want["prio_drained_bytes"]
    assert r.fabric is None and r.tor_up_busy_frac is None


def test_fabric_none_sentinel_equals_disabled(golden):
    """``FabricConfig(None)`` is the disabled sentinel — bit-identical to
    ``fabric=None``."""
    meta = golden["meta"]
    tbl = _golden_table(meta)
    a = simulate(_golden_cfg(meta, "homa"), tbl)
    b = simulate(_golden_cfg(meta, "homa", fabric=FabricConfig(None)), tbl)
    np.testing.assert_array_equal(a.completion, b.completion)
    np.testing.assert_array_equal(a.q_max_bytes, b.q_max_bytes)
    assert not FabricConfig(None).enabled
    assert b.fabric is None


def test_single_rack_fabric_is_single_switch(golden):
    """racks=1 leaves every flow intra-rack: the uplink tier exists but
    never queues, and results match the single switch exactly."""
    meta = golden["meta"]
    tbl = _golden_table(meta)
    a = simulate(_golden_cfg(meta, "homa"), tbl)
    b = simulate(_golden_cfg(meta, "homa", fabric=FabricConfig(racks=1)),
                 tbl)
    np.testing.assert_array_equal(a.completion, b.completion)
    assert float(b.tor_up_busy_frac.sum()) == 0.0
    assert b.fabric["racks"] == 1


# ------------------------------------------------------ fabric invariants

@pytest.mark.parametrize("proto", ALL_PROTOS)
def test_fabric_conservation(proto):
    """With the uplink tier in the path, chunks are still conserved:
    sent == received + buffered (either tier) + lost (either tier)."""
    tbl = make_messages("W3", n_hosts=12, load=0.7, n_messages=250,
                        slot_bytes=256, seed=3)
    cfg = SimConfig(protocol=proto, n_hosts=12, max_slots=5000,
                    ring_cap=512, fabric=FabricConfig(racks=3, oversub=2.0))
    r = simulate(cfg, tbl, return_state=True)
    st = r.state
    assert int(st["recv"].sum()) + int(st["r_valid"].sum()) \
        + int(st["u_valid"].sum()) + int(st["lost"]) + int(st["u_lost"]) \
        == int(st["sent"].sum())
    done = st["completion"] >= 0
    assert (st["completion"][done] >= r.static["arrival"][done]).all()
    assert done.sum() > 0


def test_oversubscription_queues_uplinks():
    """An all-to-all shuffle through a tighter oversubscription ratio
    must queue more at the TOR uplinks, and the per-tier stats must
    surface in summary()/to_json()."""
    tbl = scenarios.shuffle(n_hosts=16, bytes_per_pair=10_000,
                            spread_slots=2000, seed=1)
    qmax = {}
    for ovs in (1.0, 4.0):
        cfg = SimConfig(protocol="homa", n_hosts=16, max_slots=12_000,
                        ring_cap=1024,
                        fabric=FabricConfig(racks=4, oversub=ovs,
                                            up_cap=2048))
        r = simulate(cfg, tbl)
        qmax[ovs] = int(r.tor_up_q_max_bytes.max())
        s = json.loads(r.to_json())
        assert s["fabric"]["oversub"] == ovs
        assert s["fabric"]["n_uplinks"] == max(1, round(4 / ovs))
        assert set(s["fabric"]) >= {"racks", "up_busy_frac",
                                    "up_q_mean_bytes", "up_q_max_bytes",
                                    "up_lost_chunks"}
    assert qmax[4.0] > qmax[1.0], qmax


def test_spine_selection_deterministic_and_seeded():
    import warnings
    from repro.core.fabric import spine_hash
    src = np.arange(64) % 16
    dst = (np.arange(64) * 7 + 1) % 16
    ids = np.arange(64)
    a = spine_hash(src, dst, ids, seed=0, n_uplinks=4)
    b = spine_hash(src, dst, ids, seed=0, n_uplinks=4)
    np.testing.assert_array_equal(a, b)
    assert ((0 <= a) & (a < 4)).all()
    assert (a != spine_hash(src, dst, ids, seed=1, n_uplinks=4)).any()
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # wraparound must be silent
        spine_hash(src, dst, ids, seed=100, n_uplinks=4)
    # and whole runs are reproducible / seed-sensitive
    tbl = scenarios.shuffle(n_hosts=8, bytes_per_pair=20_000, seed=0)
    fab = FabricConfig(racks=4, oversub=2.0)
    cfg = SimConfig(protocol="homa", n_hosts=8, max_slots=6000,
                    ring_cap=512, fabric=fab)
    r1, r2 = simulate(cfg, tbl), simulate(cfg, tbl)
    np.testing.assert_array_equal(r1.completion, r2.completion)


def test_fabric_composes_with_run_sweep():
    """The fabric stage must ride inside the vmapped sweep unchanged:
    batched results are bit-identical to sequential simulate calls."""
    fab = FabricConfig(racks=4, oversub=2.0)
    cfg = SimConfig(protocol="homa", n_hosts=16, max_slots=3000,
                    ring_cap=256, fabric=fab)
    tables = [make_messages("W2", n_hosts=16, load=0.6, n_messages=120,
                            slot_bytes=256, seed=s) for s in range(3)]
    seq = [simulate(cfg, t) for t in tables]
    swe = run_sweep(cfg, SweepSpec(tables=tables))
    for a, b in zip(seq, swe):
        np.testing.assert_array_equal(a.completion, b.completion)
        np.testing.assert_array_equal(a.tor_up_q_max_bytes,
                                      b.tor_up_q_max_bytes)
        assert a.tor_up_lost_chunks == b.tor_up_lost_chunks


def test_nondefault_delays_keep_slowdown_anchored():
    """Slowdown's unloaded baseline must track the fabric's cross-rack
    delay budget, not net_delay_slots, when they differ."""
    from repro.core.workloads import MessageTable
    # one sparse cross-rack message (src rack 0 -> dst rack 1)
    tbl = MessageTable(np.array([0], np.int32), np.array([4], np.int32),
                       np.array([256], np.int64), np.array([0], np.int32),
                       "custom", 0.0, 256)
    cfg = SimConfig(protocol="homa", n_hosts=8, max_slots=400,
                    ring_cap=64,
                    fabric=FabricConfig(racks=2, leaf_delay_slots=20,
                                        spine_delay_slots=20))
    r = simulate(cfg, tbl)
    assert r.done.all()
    np.testing.assert_allclose(r.slowdown[0], 1.0, atol=0.05)


def test_fabric_validation_errors():
    with pytest.raises(ValueError, match="divisible"):
        SimConfig(n_hosts=10, fabric=FabricConfig(racks=3))
    with pytest.raises(ValueError, match="oversub"):
        SimConfig(n_hosts=8, fabric=FabricConfig(racks=2, oversub=0))
    with pytest.raises(ValueError, match="spine_delay"):
        SimConfig(n_hosts=8, fabric=FabricConfig(racks=2,
                                                 spine_delay_slots=0))
    with pytest.raises(ValueError, match="racks"):
        SimConfig(n_hosts=8, fabric=FabricConfig(racks=0))


# -------------------------------------------------- acceptance: Fig. 14

def test_incast_on_oversubscribed_fabric_homa_beats_basic():
    """Fig. 14 shape on a 2:1-oversubscribed leaf-spine: repeated fan-in
    bursts + Poisson background; Homa's priorities keep small messages'
    p99 slowdown far below basic's."""
    tbl = scenarios.incast(12, 2048, n_hosts=16, n_bursts=8,
                           period_slots=1500, background="W2",
                           background_load=0.5, n_background=600, seed=2)
    p99 = {}
    for proto in ("homa", "basic"):
        cfg = SimConfig(protocol=proto, n_hosts=16, max_slots=16_000,
                        ring_cap=1024,
                        fabric=FabricConfig(racks=4, oversub=2.0,
                                            up_cap=1024))
        r = simulate(cfg, tbl)
        assert r.n_complete == r.n_messages, (proto, r.n_complete)
        small = r.steady_mask() & (r.size_bytes < 1000)
        p99[proto] = r.percentile(99, small)
    assert p99["homa"] * 2 < p99["basic"], p99


# ------------------------------------------------------ scenario library

def test_incast_table_structure():
    t = scenarios.incast(10, 4096, n_hosts=16, dst=3, n_bursts=2,
                         period_slots=500)
    assert len(t.size) == 20
    assert (t.dst == 3).all()
    assert (t.src != 3).all()
    assert (t.size == 4096).all()
    assert sorted(set(t.arrival_slot)) == [0, 500]
    for slot in (0, 500):
        burst = t.src[t.arrival_slot == slot]
        assert len(set(burst.tolist())) == 10       # distinct senders
    with pytest.raises(ValueError, match="fan_in"):
        scenarios.incast(16, 1000, n_hosts=16)


def test_hotspot_skews_destinations():
    t = scenarios.hotspot("W2", n_hosts=16, load=0.6, n_messages=400,
                          hot_fraction=0.6, n_hot=2, seed=0)
    hot = np.isin(t.dst, [0, 1]).mean()
    assert hot > 0.45                                # vs 2/16 uniform
    assert (t.src != t.dst).all()
    base = make_messages("W2", n_hosts=16, load=0.6, n_messages=400,
                         slot_bytes=256, seed=0)
    np.testing.assert_array_equal(t.size, base.size)  # sizes untouched
    with pytest.raises(ValueError, match="hot_fraction"):
        scenarios.hotspot("W2", n_hosts=16, load=0.6, n_messages=10,
                          hot_fraction=1.5)


def test_shuffle_covers_all_pairs():
    t = scenarios.shuffle(n_hosts=6, bytes_per_pair=5000)
    assert len(t.size) == 30
    pairs = set(zip(t.src.tolist(), t.dst.tolist()))
    assert len(pairs) == 30 and all(s != d for s, d in pairs)
    assert (t.size == 5000).all()
    assert (t.arrival_slot == 0).all()
    t2 = scenarios.shuffle(n_hosts=6, bytes_per_pair=5000,
                           spread_slots=100, seed=4)
    assert t2.arrival_slot.max() < 100 and len(set(t2.arrival_slot)) > 1


# ------------------------------------------- satellites: wiring + errors

def test_make_messages_incast_param_changes_table():
    """Regression: the ``incast`` parameter used to be accepted and
    silently ignored."""
    kw = dict(n_hosts=8, load=0.5, n_messages=200, slot_bytes=256, seed=0)
    plain = make_messages("W2", **kw)
    with_incast = make_messages("W2", incast=(5, 4096, 300), **kw)
    assert len(with_incast.size) > len(plain.size)
    burst = with_incast.size == 4096
    assert burst.sum() >= 5
    assert (with_incast.dst[burst] == 0).all()
    # background stream is preserved underneath the overlay
    assert np.isin(plain.size, with_incast.size).all()
    # and arrivals remain sorted so the simulator's warmup mask is valid
    assert (np.diff(with_incast.arrival_slot) >= 0).all()
    with pytest.raises(ValueError, match="period_slots"):
        make_messages("W2", incast=(5, 4096, 0), **kw)


def test_prepare_rejects_oversized_inputs_with_valueerror():
    """Satellite: the MSG_MOD / max_slots guards must survive
    ``python -O`` (they were asserts)."""
    from repro.core.protocols import MSG_MOD
    from repro.core.workloads import MessageTable
    n = MSG_MOD + 1
    tbl = MessageTable(np.zeros(n, np.int32), np.ones(n, np.int32),
                       np.full(n, 100, np.int64), np.zeros(n, np.int32),
                       "custom", 0.0, 256)
    with pytest.raises(ValueError, match="at most"):
        simulate(SimConfig(n_hosts=4, max_slots=100), tbl)
    small = make_messages("W1", n_hosts=4, load=0.5, n_messages=10,
                          slot_bytes=256, seed=0)
    with pytest.raises(ValueError, match="max_slots"):
        simulate(SimConfig(n_hosts=4, max_slots=2 ** 21), small)


# ------------------------------------------------------- ring insert ------

def _insert_oracle(msg_a, prio_a, seq_a, valid_a, row, ok, msg, prio, seq):
    """Item by item, in input order: the lowest-numbered free buffer of
    the item's row; dropped only when that row is full."""
    m, p, s, v = (np.array(a) for a in (msg_a, prio_a, seq_a, valid_a))
    dropped = 0
    for i in np.flatnonzero(ok):
        free = np.flatnonzero(~v[row[i]])
        if free.size == 0:
            dropped += 1
            continue
        j = free[0]
        m[row[i], j], p[row[i], j], s[row[i], j] = msg[i], prio[i], seq[i]
        v[row[i], j] = True
    return m, p, s, v, dropped


def _insert_case(cap, fill, layout, seed, R=12, n=48):
    """Rings and items for one case. ``spread``: random fill, items on
    random rows; ``last_free``: every row full but one buffer of its
    last block (the last buffer on even rows); ``crowded``: all items on
    one row that has n // 2 free buffers. About a fifth of the items are
    masked, some of those on the sentinel row ``R``."""
    rng = np.random.default_rng(seed)
    if layout == "spread":
        valid = rng.random((R, cap)) < fill
        row = rng.integers(0, R, n)
    elif layout == "last_free":
        valid = np.ones((R, cap), bool)
        lo = (cap - 1) // fab.RING_BLOCK * fab.RING_BLOCK
        for r in range(R):
            valid[r, cap - 1 if r % 2 == 0 else rng.integers(lo, cap)] = False
        row = rng.integers(0, R, n)
    else:
        valid = rng.random((R, cap)) < fill
        valid[3] = True
        valid[3, rng.choice(cap, n // 2, replace=False)] = False
        row = np.full(n, 3)
    ok = rng.random(n) >= 0.2
    row = np.where(~ok & (rng.random(n) < 0.5), R, row)
    ring = [np.where(valid, rng.integers(0, 99, (R, cap)), -1)
            for _ in range(3)]
    items = [rng.integers(0, 99, n) for _ in range(3)]
    return (*ring, valid, row, ok, *items)


def _as_jax(case):
    return [jnp.asarray(a, bool if a.dtype == bool else jnp.int32)
            for a in case]


_INSERT_CASES = (
    [(cap, fill, "spread") for cap in (24, 100, 128, 300, 4096)
     for fill in (0.0, 0.5, 0.97, 1.0)]
    + [(cap, None, "last_free") for cap in (24, 100, 128, 300, 4096)]
    + [(cap, 0.5, "crowded") for cap in (100, 4096)])


@pytest.mark.parametrize("batch", [None, 2])
@pytest.mark.parametrize("cap,fill,layout", _INSERT_CASES)
def test_ring_insert_takes_lowest_free_buffer_in_input_order(
        cap, fill, layout, batch):
    """Every item gets its row's lowest free buffer, in input order, and
    is dropped only when its row is full: the drain breaks (priority,
    seq) ties by buffer index, so positions must match exactly. With
    ``batch`` the rings and items are vmapped, as a sweep runs them."""
    seeds = [0] if batch is None else range(batch)
    cases = [_insert_case(cap, fill, layout, seed=s + 1000 * cap)
             for s in seeds]
    if batch is None:
        got = [jax.jit(fab.ring_insert)(*_as_jax(cases[0]))]
    else:
        stacked = _as_jax([np.stack(a) for a in zip(*cases)])
        out = jax.jit(jax.vmap(fab.ring_insert))(*stacked)
        got = [[a[b] for a in out] for b in range(batch)]
    for case, res in zip(cases, got):
        want = _insert_oracle(*case)
        for name, g, w in zip(("msg", "prio", "seq", "valid", "dropped"),
                              res, want):
            np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)


def test_ring_insert_lowers_without_loops_or_full_row_cumsum():
    """At the paper's ring shape, (144, 4096) with 144 items, the insert
    lowers with no ``while`` and no ``reduce_window`` wider than one
    block: JAX lowers ``cumsum`` as a ``reduce_window`` as wide as the
    axis, O(cap^2) adds per row, which is what made the insert slow."""
    R, cap, n = 144, 4096, 144
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    b = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)     # noqa: E731
    txt = jax.jit(fab.ring_insert).lower(
        i32(R, cap), i32(R, cap), i32(R, cap), b(R, cap),
        i32(n), b(n), i32(n), i32(n), i32(n)).as_text()
    assert "stablehlo.while" not in txt
    windows = re.findall(r"window_dimensions = array<i64: ([\d, ]+)>", txt)
    assert windows
    assert max(int(w) for dims in windows for w in dims.split(",")) \
        <= fab.RING_BLOCK
