"""In-scan telemetry & trace subsystem tests (DESIGN.md §8).

Trace-off invariance is the load-bearing guarantee: with ``trace=None``
(the default) or ``TraceConfig(enabled=False)`` every protocol must
reproduce the committed fabric goldens bit-for-bit on BOTH backends —
the telemetry arrays and ops never enter the untraced program. Tracing
on must be pure observation (hypothesis property), the ledger must stay
bounded with an exact overflow count, and the strided series must agree
with the end-of-run aggregates exactly. The JSON satellites (SimResult
round-trip, bucketed_percentiles empty schema) are pinned here too.
"""
import json
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st_

from repro.core import (SimConfig, FabricConfig, TraceConfig, SimTrace,
                        SweepSpec, simulate, run_sweep, make_messages)
from repro.core import telemetry
from repro.core.results import SimResult, bucketed_percentiles
from repro.core.telemetry import (EV_GRANT, EV_PREEMPT, EV_LOSS,
                                  EV_OVERFLOW, EV_RESEND, EV_TIMEOUT,
                                  EV_COMPLETE, EV_COLUMNS)

GOLDEN = Path(__file__).parent / "golden"
ALL_PROTOS = ["homa", "basic", "phost", "pias", "pfabric", "ndp"]
BACKENDS = ["reference", "pallas"]
OFF_SENTINELS = [None, TraceConfig(enabled=False)]


@pytest.fixture(scope="module")
def disabled():
    return json.loads((GOLDEN / "fabric_disabled.json").read_text())


@pytest.fixture(scope="module")
def enabled():
    return json.loads((GOLDEN / "fabric_enabled.json").read_text())


def _table(meta):
    return make_messages(meta["workload"], n_hosts=meta["n_hosts"],
                         load=meta["load"], n_messages=meta["n_messages"],
                         slot_bytes=meta["slot_bytes"], seed=meta["seed"])


def _cfg(meta, proto, *, fabric=None, backend="reference", trace=None):
    return SimConfig(protocol=proto, n_hosts=meta["n_hosts"],
                     max_slots=meta["max_slots"], ring_cap=meta["ring_cap"],
                     fabric=fabric, backend=backend, trace=trace)


def _traced_run(proto="homa", *, n_hosts=8, n_messages=120, max_slots=4000,
                trace=None, fabric=None, seed=0, load=0.6):
    tbl = make_messages("W2", n_hosts=n_hosts, load=load,
                        n_messages=n_messages, slot_bytes=256, seed=seed)
    cfg = SimConfig(n_hosts=n_hosts, protocol=proto, ring_cap=512,
                    max_slots=max_slots, fabric=fabric, trace=trace)
    return simulate(cfg, tbl)


# ------------------------------------------------ trace-off invariance ----

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("trace", OFF_SENTINELS,
                         ids=["trace=None", "enabled=False"])
@pytest.mark.parametrize("proto", ALL_PROTOS)
def test_trace_off_matches_disabled_golden(disabled, proto, trace, backend):
    """Acceptance: with tracing absent or disabled, every protocol on
    both backends reproduces the pre-telemetry golden bit-for-bit."""
    meta, want = disabled["meta"], disabled["protocols"][proto]
    r = simulate(_cfg(meta, proto, backend=backend, trace=trace),
                 _table(meta))
    assert [int(x) for x in r.completion] == want["completion"]
    assert [int(x) for x in r.q_max_bytes] == want["q_max_bytes"]
    assert r.trace is None and r.trace_summary is None


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("trace", OFF_SENTINELS,
                         ids=["trace=None", "enabled=False"])
@pytest.mark.parametrize("proto", ["homa", "pfabric"])
def test_trace_off_matches_enabled_golden(enabled, proto, trace, backend):
    """Same invariance through the fabric tier (TOR uplink state in the
    scan carry must not shift with telemetry compiled out)."""
    meta, want = enabled["meta"], enabled["protocols"][proto]
    fab = FabricConfig(racks=meta["racks"], oversub=meta["oversub"],
                       up_cap=meta["up_cap"])
    r = simulate(_cfg(meta, proto, fabric=fab, backend=backend,
                      trace=trace), _table(meta))
    assert [int(x) for x in r.completion] == want["completion"]
    assert [int(x) for x in r.tor_up_q_max_bytes] \
        == want["tor_up_q_max_bytes"]


@settings(max_examples=8, deadline=None)
@given(proto=st_.sampled_from(ALL_PROTOS),
       n_hosts=st_.sampled_from([4, 8]),
       racks=st_.sampled_from([0, 2]),
       stride=st_.sampled_from([1, 7, 64]),
       ledger_cap=st_.sampled_from([0, 8, 512]),
       seed=st_.integers(min_value=0, max_value=4))
def test_tracing_is_pure_observation(proto, n_hosts, racks, stride,
                                     ledger_cap, seed):
    """Property: for any protocol, topology, stride and ledger size,
    tracing never changes completion slots or slowdowns."""
    tbl = make_messages("W1", n_hosts=n_hosts, load=0.5, n_messages=40,
                        slot_bytes=256, seed=seed, max_bytes=2000)
    fab = FabricConfig(racks=racks, oversub=2.0) if racks else None
    base = dict(n_hosts=n_hosts, protocol=proto, fabric=fab,
                max_slots=3000, ring_cap=256)
    r0 = simulate(SimConfig(**base), tbl)
    r1 = simulate(SimConfig(**base, trace=TraceConfig(
        stride=stride, ledger_cap=ledger_cap)), tbl)
    np.testing.assert_array_equal(r0.completion, r1.completion)
    np.testing.assert_array_equal(r0.slowdown, r1.slowdown)


# ------------------------------------------------------ ledger capture ----

def test_ledger_records_all_completions_when_roomy():
    """With capacity to spare, the ledger holds exactly one COMPLETE row
    per finished message, values = elapsed slots, in slot order."""
    r = _traced_run(trace=TraceConfig(stride=32, ledger_cap=8192))
    tr = r.trace
    assert isinstance(tr, SimTrace)
    assert tr.events_dropped == 0
    comp = tr.events_of(EV_COMPLETE)
    assert comp.shape[0] == r.n_complete
    # each row's (slot, msg, value) must reconcile with SimResult
    done = {int(m): int(s) for m, s in zip(comp[:, 2], comp[:, 0])}
    for m, slot in done.items():
        assert int(r.completion[m]) == slot
        assert int(comp[comp[:, 2] == m, 4][0]) == int(r.elapsed[m])
    assert np.all(np.diff(tr.events[:, 0]) >= 0)        # slot-ordered
    assert tr.events.shape[1] == len(EV_COLUMNS)


def test_ledger_overflow_bounded_and_counted():
    """A tiny ledger stays at capacity and the overflow counter equals
    seen - kept exactly; the kept prefix is untouched by later events."""
    small = _traced_run(trace=TraceConfig(stride=32, ledger_cap=16))
    big = _traced_run(trace=TraceConfig(stride=32, ledger_cap=8192))
    ts, tb = small.trace, big.trace
    assert ts.n_events == 16
    assert ts.n_events_seen == tb.n_events_seen       # same run, same events
    assert ts.events_dropped == ts.n_events_seen - 16
    np.testing.assert_array_equal(ts.events, tb.events[:16])


def test_ledger_cap_zero_disables_ledger_keeps_series():
    r = _traced_run(trace=TraceConfig(stride=32, ledger_cap=0))
    tr = r.trace
    assert tr.n_events == 0 and tr.n_events_seen == 0
    assert tr.q_bytes.shape[0] == len(tr.sample_slots)


def test_fault_events_reach_the_ledger():
    """Loss, RESEND and timeout rows appear under injected uplink loss,
    and grant rows exist for a scheduled protocol."""
    fab = FabricConfig(racks=2, oversub=2.0, faults=dict(up_loss=0.05))
    r = _traced_run(n_hosts=8, fabric=fab, max_slots=12_000,
                    trace=TraceConfig(stride=64, ledger_cap=65536))
    tr = r.trace
    assert tr.events_of(EV_GRANT).shape[0] > 0
    assert tr.events_of(EV_LOSS)[:, 4].sum() == r.fault_lost_chunks
    assert tr.events_of(EV_RESEND).shape[0] \
        + tr.events_of(EV_TIMEOUT).shape[0] > 0


# -------------------------------------------------------- strided series --

def test_series_cumulative_counters_match_result_aggregates():
    """The final sample of each cumulative series must equal the
    end-of-run aggregate SimResult already reports — the strided series
    is exact, not approximate."""
    r = _traced_run(trace=TraceConfig(stride=16, ledger_cap=0))
    tr = r.trace
    # busy_frac aggregates pool all hosts x slots
    assert int(tr.busy_cum[-1]) == int(round(
        float(np.mean(r.busy_frac)) * 4000 * tr.n_hosts))
    np.testing.assert_array_equal(
        tr.prio_drained_cum_bytes[-1],
        np.asarray(r.prio_drained_bytes))
    # windowed rates sum back to the cumulative total
    assert np.isclose(tr.busy_frac().sum(),
                      tr.busy_cum[-1] / (tr.n_hosts * 16))


def test_series_shapes_and_sample_slots():
    """ceil(max_slots/stride) rows; windows end at stride-1 boundaries
    with the last (partial) window ending at max_slots-1."""
    r = _traced_run(max_slots=1000,
                    trace=TraceConfig(stride=300, ledger_cap=0))
    tr = r.trace
    assert tr.sample_slots.tolist() == [299, 599, 899, 999]
    assert tr.q_bytes.shape == (4, 8)
    assert tr.grant_out_bytes.shape == (4, 8)
    widths = np.diff(tr.sample_slots, prepend=-1)
    assert widths.tolist() == [300, 300, 300, 100]


def test_fabric_series_present_only_with_fabric():
    fab = FabricConfig(racks=2, oversub=2.0)
    r_fab = _traced_run(fabric=fab, trace=TraceConfig(stride=64))
    r_one = _traced_run(trace=TraceConfig(stride=64))
    assert r_fab.trace.up_q_bytes is not None
    assert r_fab.trace.prio_usage("up").shape[1] == 8
    assert r_one.trace.up_q_bytes is None
    with pytest.raises(ValueError):
        r_one.trace.prio_usage("up")


# ---------------------------------------------------- sweeps & reduction --

def test_run_sweep_reduces_trace_to_scalars():
    """vmapped sweeps keep only SimTrace.reduce() scalars per run — no
    (N, T, H) histories — and stay bit-identical to solo runs."""
    tables = [make_messages("W2", n_hosts=8, load=0.5, n_messages=60,
                            slot_bytes=256, seed=s) for s in range(2)]
    cfg = SimConfig(n_hosts=8, protocol="homa", ring_cap=256,
                    max_slots=2000,
                    trace=TraceConfig(stride=32, ledger_cap=256))
    solo = [simulate(cfg, t) for t in tables]
    swept = run_sweep(cfg, SweepSpec(tables=tables))
    for a, b in zip(solo, swept):
        np.testing.assert_array_equal(a.completion, b.completion)
        assert b.trace is None
        assert b.trace_summary["n_events_seen"] == a.trace.n_events_seen
        assert b.trace_summary["q_peak_bytes"] \
            == int(a.trace.q_bytes.max())


# ------------------------------------------------------------ exporters ----

def test_perfetto_export_valid_and_complete(tmp_path):
    r = _traced_run(trace=TraceConfig(stride=64, ledger_cap=2048))
    fp = tmp_path / "trace.json"
    doc = r.trace.to_perfetto(fp)
    loaded = json.loads(fp.read_text())
    assert loaded["traceEvents"] == doc["traceEvents"]
    phases = {e["ph"] for e in loaded["traceEvents"]}
    assert {"M", "C", "i", "X"} <= phases
    n_complete_slices = sum(1 for e in loaded["traceEvents"]
                            if e["ph"] == "X")
    assert n_complete_slices == r.trace.events_of(EV_COMPLETE).shape[0]
    assert loaded["otherData"]["stride"] == 64


def test_timeseries_json_is_json_safe():
    fab = FabricConfig(racks=2, oversub=2.0, faults=dict(up_loss=0.02))
    r = _traced_run(fabric=fab, max_slots=6000,
                    trace=TraceConfig(stride=64, ledger_cap=128))
    doc = r.trace.to_timeseries_json()
    s = json.dumps(doc)                       # must not raise
    back = json.loads(s)
    assert back["events"]["columns"] == list(EV_COLUMNS)
    assert back["events"]["dropped"] == r.trace.events_dropped
    assert "up_q_bytes" in back


# ------------------------------------------------- JSON satellites --------

def test_bucketed_percentiles_empty_schema_has_count():
    """Satellite: the empty return carries the same keys as the
    non-empty one (the bench cache iterates count unconditionally)."""
    out = bucketed_percentiles(np.array([]), np.array([]),
                               np.array([], bool))
    assert set(out) == {"sizes", "p", "median", "count"}
    assert out["count"] == []
    # no-finished-messages case shares the schema too
    out2 = bucketed_percentiles(np.array([100, 200]),
                                np.array([np.nan, np.nan]),
                                np.array([False, False]))
    assert set(out2) == {"sizes", "p", "median", "count"}


def test_simresult_summary_json_safe_with_all_optionals():
    """Satellite: summary() must json.dumps cleanly with fabric, fault
    and trace fields populated (numpy scalars, arrays, NaN)."""
    fab = FabricConfig(racks=2, oversub=2.0, faults=dict(up_loss=0.02))
    r = _traced_run(fabric=fab, n_messages=60, max_slots=1500,
                    trace=TraceConfig(stride=64, ledger_cap=64))
    s = json.dumps(json.loads(r.to_json()))   # round-trips as strict JSON
    assert "trace" in json.loads(s)


def test_simresult_full_json_round_trip():
    """Satellite: to_json(full=True) -> from_json reconstructs every
    array field bit-for-bit, including NaN slowdowns for incomplete
    messages and the fault/fabric arrays."""
    fab = FabricConfig(racks=2, oversub=2.0, faults=dict(up_loss=0.02))
    r = _traced_run(fabric=fab, n_messages=80, max_slots=900,
                    trace=TraceConfig(stride=128, ledger_cap=64))
    assert r.n_complete < r.n_messages        # NaN slowdowns exercised
    back = SimResult.from_json(r.to_json(full=True))
    np.testing.assert_array_equal(back.completion, r.completion)
    np.testing.assert_array_equal(back.done, r.done)
    np.testing.assert_allclose(back.slowdown, r.slowdown)   # NaN == NaN
    np.testing.assert_array_equal(back.retx_chunks, r.retx_chunks)
    np.testing.assert_array_equal(back.tor_up_q_max_bytes,
                                  r.tor_up_q_max_bytes)
    assert back.alloc.cutoffs == r.alloc.cutoffs
    assert back.trace_summary == r.trace_summary
    assert back.protocol == r.protocol


def test_from_json_rejects_foreign_documents():
    with pytest.raises(ValueError):
        SimResult.from_json(json.dumps({"completion": [1, 2]}))


# ------------------------------------------------------- config plumbing --

def test_trace_config_validation():
    with pytest.raises(ValueError):
        SimConfig(n_hosts=4, trace=TraceConfig(stride=0))
    with pytest.raises(ValueError):
        SimConfig(n_hosts=4, trace=TraceConfig(ledger_cap=-1))
    with pytest.raises(ValueError):
        SimConfig(n_hosts=4, trace=TraceConfig(wallclock_repeats=0))


def test_trace_config_coerced_from_dict():
    cfg = SimConfig(n_hosts=4, trace=dict(stride=8, ledger_cap=32))
    assert isinstance(cfg.trace, TraceConfig)
    assert cfg.trace.stride == 8 and cfg.trace_on


def test_wallclock_reports_aot_split():
    """wallclock=True runs the scan through the AOT path and attaches
    the trace/compile/execute split — with capture on or off. Every
    repeat is reported, ``execute_s`` is their median, and each phase
    is a span of the host span record."""
    r_on = _traced_run(n_messages=30, max_slots=500, trace=TraceConfig(
        stride=64, ledger_cap=32, wallclock=True))
    t = r_on.trace.timings
    assert set(t) >= {"trace_s", "compile_s", "execute_s"}
    telemetry.clear_spans()
    r_off = _traced_run(n_messages=30, max_slots=500, trace=TraceConfig(
        enabled=False, wallclock=True, wallclock_repeats=3))
    t2 = r_off.trace_summary["timings"]
    assert r_off.trace is None and t2["execute_repeats"] == 3
    assert len(t2["execute_each_s"]) == 3
    assert t2["execute_s"] == sorted(t2["execute_each_s"])[1]
    spans = telemetry.host_spans()
    assert [s["name"] for s in spans] == [
        "sim.prepare", "sim.init_state", "sim.lower", "sim.compile",
        "sim.execute", "sim.execute", "sim.execute", "sim.fetch",
        "sim.finalize", "sim.simulate"]
    assert {s["parent"] for s in spans[:-1]} == {"sim.simulate"}
