"""The wall-clock plane of ``repro.core.telemetry`` and the stage scopes of
the scan (DESIGN.md §8).

``simulate`` and ``run_sweep`` record named, nested host spans of every
call in a bounded record, entered in the profiler's trace as well;
``sim.step_fn`` runs each stage under a ``jax.named_scope``, which puts
the stage's name in its ops' metadata and changes no result.
"""
import re

import jax
import numpy as np
import pytest

from repro.core import (FabricConfig, FaultConfig, SimConfig, StreamSpec,
                        SweepSpec, TraceConfig, make_messages, run_sweep,
                        simulate, sim, sweep, telemetry)
from repro.core.priorities import PriorityAllocation
from repro.core.protocols import get_protocol
from repro.kernels.arbiter import dispatch

SIM_CHILDREN = ["sim.prepare", "sim.init_state", "sim.dispatch",
                "sim.scan_wait", "sim.fetch", "sim.finalize"]
GROUP_CHILDREN = ["sweep.stack", "sweep.dispatch", "sweep.scan_wait",
                  "sweep.fetch", "sweep.stats"]


def _table(seed=0, n_hosts=8, n_messages=40):
    return make_messages("W2", n_hosts=n_hosts, load=0.6,
                         n_messages=n_messages, slot_bytes=256, seed=seed)


def _cfg(**kw):
    return SimConfig(**{"n_hosts": 8, "ring_cap": 256, "max_slots": 300,
                        **kw})


def _call(spans, root):
    """The spans of the last call whose root span is ``root``."""
    call = [s for s in spans if s["name"] == root][-1]["call"]
    return [s for s in spans if s["call"] == call]


def _check_nesting(call, root, children):
    *kids, top = call
    assert top["name"] == root and top["parent"] is None
    assert [s["name"] for s in kids] == children
    assert all(s["parent"] == root for s in kids)
    ends = [top["start_ns"]] + [x for s in kids
                                for x in (s["start_ns"], s["end_ns"])]
    assert ends == sorted(ends) and kids[-1]["end_ns"] <= top["end_ns"]
    return top


def test_simulate_records_named_spans_nested_by_call():
    telemetry.clear_spans()
    cfg = _cfg()
    simulate(cfg, _table(0))
    simulate(cfg, _table(1))
    spans = telemetry.host_spans()
    assert len(spans) == 2 * (len(SIM_CHILDREN) + 1)
    first, second = spans[:7], spans[7:]
    tops = [_check_nesting(c, "sim.simulate", SIM_CHILDREN)
            for c in (first, second)]
    assert [t["counts"]["slots"] for t in tops] == [300] * 2
    assert all(set(t["counts"]) == {"slots", "grant_topk_rounds"}
               for t in tops)
    assert len({s["call"] for s in first}) == 1
    assert first[0]["call"] != second[0]["call"]


@pytest.mark.parametrize("streaming", [False, True],
                         ids=["exact", "streaming"])
def test_run_sweep_records_named_spans_per_group(streaming):
    telemetry.clear_spans()
    cfg = _cfg()
    tables = (_table(0), _table(1), _table(2))
    spec = SweepSpec(tables=tables, shared_alloc=True,
                     streaming=StreamSpec() if streaming else None,
                     chunk_slots=128 if streaming else None)
    run_sweep(cfg, spec)
    call = _call(telemetry.host_spans(), "sweep.run")
    top = _check_nesting(call, "sweep.run",
                         ["sweep.prepare"] + GROUP_CHILDREN)
    assert top["counts"]["slots"] == 3 * 300
    assert set(top["counts"]) == {"slots", "grant_topk_rounds"}


# the allocation of the homa_w4 benchmark cell: one unscheduled level,
# seven scheduled ones, so Homa's K is 7
CELL_ALLOC = PriorityAllocation(n_prios=8, n_unsched=1, cutoffs=(),
                                unsched_bytes_frac=0.040)


@pytest.mark.parametrize("proto,rounds", [("homa", 7), ("phost", 1),
                                          ("pfabric", 0)])
def test_grant_topk_rounds_counts_the_compiled_selection(proto, rounds):
    """``grant_topk_rounds`` on ``sim.simulate`` and ``sweep.run``: K
    where the reference grant top-K runs by rounds, 0 for a protocol
    with no grant top-K."""
    telemetry.clear_spans()
    cfg = _cfg(protocol=proto, max_slots=100, backend="reference")
    tables = (_table(0, n_messages=256), _table(1, n_messages=256))
    simulate(cfg, tables[0], alloc=CELL_ALLOC)
    run_sweep(cfg, SweepSpec(tables=tables, alloc=CELL_ALLOC))
    spans = telemetry.host_spans()
    for root in ("sim.simulate", "sweep.run"):
        top = _call(spans, root)[-1]
        assert top["counts"]["grant_topk_rounds"] == rounds
    assert dispatch.topk_rounds(7, 256) == 7


def test_span_record_is_bounded():
    telemetry.clear_spans()
    for i in range(telemetry.SPAN_CAP + 100):
        with telemetry.span("t.span", i=i):
            pass
    spans = telemetry.host_spans()
    assert telemetry.SPAN_CAP == 4096 and len(spans) == 4096
    assert spans[0]["counts"] == {"i": 100}
    assert spans[-1]["counts"] == {"i": 4195}
    telemetry.clear_spans()
    assert telemetry.host_spans() == []


def test_span_is_recorded_when_its_block_raises():
    telemetry.clear_spans()
    with pytest.raises(ValueError):
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                raise ValueError("boom")
    inner, outer = telemetry.host_spans()
    assert (inner["name"], inner["parent"]) == ("inner", "outer")
    assert outer["parent"] is None and inner["call"] == outer["call"]
    with telemetry.span("next") as rec:
        pass
    assert rec["parent"] is None and rec["call"] != outer["call"]


def test_spans_land_in_profiler_trace_and_change_no_completion(tmp_path):
    """Under an active profiler the spans are in the trace, and the run's
    answers are bit-identical to a run with no profiler."""
    from jax.profiler import ProfileData
    cfg = _cfg()
    tbl = _table(3)
    want = simulate(cfg, tbl)
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = simulate(cfg, tbl)
    finally:
        jax.profiler.stop_trace()
    for f in ("completion", "busy_frac", "q_max_bytes", "wasted_frac",
              "prio_drained_bytes"):
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f))
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"sim.simulate", *SIM_CHILDREN} <= names


def _scopes_in(lowered) -> set:
    """Stage names found in the op locations of a lowered program."""
    text = lowered.as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    stages = set(sim.STAGES) | {"stream_fold"}
    return {seg for n in names for seg in n.split("/") if seg in stages}


RUNS_ALL = set(sim.STAGES) - {"post_step"}
BASE = {"grants", "sender_select", "route", "downlink_drain", "stats"}


@pytest.mark.parametrize("proto,kw,want", [
    ("homa", {}, BASE),
    ("homa", {"fabric": FabricConfig(racks=2, faults=FaultConfig(
        up_loss=0.01)), "trace": TraceConfig(stride=16),
        "backend": "pallas_fused"}, RUNS_ALL),
    ("phost", {}, BASE | {"post_step"}),
], ids=["homa", "homa-fabric-faults-trace-fused", "phost"])
def test_lowered_scan_carries_stage_scopes(proto, kw, want):
    """Each stage a configuration runs names its ops; a stage it does not
    run emits none."""
    cfg = _cfg(protocol=proto, **kw)
    p = get_protocol(proto)
    tbl = _table(0)
    S, alloc = sim.prepare(cfg, tbl, None)
    st0 = sim._init_state(cfg, p, len(tbl.size))
    lowered = sim._run.lower(cfg, p, S, st0, p.n_sched(cfg, alloc))
    assert _scopes_in(lowered) == want


def test_sweep_scan_carries_stream_fold_scope():
    cfg = _cfg()
    p = get_protocol("homa")
    stream = StreamSpec()
    tables = [_table(0), _table(1)]
    alloc = None
    rows, auxs = [], []
    for t in tables:
        S, alloc = sim.prepare(cfg, t, alloc)
        rows.append(S)
        auxs.append(sweep._pack_aux(stream, t))
    stack = lambda xs: jax.tree.map(lambda *a: np.stack(a), *xs)  # noqa
    lowered = sweep._sweep_batch.lower(
        cfg, p, stack(rows), stack(auxs), p.n_sched(cfg, alloc), 128,
        stream, 1)
    assert _scopes_in(lowered) == BASE | {"stream_fold"}
