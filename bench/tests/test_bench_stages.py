"""The readers of what the program records about itself
(``bench/stages.py``): the window's calls from the host span record, the
span-timed scan and finalize, self time of nested device ops, and the
stage shares of a trace; on hand-made spans and events, on a trace
recorded on the CPU, and in a whole traced run at test size."""
from __future__ import annotations

import json

import pytest

from bench import cells, stages, xtrace
from bench.tests.runs import drive, tiny_root

MS = 1_000_000
SIM = "sim.simulate"


def spans_of(calls):
    """A span record in ``telemetry.host_spans()``' form: one root per
    call with its ``slots`` and children ``{part: (start, dur)}``."""
    out = []
    for k, (root, slots, parts) in enumerate(calls):
        t = 1000 * MS * k
        for part, (s, d) in parts.items():
            prefix = root.split(".")[0]
            out.append({"name": f"{prefix}.{part}", "start_ns": t + s,
                        "end_ns": t + s + d, "parent": root, "call": k,
                        "counts": {}})
        out.append({"name": root, "start_ns": t, "end_ns": t + 900 * MS,
                    "parent": None, "call": k, "counts": {"slots": slots}})
    return out


SIM_PARTS = {"prepare": (0, 5 * MS), "dispatch": (5 * MS, 1 * MS),
             "scan_wait": (6 * MS, 799 * MS), "fetch": (805 * MS, 3 * MS),
             "finalize": (808 * MS, 7 * MS)}


@pytest.fixture
def recorded(monkeypatch):
    from repro.core import telemetry

    def use(calls):
        monkeypatch.setattr(telemetry, "host_spans",
                            lambda: spans_of(calls))
    return use


def test_span_readers_take_the_window_calls(recorded):
    """An older call (the warm-up) comes before the window's two; each
    window call's scan is 800 ms for 1,000 slots."""
    old = dict(SIM_PARTS, scan_wait=(6 * MS, 1999 * MS))
    recorded([(SIM, 1000, old), (SIM, 1000, SIM_PARTS),
              (SIM, 1000, SIM_PARTS)])
    rec = {"entry": "simulate", "runs": 2, "slots_per_run": 1000}
    assert stages.scan_wall_us_per_slot(rec) == pytest.approx(800.0)
    assert stages.finalize_ms(rec) == pytest.approx(10.0)
    assert cells.metric_reader("scan_wall_us_per_slot")(
        {"record": rec}) == pytest.approx(800.0)
    assert cells.metric_reader("finalize_ms")(
        {"record": rec}) == pytest.approx(10.0)


def test_span_readers_on_sweep_calls(recorded):
    parts = {"prepare": (0, 5 * MS), "stack": (5 * MS, 1 * MS),
             "dispatch": (6 * MS, 2 * MS), "scan_wait": (8 * MS, 798 * MS),
             "fetch": (806 * MS, 4 * MS), "stats": (810 * MS, 4 * MS)}
    recorded([("sweep.run", 8000, parts)])
    rec = {"entry": "run_sweep", "runs": 8, "slots_per_run": 1000}
    assert stages.scan_wall_us_per_slot(rec) == pytest.approx(100.0)
    assert stages.finalize_ms(rec) == pytest.approx(1.0)


@pytest.mark.parametrize("runs", [0, 3])
def test_span_readers_without_the_window_runs_return_none(recorded, runs):
    """No runs, or fewer recorded calls than the window ran: nothing to
    read, never a number from part of the window."""
    recorded([(SIM, 1000, SIM_PARTS), (SIM, 1000, SIM_PARTS)])
    rec = {"entry": "simulate", "runs": runs, "slots_per_run": 1000}
    assert stages.scan_wall_us_per_slot(rec) is None
    assert stages.finalize_ms(rec) is None


def test_span_readers_on_a_program_without_spans(monkeypatch):
    from repro.core import telemetry
    monkeypatch.delattr(telemetry, "host_spans")
    rec = {"entry": "simulate", "runs": 1, "slots_per_run": 1000}
    assert stages.scan_wall_us_per_slot(rec) is None


def test_attribute_self_time_of_nested_events():
    """A loop [0, 100) holding ops [10, 30) and [40, 90), the second
    holding [50, 60); an op after the loop; one overrunning its parent
    is clipped. A nested op counts under its outermost scoped
    ancestor, else under its own scope."""
    ev = [(0, 100), (10, 20), (40, 50), (50, 10), (120, 5), (200, 10),
          (205, 10)]
    scopes = [stages.UNSCOPED, "grants", "route", "stats", "sender_select",
              stages.UNSCOPED, "post_step"]
    want = {stages.UNSCOPED: 35, "grants": 20, "route": 50,
            "sender_select": 5, "post_step": 10}
    assert stages.attribute(ev, scopes) == want
    assert stages.attribute(ev[::-1], scopes[::-1]) == want


@pytest.mark.parametrize("name,scope", [
    ("jit(_run)/while/body/closed_call/grants/sort", "grants"),
    ("jit(_run)/while/body/closed_call/route/vmap(jit(searchsorted))/add",
     "route"),
    ("jit(_sweep_batch)/while/body/stream_fold/scatter-add", "stream_fold"),
    ("jit(_run)/while/body/add", stages.UNSCOPED),
    ("jit(_run)/while/body/stats_extra/add", stages.UNSCOPED),
])
def test_scope_of_op_names(name, scope):
    assert stages.scope_of(name) == scope


@pytest.mark.parametrize("event,inst", [
    ("%sort.12 = (s32[144,6000]{1,0:T(8,128)S(1)}, s32[144,6000]) sort(...)",
     "sort.12"),
    ("%while.140 = (s32[]{:T(128)}, s32[144]) while((s32[]) %tuple.199)",
     "while.140"),
    ("copy.6", "copy.6"),
])
def test_instruction_of_op_events(event, inst):
    assert stages.instruction(event) == inst


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _inst(name, iid, op_name, operands, opcode=""):
    meta = _msg(7, _msg(2, op_name)) if op_name else b""
    code = _msg(2, opcode) if opcode else b""
    return _msg(2, _msg(1, name) + code + meta + _int(35, iid) + _msg(
        36, b"".join(_varint(o) for o in operands)))


def _space(tmp_path, insts):
    """An ``XSpace`` file whose metadata plane holds ``jit__run(42)``'s
    ``HloProto`` of one computation of ``insts``."""
    hlo = _msg(1, _msg(3, b"".join(insts)))
    meta = _int(1, 9) + _msg(2, "jit__run(42)") + _msg(
        5, _int(1, 7) + _msg(6, hlo))
    space = _msg(1, _msg(2, "/host:metadata")
                 + _msg(4, _int(1, 9) + _msg(2, meta)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    return str(path)


def test_hlo_scopes_from_the_metadata_plane(tmp_path):
    """An ``XSpace`` whose metadata plane holds the scan's ``HloProto``:
    each instruction takes its op name's scope; one without takes the
    scope its users agree on (a ``cumsum`` through a ``bitcast`` to a
    ``route`` gather), and one whose users disagree stays unscoped."""
    body = "jit(_run)/while/body/closed_call/"
    comp = _msg(3, b"".join([
        _inst("cumsum", 1, "reduce_window_sum", []),
        _inst("bitcast", 2, "", [1]),
        _inst("iota", 5, "", []),
        _inst("gather", 3, body + "route/gather", [2, 5]),
        _inst("sort", 6, body + "grants/sort", [5]),
        _inst("add", 4, body + "add", [3]),
    ]))
    hlo = _msg(1, comp)
    meta = _int(1, 9) + _msg(2, "jit__run(42)") + _msg(
        5, _int(1, 7) + _msg(6, hlo))
    space = (_msg(1, _msg(2, "/device:TPU:0"))
             + _msg(1, _msg(2, "/host:metadata")
                    + _msg(4, _int(1, 9) + _msg(2, meta))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    want = {"cumsum": "route", "bitcast": "route", "gather": "route",
            "sort": "grants", "iota": stages.UNSCOPED,
            "add": stages.UNSCOPED}
    assert stages.hlo_scopes(str(path), "jit__run", "jit__run(42)") == want
    assert stages.hlo_scopes(str(path), "jit__run", "other") == want
    assert stages.hlo_scopes(str(path), "jit__sweep_batch", "") == {}


def test_hlo_scopes_leave_a_loop_unscoped(tmp_path):
    """A sweep's slot loop has only the histogram fold after it for a
    user: the loop, which runs every stage, takes no scope from it,
    while an ordinary op with the same user does."""
    body = "jit(_sweep_batch)/vmap()/while/body/closed_call/"
    path = _space(tmp_path, [
        _inst("while.7", 1, "jit(_sweep_batch)/vmap()/while", [],
              "while"),
        _inst("copy.3", 2, "", [], "copy"),
        _inst("fold", 3, body + "stream_fold/add", [1, 2], "add"),
    ])
    assert stages.hlo_scopes(path, "jit__run", "jit__run(42)") == {
        "while.7": stages.UNSCOPED, "copy.3": "stream_fold",
        "fold": "stream_fold"}


def test_hlo_scopes_from_a_cpu_trace(tmp_path):
    """The scan's HLO module in a real trace's metadata plane names the
    stages the configuration runs."""
    import jax
    from jax.profiler import ProfileData

    from repro.core import SimConfig, make_messages, simulate
    tbl = make_messages("W2", n_hosts=8, load=0.6, n_messages=40,
                        slot_bytes=256, seed=0)
    cfg = SimConfig(n_hosts=8, ring_cap=256, max_slots=200)
    simulate(cfg, tbl)
    jax.profiler.start_trace(str(tmp_path))
    simulate(cfg, tbl)
    jax.profiler.stop_trace()
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    # the metadata plane holds every jit__run this process compiled; the
    # traced execution names its own program
    pids = {st["program_id"] for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            for st in [dict(e.stats)] if st.get("hlo_module") == "jit__run"}
    assert len(pids) == 1
    scopes = set(stages.hlo_scopes(path, "jit__run",
                                   f"jit__run({pids.pop()})").values())
    staged = {"grants", "sender_select", "route", "downlink_drain", "stats"}
    assert staged <= scopes <= staged | {stages.UNSCOPED}
    assert stages.hlo_scopes(path, "jit__sweep_batch", "") == {}


def test_stage_metric_is_share_of_span_timed_scan(recorded, monkeypatch,
                                                  tmp_path):
    """Shares of the recorded self time, times the span-timed scan; the
    trace is parsed once for all readers."""
    recorded([(SIM, 1000, SIM_PARTS)])
    trace = (tmp_path / "artifacts" / "bench" / "cell" / "trace" / "plugins"
             / "profile" / "t0")
    trace.mkdir(parents=True)
    (trace / "h.xplane.pb").write_bytes(b"")
    parsed = []

    def parse(path, module):
        parsed.append((path, module))
        return {"self_ns": {"grants": 500, "route": 200, "uplink_drain": 100,
                            "downlink_drain": 100, stages.UNSCOPED: 100},
                "ops": 9, "hlo_instructions": 9}
    monkeypatch.setattr(stages, "parse", parse)
    monkeypatch.setattr(stages, "_PARSED", {})
    rec = {"workload": "cell", "entry": "simulate", "runs": 1,
           "slots_per_run": 1000}
    got = {s: stages.stage_us_per_slot(tmp_path, rec, s) for s in (
        ("grants",), ("route",), ("uplink_drain", "downlink_drain"),
        ("stats",))}
    assert list(got.values()) == pytest.approx([400.0, 160.0, 160.0, 0.0])
    assert len(parsed) == 1 and parsed[0][1] == "jit__run"
    assert stages.stage_us_per_slot(tmp_path, {**rec, "workload": "x"},
                                    ("grants",)) is None


def test_stage_shares_on_a_cpu_trace_return_none(tmp_path):
    """A trace recorded on the CPU has no device plane to read."""
    import jax
    import jax.numpy as jnp
    rec = xtrace.Recorder(tmp_path / "artifacts" / "bench" / "cell"
                          / "trace")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    rec.start()
    f(x).block_until_ready()
    rec.stop()
    path = stages.trace_path(tmp_path, {"workload": "cell"})
    assert path is not None
    assert stages.parse(path, "jit__run") is None
    assert stages.stage_shares(tmp_path, {"workload": "cell",
                                          "entry": "simulate"}) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny cells, their BENCHMARK.json given every per-layer metric
    of the real one."""
    r = tiny_root(tmp_path_factory)
    bench = json.loads((r / "BENCHMARK.json").read_text())
    have = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [m for m in cells.benchmark()["per_layer"]
                           if m["name"] not in have]
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    return r


def test_traced_tiny_run_reports_span_metrics(root):
    """The span metrics come from the run's own calls and fit inside its
    window; the CPU trace has no device plane, so the device metrics are
    left out."""
    res, _ = drive(root, "homa_tiny", trace="1")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"prepare_ms", "scan_wall_us_per_slot",
                                   "finalize_ms"}
    rec = json.loads((root / "artifacts" / "bench" / "homa_tiny"
                      / "seed3000000019_trace1.json").read_text())
    scan = res["metrics"]["scan_wall_us_per_slot"]["value"]
    fin = res["metrics"]["finalize_ms"]["value"]
    run_slots = rec["runs"] * rec["slots_per_run"]
    assert 0 < scan * run_slots / 1e6 + fin * rec["runs"] / 1e3 \
        < rec["window_s"]
