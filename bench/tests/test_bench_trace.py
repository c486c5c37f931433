"""The trace reduction (``bench/xtrace.py``): busy and idle time, time per
XLA module, top operations and idle gaps, on hand-made events whose
answers are known, and on the host spans of a trace recorded on the
CPU."""
from __future__ import annotations

import pytest

from bench import xtrace

MS = 1_000_000


def device(ops, modules):
    """A device in ``xtrace.events``' form, from ``[name, start, dur]``."""
    op_ns = {}
    for name, _, d in ops:
        op_ns[name] = op_ns.get(name, 0) + d
    return {"start": [o[1] for o in ops], "dur": [o[2] for o in ops],
            "op_ns": op_ns, "modules": modules}


def hand_made():
    """A 100 ms window on two devices. Device 0 runs ops over [10, 30]
    (two overlapping ops) and [50, 60] ms; device 1 over [0, 100]."""
    return {
        "host": [[xtrace.WINDOW_SPAN, 0, 100 * MS],
                 ["bench.call", 5 * MS, 90 * MS],
                 ["bench.prepare", 35 * MS, 10 * MS],
                 ["$sim.py:641 simulate", 6 * MS, 80 * MS]],
        "devices": {
            "/device:TPU:0": device(
                [["fusion.1", 50 * MS, 10 * MS],
                 ["fusion.1", 10 * MS, 15 * MS],
                 ["sort.2", 20 * MS, 10 * MS]],
                [["jit__run(7)", 10 * MS, 50 * MS]]),
            "/device:TPU:1": device(
                [["fusion.1", -5 * MS, 110 * MS]],
                [["jit__run(7)", 0, 100 * MS], ["jit_other", 0, 1 * MS]])}}


def test_busy_and_idle_share():
    s = xtrace.reduce(hand_made())["summary"]
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s_by_device"]["/device:TPU:0"] == pytest.approx(0.03)
    assert s["busy_s_by_device"]["/device:TPU:1"] == pytest.approx(0.1)
    assert s["idle_share_by_device"]["/device:TPU:0"] == pytest.approx(0.7)
    assert s["idle_share_by_device"]["/device:TPU:1"] == pytest.approx(0.0)
    assert s["busy_s"] == pytest.approx(0.065)


def test_module_time_summed_over_devices():
    s = xtrace.reduce(hand_made())["summary"]
    assert s["module_s"]["jit__run(7)"] == pytest.approx(0.15)
    assert s["module_s"]["jit_other"] == pytest.approx(0.001)


def test_top_ops_by_device_time():
    s = xtrace.reduce(hand_made())["summary"]
    assert [n for n, _ in s["top_ops"]] == ["fusion.1", "sort.2"]
    assert s["top_ops"][0][1] == pytest.approx(0.135)


def test_idle_gaps_longest_first_with_host_activity():
    s = xtrace.reduce(hand_made())["summary"]
    # device 0 idles [60, 100], [0, 10] and [30, 50] ms
    assert [g for _, g in s["idle_gaps"]] == pytest.approx([0.04, 0.02,
                                                            0.01])
    assert [n for n, _ in s["idle_gaps"]] == [
        "$sim.py:641 simulate", "bench.prepare", "bench.call"]
    assert xtrace.breakdown({"summary": s}) == {
        "device_ops": s["top_ops"], "idle_gaps": s["idle_gaps"]}


def test_window_span_is_required():
    ev = hand_made()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        xtrace.reduce(ev)


def test_recorder_on_cpu_reads_host_spans(tmp_path):
    """The recorder's trace holds its spans; the CPU has no device plane,
    so there is no busy time to read."""
    import jax
    import jax.numpy as jnp
    rec = xtrace.Recorder(tmp_path / "trace")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    rec.start()
    with rec.span("bench.call"):
        f(x).block_until_ready()
    rec.stop()
    red = rec.reduce()
    ev = xtrace.events(next(iter((tmp_path / "trace").rglob("*.xplane.pb"))))
    names = {h[0] for h in ev["host"]}
    assert {xtrace.WINDOW_SPAN, "bench.call"} <= names
    assert ev["devices"] == {}
    assert red["summary"]["window_s"] > 0
    assert red["summary"]["idle_share_by_device"] == {}
