"""Profiler trace of a run's window, and its reduction to metrics.

``Recorder`` writes a JAX profiler trace of the window, with the
harness's own spans in it (``bench.window`` around the window,
``bench.call`` around each call of the entry, ``bench.prepare`` around
the harness's timed ``prepare``). It writes the trace's ``.xplane.pb``
alone: ``jax.profiler.stop_trace`` also converts every event to a
``trace.json.gz`` for TensorBoard, which took 75 of its 160 s on a
``homa_w4`` trace of 6.2 million device ops (TPU v5e) and which nothing
here reads. ``events`` reads the ``.xplane.pb``
into plain lists; ``reduce`` turns those lists into the numbers the
per-layer metrics read:

- per device, busy seconds: the union of the intervals in which an XLA
  operation ran (the device plane's ``XLA Ops`` line), inside the window;
  the idle share is 1 minus busy over the window;
- per XLA module (program) name, its device seconds, summed over devices;
- the operations that took the most device time, under their XLA names;
- the longest idle gaps, each labelled with what the host was doing: the
  innermost host span (the harness's, or a Python function of the
  program) that covers the middle of the gap.
"""
from __future__ import annotations

import glob
import shutil
from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


class Recorder:
    """Profiler trace of the window, written under ``out_dir``."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)
        self._window = None
        self._session = None

    def start(self) -> None:
        import jax
        from jax._src.lib import _profiler
        shutil.rmtree(self.out_dir, ignore_errors=True)
        jax.devices()       # the backends exist before the session does
        self._session = _profiler.ProfilerSession()
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def stop(self) -> None:
        self._window.__exit__(None, None, None)
        xspace = self._session.stop()
        self._session = None
        out = self.out_dir / "plugins" / "profile" / "window"
        out.mkdir(parents=True)
        (out / "trace.xplane.pb").write_bytes(xspace)

    def reduce(self) -> dict:
        paths = glob.glob(str(self.out_dir / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace under {self.out_dir}, "
                               f"found {paths}")
        return reduce(events(paths[0]))


def events(xplane_path: str) -> dict:
    """The trace in plain form. ``devices`` maps each device plane to its
    operations (``start``/``dur`` in ns, one entry per execution, and
    ``op_ns``: device ns per operation name) and its ``modules``
    (``[name, start_ns, duration_ns]`` per program execution); ``host``
    holds every event of the host threads in that form; ``planes`` names
    the lines of each plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane_path))
    out = {"devices": {}, "host": [], "planes": {}}
    for plane in pd.planes:
        out["planes"][plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith("/device:"):
            dev = {"start": [], "dur": [], "op_ns": {}, "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    op_ns = dev["op_ns"]
                    for e in line.events:
                        d = e.duration_ns
                        dev["start"].append(e.start_ns)
                        dev["dur"].append(d)
                        op_ns[e.name] = op_ns.get(e.name, 0) + d
                elif line.name == MODULES_LINE:
                    dev["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
            if dev["start"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
    return out


def _busy(start, dur, w0: float, w1: float):
    """Length of the union of the op intervals clipped to ``[w0, w1]``,
    and the longest idle gaps in it as ``(length, midpoint)`` pairs."""
    s0 = np.asarray(start, np.float64)
    s = np.clip(s0, w0, w1)
    e = np.clip(s0 + np.asarray(dur, np.float64), w0, w1)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(np.concatenate([[w0], e]))
    starts = np.concatenate([s, [w1]])
    gap = np.maximum(starts - reach, 0.0)       # idle before each start
    mids = (starts + reach) / 2
    keep = np.argsort(-gap, kind="stable")[:TOP]
    return ((w1 - w0) - float(gap.sum()),
            [(float(gap[i]), float(mids[i])) for i in keep if gap[i] > 0])


def _label(host: list, t: float) -> str:
    """Innermost host span covering time ``t``."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "(no host span)"


def reduce(ev: dict) -> dict:
    """Busy and idle time, module time, top operations and idle gaps of
    the traced window. Times in the summary are seconds."""
    wins = [(s, s + d) for name, s, d in ev["host"] if name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(wins)}")
    w0, w1 = wins[0]
    window_ns = w1 - w0
    idle, busy, module_ns, op_ns, gaps = {}, {}, {}, {}, []
    for dev, d in ev["devices"].items():
        b, g = _busy(d["start"], d["dur"], w0, w1)
        busy[dev] = b / 1e9
        idle[dev] = 1.0 - b / window_ns
        gaps += g
        for name, _, du in d["modules"]:
            module_ns[name] = module_ns.get(name, 0) + du
        for name, du in d["op_ns"].items():
            op_ns[name] = op_ns.get(name, 0) + du
    gaps.sort(reverse=True)
    host = [h for h in ev["host"] if h[0] != WINDOW_SPAN]
    summary = {
        "planes": ev.get("planes", {}),
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy.values()) / len(busy) if busy else 0.0,
        "busy_s_by_device": busy,
        "ops_by_device": {dev: len(d["start"])
                          for dev, d in ev["devices"].items()},
        "idle_share_by_device": idle,
        "module_s": {k: v / 1e9 for k, v in module_ns.items()},
        "top_ops": [[k, v / 1e9] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(host, mid), g / 1e9]
                      for g, mid in gaps[:TOP]],
    }
    return {"summary": summary}


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: top device operations and the
    longest idle gaps by host activity."""
    s = red["summary"]
    return {"device_ops": s["top_ops"], "idle_gaps": s["idle_gaps"]}
