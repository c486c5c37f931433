"""In-scan telemetry & trace subsystem (DESIGN.md §8).

Every aggregate ``SimResult`` reports is end-of-run; this module adds the
*when* and the *what sequence*: a :class:`TraceConfig` hung off
``SimConfig.trace`` threads bounded accumulators through the existing
``lax.scan`` and post-processes them into a :class:`SimTrace` attached
to the result. Three capture planes, all jit-safe and memory-bounded:

**1. Strided time series.** Every ``stride`` slots (at the *end* of each
window, plus the final slot) the scan snapshots instantaneous queue
occupancy (per-host downlink, per-uplink TOR) and the cumulative
counters (downlink busy/wasted, uplink busy, per-priority-level drained
chunks for both tiers, outstanding-grant backlog per receiver).
Cumulative snapshots diff into exact per-window rates in post-processing
(:meth:`SimTrace.busy_frac`, :meth:`SimTrace.prio_usage` — the paper's
Fig. 13 priority-usage-over-time view), so no division happens in the
scan.

**2. Protocol event ledger.** A fixed-capacity ``(ledger_cap, 5)`` int32
table of ``(slot, kind, msg, host, value)`` rows. Event kinds: grant
issued/raised (``EV_GRANT``), receiver preemption — an incomplete
message evicted from the active grant set (``EV_PREEMPT``), fault chunk
loss per message (``EV_LOSS``), ring-overflow drops (``EV_OVERFLOW``,
msg/host = -1), receiver RESEND and sender-timeout rewinds
(``EV_RESEND`` / ``EV_TIMEOUT``, from the ``faults.apply_recovery``
tap), and message completion (``EV_COMPLETE``). Appends are a masked
cumsum scatter with out-of-bounds drop: once the ledger fills, later
events fall off and ``events_dropped`` counts them — capture stays
jit-safe and bounded no matter how eventful the run is. Rows are
recorded in slot order.

**3. Host wall-clock.** :func:`span` times the host side of
``simulate`` and ``run_sweep`` on every call: named, nested spans kept in
a bounded in-memory record (:func:`host_spans`, the last ``SPAN_CAP``)
and entered as ``jax.profiler.TraceAnnotation`` so that each one lands
in a profiler trace on the device ops' clock. ``TraceConfig(wallclock=
True)`` makes ``simulate`` run the scan through the AOT path
(``jit.lower`` → ``.compile()`` → execute, the ``sim.lower`` /
``sim.compile`` / ``sim.execute`` spans) and records the split in
``SimTrace.timings``.

``SimConfig.trace=None`` (the default) and ``TraceConfig(enabled=False)``
keep the scan free of every array and op defined here: the untraced
program is bit-identical to the committed fabric goldens on both
backends (tests/test_telemetry.py), so the default path pays zero cost.
Under ``run_sweep``'s vmapped batches the full series are reduced to
streaming scalars per run (:meth:`SimTrace.reduce`) so mega-sweeps never
materialize ``(N, T, H)`` histories.

Exporters: :meth:`SimTrace.to_perfetto` (Chrome trace-event JSON,
loadable in https://ui.perfetto.dev), :meth:`SimTrace.to_timeseries_json`
(JSON-safe dict for the bench cache), and ``scripts/export_trace.py``
(CLI around both).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import json
import statistics
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.protocols import I32, grant_preempted

# ------------------------------------------------------------ event kinds --

EV_GRANT = 0       # receiver granted / raised a message's grant (value=slots)
EV_PREEMPT = 1     # incomplete msg evicted from the active set (value=remain)
EV_LOSS = 2        # fault-injected chunk drops on a message (value=chunks)
EV_OVERFLOW = 3    # ring-overflow drops, either tier (msg=host=-1, value=n)
EV_RESEND = 4      # receiver RESEND rewound the sender (value=chunks)
EV_TIMEOUT = 5     # sender fallback timeout rewound (value=chunks)
EV_COMPLETE = 6    # message completed (value=elapsed slots)

EV_NAMES = {EV_GRANT: "grant", EV_PREEMPT: "preempt", EV_LOSS: "loss",
            EV_OVERFLOW: "overflow", EV_RESEND: "resend",
            EV_TIMEOUT: "timeout", EV_COMPLETE: "complete"}
EV_COLUMNS = ("slot", "kind", "msg", "host", "value")


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Telemetry capture parameters (hashable: rides the jit-static
    ``SimConfig``). ``TraceConfig(enabled=False)`` is the disabled
    sentinel — bit-identical to ``SimConfig.trace=None``."""
    enabled: bool = True
    stride: int = 16                # slots per time-series sample window
    ledger_cap: int = 4096          # event rows kept; 0 disables the ledger
    wallclock: bool = False         # exact AOT trace/compile/execute split
    wallclock_repeats: int = 1      # execute N times, report each and
    #   their median (the scan is deterministic, so repeats change
    #   nothing but the timing)

    def validate(self) -> None:
        if self.stride < 1:
            raise ValueError(f"TraceConfig.stride must be >= 1, got "
                             f"{self.stride}")
        if self.ledger_cap < 0:
            raise ValueError(f"TraceConfig.ledger_cap must be >= 0, got "
                             f"{self.ledger_cap}")
        if self.wallclock_repeats < 1:
            raise ValueError(f"TraceConfig.wallclock_repeats must be "
                             f">= 1, got {self.wallclock_repeats}")


def n_samples(cfg) -> int:
    """Time-series rows for a run: one per full/partial stride window."""
    return -(-cfg.max_slots // cfg.trace.stride)


# ------------------------------------------------------------- scan state --

def init_trace_state(cfg, M: int) -> dict:
    """Telemetry scan state; only trace-enabled configs carry it."""
    tr = cfg.trace
    T, H, P = n_samples(cfg), cfg.n_hosts, cfg.n_prios
    z = lambda shape: jnp.zeros(shape, I32)  # noqa: E731
    st = {
        "tr_q": z((T, H)),           # instantaneous downlink queue (chunks)
        "tr_grant_out": z((T, H)),   # outstanding granted-not-received slots
        "tr_busy": z((T,)),          # cumulative downlink-busy slot count
        "tr_wasted": z((T,)),        # cumulative idle-but-withheld count
        "tr_upbusy": z((T,)),        # cumulative sender-uplink busy count
        "tr_prio": z((T, P)),        # cumulative downlink drains per level
        "tr_active": jnp.zeros((M,), bool),   # last slot's active grant set
    }
    if cfg.fabric_on:
        U = cfg.fabric.n_uplinks_total(cfg.n_hosts)
        st["tr_uq"] = z((T, U))      # instantaneous TOR uplink queues
        st["tr_uprio"] = z((T, P))   # cumulative uplink drains per level
        st["tr_uprio_c"] = z((P,))   # running counter (fabric.uplink_drain)
    if cfg.host_rx_on:
        st["tr_hq"] = z((T, H))      # instantaneous host RX-ring backlog
    if tr.ledger_cap > 0:
        st["tr_ev"] = jnp.full((tr.ledger_cap, 5), -1, I32)
        st["tr_ev_n"] = z(())        # total events SEEN (incl. dropped)
        if cfg.faults_on:
            st["tr_resend"] = z((M,))   # chunks rewound by receiver RESEND
            st["tr_timeout"] = z((M,))  # chunks rewound by sender timeout
    return st


def snapshot(cfg, st) -> dict:
    """Pre-step references needed to difference per-slot event deltas
    (arrays are functional, so this costs nothing)."""
    prev = {"grant_r": st["grant_r"], "completion": st["completion"],
            "lost": st["lost"]}
    if cfg.fabric_on:
        prev["u_lost"] = st["u_lost"]
    if cfg.faults_on:
        prev["msg_lost"] = st["msg_lost"]
    return prev


def _append_events(cfg, st, mask, kind, msg, host, value, now):
    """Masked bulk-append into the fixed ledger: each masked candidate
    takes the next free row; candidates past capacity drop out of bounds
    (``mode="drop"``) and only the seen-counter keeps growing."""
    E = cfg.trace.ledger_cap
    pos = st["tr_ev_n"] + jnp.cumsum(mask.astype(I32)) - mask.astype(I32)
    idx = jnp.where(mask & (pos < E), pos, E)
    rows = jnp.stack([jnp.full_like(kind, now), kind, msg, host, value],
                     axis=1).astype(I32)
    return {**st, "tr_ev": st["tr_ev"].at[idx].set(rows, mode="drop"),
            "tr_ev_n": st["tr_ev_n"] + mask.sum(dtype=I32)}


def _slot_events(cfg, st, S, now, prev, active):
    """Collect this slot's protocol events into the ledger."""
    M = S["size"].shape[0]
    dst, msg_ids = S["dst"], S["msg_ids"]

    def cand(mask, kind, value, msg=msg_ids, host=dst):
        return (mask, jnp.full((mask.shape[0],), kind, I32), msg, host,
                value)

    cands = [
        cand(st["grant_r"] > prev["grant_r"], EV_GRANT, st["grant_r"]),
        cand(grant_preempted(st["tr_active"], active, st["completion"]),
             EV_PREEMPT, jnp.maximum(S["size"] - st["recv"], 0)),
    ]
    if cfg.faults_on:
        lost_d = st["msg_lost"] - prev["msg_lost"]
        cands.append(cand(lost_d > 0, EV_LOSS, lost_d))
        cands.append(cand(st["tr_resend"] > 0, EV_RESEND, st["tr_resend"]))
        cands.append(cand(st["tr_timeout"] > 0, EV_TIMEOUT,
                          st["tr_timeout"]))
    # ring-overflow drops have no message attribution: one scalar row
    over_d = st["lost"] - prev["lost"]
    if cfg.fabric_on:
        over_d = over_d + st["u_lost"] - prev["u_lost"]
    neg1 = jnp.full((1,), -1, I32)
    cands.append(cand((over_d > 0)[None], EV_OVERFLOW, over_d[None],
                      msg=neg1, host=neg1))
    cands.append(cand(st["completion"] == now, EV_COMPLETE,
                      now - S["arrival"] + 1))

    mask = jnp.concatenate([c[0] for c in cands])
    kind = jnp.concatenate([c[1] for c in cands])
    msg = jnp.concatenate([c[2] for c in cands]).astype(I32)
    host = jnp.concatenate([c[3] for c in cands]).astype(I32)
    value = jnp.concatenate([c[4] for c in cands]).astype(I32)
    return _append_events(cfg, st, mask, kind, msg, host, value, now)


def capture_slot(cfg, st, S, now, prev, active, qlen):
    """End-of-slot telemetry hook (called by ``sim.step_fn`` only when
    ``cfg.trace_on``): append this slot's events, then — on window
    boundaries — write one strided time-series row."""
    tr = cfg.trace
    T, H = n_samples(cfg), cfg.n_hosts

    if tr.ledger_cap > 0:
        st = _slot_events(cfg, st, S, now, prev, active)
    st = {**st, "tr_active": active}

    # sample at each window's END (cumulative diffs = exact window rates)
    stride = tr.stride
    do = (now % stride == stride - 1) | (now == cfg.max_slots - 1)
    row = jnp.where(do, now // stride, T)            # OOB drop when idle
    outstanding = jnp.where(st["completion"] < 0,
                            jnp.maximum(st["grant_r"] - st["recv"], 0), 0)
    grant_out = jax.ops.segment_sum(outstanding, S["dst"], num_segments=H)
    upd = {
        "tr_q": st["tr_q"].at[row].set(qlen, mode="drop"),
        "tr_grant_out": st["tr_grant_out"].at[row].set(
            grant_out.astype(I32), mode="drop"),
        "tr_busy": st["tr_busy"].at[row].set(st["busy"].sum(),
                                             mode="drop"),
        "tr_wasted": st["tr_wasted"].at[row].set(st["wasted"].sum(),
                                                 mode="drop"),
        "tr_upbusy": st["tr_upbusy"].at[row].set(st["uplink_busy"].sum(),
                                                 mode="drop"),
        "tr_prio": st["tr_prio"].at[row].set(st["prio_drained"],
                                             mode="drop"),
    }
    if cfg.fabric_on:
        upd["tr_uq"] = st["tr_uq"].at[row].set(
            st["u_valid"].sum(axis=1).astype(I32), mode="drop")
        upd["tr_uprio"] = st["tr_uprio"].at[row].set(st["tr_uprio_c"],
                                                     mode="drop")
    if cfg.host_rx_on:
        upd["tr_hq"] = st["tr_hq"].at[row].set(
            (st["h_rx_tail"] - st["h_rx_head"]).astype(I32), mode="drop")
    return {**st, **upd}


# --------------------------------------------------------------- SimTrace --

@dataclasses.dataclass
class SimTrace:
    """One run's captured telemetry, post-processed to numpy.

    Cumulative series (``*_cum``) snapshot the scan's running counters at
    each sample slot; the windowed accessors difference them into exact
    per-window rates. ``events`` is the ledger's recorded prefix (slot
    order); ``n_events_seen`` counts every event observed including the
    ``events_dropped`` that fell off a full ledger.
    """
    stride: int
    slot_bytes: int
    n_hosts: int
    max_slots: int
    sample_slots: np.ndarray             # (T,) end slot of each window
    q_bytes: np.ndarray                  # (T, H) downlink queue bytes
    grant_out_bytes: np.ndarray          # (T, H) granted-not-received bytes
    busy_cum: np.ndarray                 # (T,) downlink busy slots (all hosts)
    wasted_cum: np.ndarray               # (T,)
    uplink_busy_cum: np.ndarray          # (T,) sender-NIC busy slots
    prio_drained_cum_bytes: np.ndarray   # (T, P) downlink drains per level
    up_q_bytes: np.ndarray | None        # (T, U) TOR uplink queue bytes
    up_prio_drained_cum_bytes: np.ndarray | None   # (T, P)
    events: np.ndarray                   # (n, 5) int32, EV_COLUMNS order
    ledger_cap: int
    n_events_seen: int
    timings: dict | None = None          # wallclock=True: AOT stage split
    host_rx_q_chunks: np.ndarray | None = None   # (T, H) host RX backlog

    # ------------------------------------------------------------ derived

    @property
    def n_events(self) -> int:
        return int(self.events.shape[0])

    @property
    def events_dropped(self) -> int:
        return max(0, self.n_events_seen - self.n_events)

    def _widths(self) -> np.ndarray:
        return np.diff(self.sample_slots, prepend=-1)

    def busy_frac(self) -> np.ndarray:
        """(T,) windowed downlink busy fraction (all hosts pooled)."""
        return np.diff(self.busy_cum, prepend=0) \
            / (self._widths() * self.n_hosts)

    def wasted_frac(self) -> np.ndarray:
        return np.diff(self.wasted_cum, prepend=0) \
            / (self._widths() * self.n_hosts)

    def uplink_busy_frac(self) -> np.ndarray:
        return np.diff(self.uplink_busy_cum, prepend=0) \
            / (self._widths() * self.n_hosts)

    def prio_usage(self, tier: str = "down") -> np.ndarray:
        """(T, P) per-window drained bytes per priority level — the
        Fig. 13 view. ``tier`` is "down" or (fabric runs) "up"."""
        cum = self.prio_drained_cum_bytes if tier == "down" \
            else self.up_prio_drained_cum_bytes
        if cum is None:
            raise ValueError(f"no {tier!r}-tier priority series captured")
        return np.diff(cum, prepend=0, axis=0)

    def events_of(self, kind: int) -> np.ndarray:
        return self.events[self.events[:, 1] == kind]

    # ------------------------------------------------------------ reduce

    def reduce(self) -> dict:
        """Streaming-stat scalars (the only thing vmapped sweeps keep)."""
        return {
            "stride": self.stride,
            "samples": int(len(self.sample_slots)),
            "n_events": self.n_events,
            "n_events_seen": int(self.n_events_seen),
            "events_dropped": self.events_dropped,
            "ledger_cap": self.ledger_cap,
            "q_peak_bytes": int(self.q_bytes.max()) if self.q_bytes.size
            else 0,
            "grant_out_peak_bytes": int(self.grant_out_bytes.max())
            if self.grant_out_bytes.size else 0,
            "up_q_peak_bytes": int(self.up_q_bytes.max())
            if self.up_q_bytes is not None and self.up_q_bytes.size else None,
            "host_rx_q_peak_chunks": int(self.host_rx_q_chunks.max())
            if self.host_rx_q_chunks is not None
            and self.host_rx_q_chunks.size else None,
            "timings": self.timings,
        }

    # --------------------------------------------------------- exporters

    def to_timeseries_json(self) -> dict:
        """JSON-safe time-series dict (the bench-cache form)."""
        out = {
            "stride": self.stride, "slot_bytes": self.slot_bytes,
            "n_hosts": self.n_hosts, "max_slots": self.max_slots,
            "sample_slots": self.sample_slots.tolist(),
            "q_bytes": self.q_bytes.tolist(),
            "grant_out_bytes": self.grant_out_bytes.tolist(),
            "busy_frac": np.round(self.busy_frac(), 6).tolist(),
            "wasted_frac": np.round(self.wasted_frac(), 6).tolist(),
            "uplink_busy_frac":
                np.round(self.uplink_busy_frac(), 6).tolist(),
            "prio_drained_bytes": self.prio_usage("down").tolist(),
            "events": {"columns": list(EV_COLUMNS),
                       "rows": self.events.tolist(),
                       "kinds": {v: k for k, v in EV_NAMES.items()},
                       "n_seen": int(self.n_events_seen),
                       "dropped": self.events_dropped},
            "timings": self.timings,
        }
        if self.up_q_bytes is not None:
            out["up_q_bytes"] = self.up_q_bytes.tolist()
            out["up_prio_drained_bytes"] = self.prio_usage("up").tolist()
        if self.host_rx_q_chunks is not None:
            out["host_rx_q_chunks"] = self.host_rx_q_chunks.tolist()
        return out

    def to_perfetto(self, path=None) -> dict:
        """Chrome trace-event / Perfetto JSON. One slot maps to one
        microsecond of trace time. Counter tracks carry the strided
        series; ledger rows become instant events on per-host tracks;
        completions additionally become duration ("X") slices spanning
        arrival→completion. Load at https://ui.perfetto.dev."""
        ev: list[dict] = []

        def meta(pid, name):
            ev.append({"ph": "M", "pid": pid, "tid": 0,
                       "name": "process_name", "args": {"name": name}})

        meta(0, "time series")
        meta(1, "protocol events")
        meta(2, "messages")

        P = self.prio_drained_cum_bytes.shape[1]
        prio = self.prio_usage("down")
        for k, t in enumerate(self.sample_slots.tolist()):
            ev.append({"ph": "C", "pid": 0, "tid": 0, "ts": t,
                       "name": "downlink_q_bytes",
                       "args": {f"h{h}": int(self.q_bytes[k, h])
                                for h in range(self.n_hosts)}})
            ev.append({"ph": "C", "pid": 0, "tid": 0, "ts": t,
                       "name": "grant_outstanding_bytes",
                       "args": {f"h{h}": int(self.grant_out_bytes[k, h])
                                for h in range(self.n_hosts)}})
            ev.append({"ph": "C", "pid": 0, "tid": 0, "ts": t,
                       "name": "prio_drained_bytes",
                       "args": {f"p{p}": int(prio[k, p])
                                for p in range(P)}})
            if self.up_q_bytes is not None:
                ev.append({"ph": "C", "pid": 0, "tid": 0, "ts": t,
                           "name": "tor_uplink_q_bytes",
                           "args": {f"u{u}": int(self.up_q_bytes[k, u])
                                    for u in
                                    range(self.up_q_bytes.shape[1])}})
            if self.host_rx_q_chunks is not None:
                ev.append({"ph": "C", "pid": 0, "tid": 0, "ts": t,
                           "name": "host_rx_q_chunks",
                           "args":
                           {f"h{h}": int(self.host_rx_q_chunks[k, h])
                            for h in range(self.n_hosts)}})

        for slot, kind, msg, host, value in self.events.tolist():
            ev.append({"ph": "i", "s": "t", "pid": 1,
                       "tid": int(max(host, 0)), "ts": int(slot),
                       "name": EV_NAMES.get(int(kind), f"kind{kind}"),
                       "args": {"msg": int(msg), "value": int(value)}})
            if kind == EV_COMPLETE:
                ev.append({"ph": "X", "pid": 2, "tid": int(max(host, 0)),
                           "ts": int(slot) - int(value) + 1,
                           "dur": int(value), "name": f"msg{int(msg)}",
                           "args": {"elapsed_slots": int(value)}})

        doc = {"displayTimeUnit": "ms", "traceEvents": ev,
               "otherData": {"slot_bytes": self.slot_bytes,
                             "stride": self.stride,
                             "events_dropped": self.events_dropped}}
        if path is not None:
            from pathlib import Path
            Path(path).write_text(json.dumps(doc))
        return doc


def finalize_trace(cfg, st: dict, timings: dict | None = None) -> SimTrace:
    """Build a :class:`SimTrace` from one run's (numpy) final scan state."""
    tr = cfg.trace
    T = n_samples(cfg)
    sb = cfg.slot_bytes
    sample_slots = np.minimum(np.arange(1, T + 1) * tr.stride - 1,
                              cfg.max_slots - 1).astype(np.int64)
    if tr.ledger_cap > 0:
        seen = int(st["tr_ev_n"])
        n = min(seen, tr.ledger_cap)
        events = np.asarray(st["tr_ev"][:n]).astype(np.int32)
    else:
        seen = 0
        events = np.zeros((0, 5), np.int32)
    return SimTrace(
        stride=tr.stride, slot_bytes=sb, n_hosts=cfg.n_hosts,
        max_slots=cfg.max_slots, sample_slots=sample_slots,
        q_bytes=np.asarray(st["tr_q"]) * sb,
        grant_out_bytes=np.asarray(st["tr_grant_out"]) * sb,
        busy_cum=np.asarray(st["tr_busy"]),
        wasted_cum=np.asarray(st["tr_wasted"]),
        uplink_busy_cum=np.asarray(st["tr_upbusy"]),
        prio_drained_cum_bytes=np.asarray(st["tr_prio"]) * sb,
        up_q_bytes=np.asarray(st["tr_uq"]) * sb if cfg.fabric_on else None,
        up_prio_drained_cum_bytes=np.asarray(st["tr_uprio"]) * sb
        if cfg.fabric_on else None,
        events=events, ledger_cap=tr.ledger_cap, n_events_seen=seen,
        timings=timings,
        host_rx_q_chunks=np.asarray(st["tr_hq"]) if cfg.host_rx_on
        else None,
    )


def reduce_state(cfg, st: dict) -> dict:
    """Device-side trace reduction for streaming sweeps (DESIGN.md §9):
    the :meth:`SimTrace.reduce` peaks/counts computed INSIDE the compiled
    program, so sharded mega-sweeps gather a handful of trace scalars per
    run instead of the ``(T, H)`` series. Works unchanged under chunked
    scans — the strided rows are written by global slot index, so the
    series (and therefore its max) is identical to the flat scan's."""
    out = {"tr_q_peak": st["tr_q"].max(),
           "tr_go_peak": st["tr_grant_out"].max()}
    if cfg.fabric_on:
        out["tr_uq_peak"] = st["tr_uq"].max()
    if cfg.host_rx_on:
        out["tr_hq_peak"] = st["tr_hq"].max()
    if cfg.ledger_on:
        out["tr_ev_seen"] = st["tr_ev_n"]
    return out


# ------------------------------------------------------------- wall clock --

SPAN_CAP = 4096                    # host spans kept, newest last

_SPANS: collections.deque = collections.deque(maxlen=SPAN_CAP)
_OPEN = threading.local()          # this thread's stack of open spans
_CALLS = itertools.count()


@contextlib.contextmanager
def span(name: str, **counts):
    """Time a block of host work as a named span.

    The span enters ``jax.profiler.TraceAnnotation(name)``, so a profiler
    trace shows it on the same clock as the device ops, and on exit it
    appends ``{name, start_ns, end_ns, parent, call, counts}`` to the
    process's bounded span record (``time.perf_counter_ns``). ``parent``
    is the name of the enclosing span; ``call`` is shared by a root span
    and everything nested in it. The yielded record is the one appended,
    so a caller may read its times after the block or add counts that
    are known only inside it."""
    stack = _OPEN.__dict__.setdefault("stack", [])
    parent = stack[-1] if stack else None
    rec = {"name": name, "start_ns": 0, "end_ns": 0,
           "parent": parent["name"] if parent else None,
           "call": parent["call"] if parent else next(_CALLS),
           "counts": counts}
    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(name):
            rec["start_ns"] = time.perf_counter_ns()
            try:
                yield rec
            finally:
                rec["end_ns"] = time.perf_counter_ns()
    finally:
        stack.pop()
        _SPANS.append(rec)


def host_spans() -> list[dict]:
    """The recorded host spans, oldest first (children before parents:
    a span is recorded when it ends)."""
    return list(_SPANS)


def clear_spans() -> None:
    _SPANS.clear()


def timed_aot_run(jit_fn, all_args: tuple, dynamic_args: tuple,
                  repeats: int = 1) -> tuple[Any, dict]:
    """Run a jitted function through the AOT path and return
    ``(result, timings)`` with the trace / compile / execute split in
    seconds, each phase also a span (``sim.lower``, ``sim.compile``, one
    ``sim.execute`` per repeat). ``all_args`` is the full positional
    argument list (as the jitted function would be called);
    ``dynamic_args`` are the non-static subset, in order, passed again
    at execute. ``repeats > 1`` executes the compiled program N times:
    ``execute_each_s`` lists every repeat and ``execute_s`` is their
    median (only meaningful for deterministic functions)."""
    def secs(rec):
        return (rec["end_ns"] - rec["start_ns"]) / 1e9

    with span("sim.lower") as lo:
        lowered = jit_fn.lower(*all_args)
    with span("sim.compile") as co:
        compiled = lowered.compile()
    execs = []
    for _ in range(max(repeats, 1)):
        with span("sim.execute") as ex:
            out = compiled(*dynamic_args)
            jax.block_until_ready(out)
        execs.append(secs(ex))
    return out, {"trace_s": round(secs(lo), 4),
                 "compile_s": round(secs(co), 4),
                 "execute_s": round(statistics.median(execs), 4),
                 "execute_each_s": [round(e, 4) for e in execs],
                 "execute_repeats": len(execs)}


__all__ = ["TraceConfig", "SimTrace", "init_trace_state", "snapshot",
           "capture_slot", "finalize_trace", "reduce_state",
           "timed_aot_run", "n_samples", "span", "host_spans",
           "clear_spans", "SPAN_CAP",
           "EV_GRANT", "EV_PREEMPT", "EV_LOSS", "EV_OVERFLOW", "EV_RESEND",
           "EV_TIMEOUT", "EV_COMPLETE", "EV_NAMES", "EV_COLUMNS"]
