"""Slotted packet-level datacenter network simulator, fully vectorized in JAX.

Faithful reproduction of Homa's mechanisms (paper §3) plus the comparison
protocols, as one ``lax.scan`` over link-time slots:

  senders     chunk order + priority stamping from the protocol's
              ``SenderPolicy`` (SRPT for Homa), blind until RTTbytes,
              then grant-clocked; optionally gated by a host/NIC
              stage modeling per-chunk CPU cost and interrupt
              batching (``SimConfig.host``, ``repro.core.hostmodel``,
              DESIGN.md §10)
  network     fixed delay (single switch, the default), or a two-tier
              leaf-spine fabric with per-TOR uplink priority queues and
              configurable oversubscription (``SimConfig.fabric``,
              paper §5.2 topology — see ``repro.core.fabric``)
  downlinks   8-level priority FIFOs per receiver (the TOR egress port);
              one slot drained per tick; exact priority-then-FIFO arbitration
  receivers   grants + scheduled-priority assignment + overcommit degree
              from the protocol's ``ReceiverPolicy`` (Homa: top-K SRPT with
              controlled overcommitment, dynamic scheduled priorities
              lowest-levels-first, §3.4/Fig. 5), delayed visibility at
              senders (grant RTT); with a host model, drained chunks
              pass through a bounded per-host RX service FIFO before
              they reach ``recv`` — so software overhead delays grants
              AND completions (the §5.3 implementation-vs-sim gap)

Time unit: one slot = ``slot_bytes`` of link time (default 256 B ~ 205 ns at
10 Gbps; rtt_slots=38 -> RTTbytes ~ 9.7 KB as in the paper). All sizes are
tracked in slots; the final partial packet of a message occupies a full slot
(packetization overhead).

Protocols are pluggable policies (``repro.core.protocols``, DESIGN.md §1):
homa | basic | phost | pias | pfabric | ndp are registered out of the box
(see DESIGN.md §3 for the approximations in each baseline). ``step_fn`` is
policy-agnostic orchestration — it never inspects the protocol name.

The per-slot arbitration hot path (downlink drain, TOR uplink drain,
receiver grant-set top-K) is backend-dispatched (DESIGN.md §6):
``SimConfig.backend = "reference" | "pallas"`` (default from
``$SIM_BACKEND``) selects pure-jnp math or the ``kernels.arbiter``
Pallas kernels — bit-identical by contract, golden-tested.

Entry points:

  ``simulate(cfg, table)``    one run -> :class:`SimResult`
  ``run_sweep(cfg, spec)``    N independent runs described by one
                              :class:`repro.core.sweep.SweepSpec`: vmapped
                              per static-parameter group, optionally
                              device-sharded (``shard_map``) with chunked
                              scans + streaming stats (DESIGN.md §9)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.workloads import MessageTable
from repro.core.priorities import PriorityAllocation, allocate_priorities, \
    pias_thresholds
from repro.core.protocols import (Protocol, get_protocol,
                                  registered_protocols, MSG_BITS, MSG_MOD,
                                  BIG, I32)
from repro.core.fabric import (FabricConfig, spine_hash, ring_insert,
                               drain_select, init_fabric_state,
                               route_chunks, uplink_drain)
from repro.core.faults import (FaultConfig, init_fault_state,
                               apply_recovery, host_down_mask,
                               link_down_mask)
from repro.core.hostmodel import HostConfig, as_host_config, get_host_model
from repro.core import telemetry
from repro.core.telemetry import TraceConfig, SimTrace
from repro.core.results import SimResult, bucketed_percentiles
from repro.kernels.arbiter.dispatch import resolve_backend, \
    resolve_interpret

# ``step_fn``'s stages, in slot order: the ``jax.named_scope`` each runs
# under (a stage a configuration does not run emits no ops)
STAGES = ("fused_precompute", "grants", "sender_select", "route",
          "uplink_drain", "downlink_drain", "stats", "recovery",
          "post_step", "telemetry")

@dataclasses.dataclass(frozen=True)
class SimConfig:
    n_hosts: int = 16
    slot_bytes: int = 256
    n_prios: int = 8
    rtt_slots: int = 38                 # ~9.7 KB at 256 B slots
    net_delay_slots: int = 12           # sender NIC -> dst TOR eligibility
    grant_delay_slots: int = 19         # receiver decision -> sender visibility
    protocol: str = "homa"
    overcommit: int | None = None       # None: = n_sched (homa); basic: all
    ring_cap: int = 1024                # per-dst buffered chunks (TOR egress)
    phost_timeout_slots: int = 114      # ~3 RTT
    max_slots: int = 20_000
    fabric: FabricConfig | None = None  # None: single switch (DESIGN.md §5)
    # host/NIC software-overhead stage (repro.core.hostmodel,
    # DESIGN.md §10): HostConfig | preset name ("ideal" | "kernel_stack"
    # | "kernel_bypass") | dict | None. None and zero-cost configs are
    # structurally skipped — bit-identical to the host-free simulator.
    host: HostConfig | str | dict | None = None
    # in-scan telemetry capture (repro.core.telemetry, DESIGN.md §8);
    # None (the default) keeps the scan free of every trace array and op
    # — bit-identical to the pre-telemetry simulator
    trace: TraceConfig | None = None
    # compute backend for the per-slot arbitration hot path (DESIGN.md §6):
    # "reference" (pure-jnp) | "pallas" (kernels.arbiter, one kernel per
    # stage) | "pallas_fused" (all of a slot's arbitration in ONE kernel
    # launch — DESIGN.md §11); None resolves from $SIM_BACKEND. All
    # backends are bit-identical by contract.
    backend: str | None = None
    # pallas interpret mode; None auto-selects (interpreted off-TPU,
    # $SIM_PALLAS_INTERPRET overrides). Resolved to a concrete bool here
    # so jit retraces when the effective mode changes.
    pallas_interpret: bool | None = None

    def __post_init__(self):
        get_protocol(self.protocol)     # ValueError on unknown protocol
        object.__setattr__(self, "backend", resolve_backend(self.backend))
        object.__setattr__(self, "pallas_interpret",
                           resolve_interpret(self.pallas_interpret))
        if self.fabric is not None:
            self.fabric.validate(self.n_hosts)
        object.__setattr__(self, "host", as_host_config(self.host))
        if self.host is not None:
            self.host.validate()
        # JSON round-trip convenience: accept a plain dict for trace
        if isinstance(self.trace, dict):
            object.__setattr__(self, "trace", TraceConfig(**self.trace))
        if self.trace is not None:
            self.trace.validate()

    @property
    def rtt_bytes(self) -> int:
        return self.rtt_slots * self.slot_bytes

    @property
    def fabric_on(self) -> bool:
        """True iff the leaf-spine tier is modeled (``FabricConfig(None)``
        and ``fabric=None`` both mean the single-switch fast path)."""
        return self.fabric is not None and self.fabric.enabled

    @property
    def fused_on(self) -> bool:
        """True iff the fused per-slot mega-kernel backend is selected
        (DESIGN.md §11). Stages whose hoist-to-slot-start precondition a
        config doesn't meet (a zero ``net_delay_slots`` / ``leaf_delay_
        slots`` makes same-slot insertions immediately eligible) fall
        back to the staged pallas kernels per stage — still
        bit-identical, never wrong."""
        return self.backend == "pallas_fused"

    @property
    def faults_on(self) -> bool:
        """True iff the fault/recovery layer is active (DESIGN.md §7).
        Faults hang off the fabric tier; ``fabric.faults=None`` (the
        default) keeps the scan loss-free and bit-identical to the
        pre-fault simulator."""
        return self.fabric_on and self.fabric.faults is not None

    @property
    def trace_on(self) -> bool:
        """True iff in-scan telemetry capture is active (DESIGN.md §8).
        ``trace=None`` and ``TraceConfig(enabled=False)`` both keep the
        scan bit-identical to the untraced simulator."""
        return self.trace is not None and self.trace.enabled

    @property
    def ledger_on(self) -> bool:
        """True iff the protocol event ledger is captured (``trace_on``
        with a nonzero ``ledger_cap``)."""
        return self.trace_on and self.trace.ledger_cap > 0

    @property
    def host_on(self) -> bool:
        """True iff an active host/NIC stage is modeled (DESIGN.md §10).
        ``host=None`` and zero-overhead configs (the ``ideal`` preset)
        are structurally skipped — the scan is bit-identical to the
        host-free simulator (golden-enforced)."""
        return self.host is not None and not self.host.is_ideal

    @property
    def host_tx_on(self) -> bool:
        """Send-side host gate active (nonzero TX cost)."""
        return self.host_on and self.host.tx_on

    @property
    def host_rx_on(self) -> bool:
        """Receive-side host FIFO active (nonzero RX cost)."""
        return self.host_on and self.host.rx_on

    @property
    def host_model(self):
        """The registered :class:`repro.core.hostmodel.HostModel`."""
        return get_host_model(self.host.model)


def _to_slots(nbytes: np.ndarray, slot_bytes: int) -> np.ndarray:
    return np.maximum((nbytes + slot_bytes - 1) // slot_bytes, 1).astype(np.int32)


def prepare(cfg: SimConfig, table: MessageTable,
            alloc: PriorityAllocation | None = None,
            unsched_limit_bytes: int | np.ndarray | None = None):
    """Static per-message arrays for the scan."""
    proto = get_protocol(cfg.protocol)
    M = len(table.size)
    if M > MSG_MOD:
        raise ValueError(
            f"table has {M} messages but the simulator's packed sort keys "
            f"hold at most {MSG_MOD} (MSG_BITS={MSG_BITS}); split the "
            f"table into shorter runs or raise MSG_BITS in protocols.py")
    if cfg.max_slots >= 2 ** 21:
        raise ValueError(
            f"max_slots={cfg.max_slots} overflows the int32 sort-key "
            f"encoding (limit 2**21-1 = {2 ** 21 - 1}); lower max_slots "
            f"or coarsen slot_bytes so the horizon fits")
    size_slots = _to_slots(table.size, cfg.slot_bytes)

    if alloc is None:
        alloc = allocate_priorities(table.size, unsched_limit=cfg.rtt_bytes,
                                    n_prios=cfg.n_prios)

    ul = proto.unsched_limit(cfg, M, unsched_limit_bytes)
    unsched_slots = np.minimum(_to_slots(ul, cfg.slot_bytes), size_slots)
    up = proto.unsched_prio(cfg, table.size, alloc)

    # PIAS: sender-side MLFQ demotion thresholds (slots of bytes sent)
    pias_cut = pias_thresholds(table.size, cfg.n_prios)
    pias_cut_slots = _to_slots(np.asarray(pias_cut + [1 << 40]),
                               cfg.slot_bytes) if pias_cut else \
        np.array([1 << 20], np.int32)

    # unloaded baseline (slots): cross-rack chunks traverse leaf + spine,
    # so a fabric with non-default delays keeps slowdown anchored at 1.0.
    # Static so streaming sweeps can bin slowdowns inside the scan
    # (repro.core.sweep, DESIGN.md §9); _finalize reads it back.
    net_delay = np.full(M, cfg.net_delay_slots, np.int64)
    if cfg.fabric_on:
        rs = cfg.fabric.rack_size(cfg.n_hosts)
        cross = (table.src // rs) != (table.dst // rs)
        net_delay = np.where(cross, cfg.fabric.leaf_delay_slots
                             + cfg.fabric.spine_delay_slots, net_delay)

    static = {
        "src": jnp.asarray(table.src, I32),
        "dst": jnp.asarray(table.dst, I32),
        "size": jnp.asarray(size_slots, I32),
        "arrival": jnp.asarray(table.arrival_slot, I32),
        "unsched": jnp.asarray(unsched_slots, I32),
        "uprio": jnp.asarray(up, I32),
        "pias_cuts": jnp.asarray(pias_cut_slots, I32),
        "dst_onehot": jnp.asarray(
            np.arange(cfg.n_hosts)[:, None] == table.dst[None, :]),
        "msg_ids": jnp.arange(M, dtype=I32),
        "ideal": jnp.asarray(size_slots + net_delay, I32),
    }
    if cfg.fabric_on:
        # per-message ECMP spine choice (seeded, deterministic) — only
        # fabric-enabled configs carry the extra static array
        static["spine"] = jnp.asarray(spine_hash(
            table.src, table.dst, np.arange(M), cfg.fabric.seed,
            cfg.fabric.n_uplinks(cfg.n_hosts)), I32)
    return static, alloc


def _init_state(cfg: SimConfig, proto: Protocol, M: int):
    H, cap, Dg = cfg.n_hosts, cfg.ring_cap, cfg.grant_delay_slots
    z = functools.partial(jnp.zeros, dtype=I32)
    return {
        **proto.extra_state(cfg, M),          # protocol-private carry
        **(init_fabric_state(cfg) if cfg.fabric_on else {}),
        **(init_fault_state(cfg, M) if cfg.faults_on else {}),
        **(cfg.host_model.init_state(cfg, M) if cfg.host_on else {}),
        **(telemetry.init_trace_state(cfg, M) if cfg.trace_on else {}),
        "sent": z((M,)),
        "granted_s": z((M,)),                 # sender-visible grant (slots)
        "grant_r": z((M,)),                   # receiver-issued grant (slots)
        "recv": z((M,)),
        "sched_prio": z((M,)),
        "completion": jnp.full((M,), -1, I32),
        # downlink rings; a chunk's network-arrival time is r_seq +
        # net_delay_slots (enqueue time plus the fixed network delay), so
        # no separate r_time array is carried
        "r_msg": jnp.full((H, cap), -1, I32),
        "r_prio": jnp.full((H, cap), BIG, I32),   # smaller = served first
        "r_seq": jnp.full((H, cap), BIG, I32),
        "r_valid": jnp.zeros((H, cap), bool),
        # delayed receiver state (grant/prio propagation)
        "hist_grant": z((Dg, M)),
        "hist_prio": z((Dg, M)),
        # stats
        "busy": z((H,)), "wasted": z((H,)), "lost": z(()),
        "q_sum": jnp.zeros((H,), jnp.float32), "q_max": z((H,)),
        "prio_drained": z((cfg.n_prios,)),
        "uplink_busy": z((H,)),
    }


def _sender_select(cfg: SimConfig, proto: Protocol, st, S, now):
    """Pick one message per host by the sender policy's order key."""
    size, src = S["size"], S["src"]
    arrived = S["arrival"] <= now
    sendable = arrived & (st["sent"] < st["granted_s"]) & (st["sent"] < size)
    remaining = jnp.maximum(size - st["sent"], 0)
    order = proto.sender.order(cfg, st, S, now, remaining)
    key = (order << MSG_BITS) | S["msg_ids"]
    key = jnp.where(sendable, key, BIG)
    host_min = jax.ops.segment_min(key, src, num_segments=cfg.n_hosts)
    has = host_min < BIG
    chosen = jnp.where(has, host_min & (MSG_MOD - 1), MSG_MOD)   # (H,)
    return chosen, has


def _fused_precompute(cfg: SimConfig, proto: Protocol, S, n_sched: int,
                      st, now):
    """``pallas_fused`` backend (DESIGN.md §11): solve ALL of this slot's
    arbitration — downlink drain, TOR uplink drain, SRPT grant top-K —
    in one kernel launch at slot start, before the stages that normally
    interleave with them. Returns ``(st, grant_st, fused)``:

      st        slot state, with the host RX delivery already applied
                when the downlink stage is fused (its room gate is a
                kernel input; ``rx_deliver`` touches only RX-ring state
                and ``recv``, which stages 1–3 never read)
      grant_st  the state the receiver policy must see — slot-start
                ``recv`` (grants run before RX delivery in the staged
                order), everything else current
      fused     per-stage pre-solved answers: ``"down"``/``"up"`` ->
                the ``drain_select`` triple, ``"topk"`` -> ``(vals,
                idx)`` for ``ReceiverPolicy.grants``

    Hoisting the drains is bit-exact because every chunk inserted later
    in the slot is ineligible until the next slot (``net_delay_slots >=
    1`` / ``leaf_delay_slots >= 1`` / validated ``spine_delay_slots >=
    1``) and ``ring_insert`` only ever writes invalid slots, so the
    winners and their payloads are unchanged. A stage whose delay
    precondition fails is simply not fused — the staged kernel runs at
    its usual point instead."""
    from repro.kernels.arbiter import dispatch
    fuse_down = cfg.net_delay_slots >= 1
    fuse_up = cfg.fabric_on and cfg.fabric.leaf_delay_slots >= 1
    prob = proto.receiver.grant_problem(cfg, st, S, now, n_sched)
    grant_st = st
    down = up = None
    if fuse_down:
        if cfg.host_rx_on:
            recv_pre = st["recv"]
            st = cfg.host_model.rx_deliver(cfg, st, S, now)
            room = cfg.host_model.rx_room(cfg, st)
            grant_st = {**st, "recv": recv_pre}
        eligible = st["r_valid"] & (st["r_seq"] + cfg.net_delay_slots
                                    <= now)
        if cfg.faults_on and cfg.fabric.faults.tor_fail:
            eligible = eligible & ~host_down_mask(cfg, now)[:, None]
        if cfg.host_rx_on:
            st = {**st, "h_rx_stall": st["h_rx_stall"]
                  + (eligible.any(axis=1) & ~room).astype(I32)}
            eligible = eligible & room[:, None]
        down = (st["r_prio"], st["r_seq"], eligible)
    if fuse_up:
        fab = cfg.fabric
        u_elig = st["u_valid"] & (st["u_seq"] + fab.leaf_delay_slots
                                  <= now)
        fl = fab.faults
        if fl is not None and (fl.link_fail or fl.tor_fail):
            u_elig = u_elig & ~link_down_mask(cfg, now)[:, None]
        up = (st["u_prio"], st["u_seq"], u_elig)
    if down is None and up is None and prob is None:
        return st, grant_st, {}
    out = dispatch.fused_slot(down=down, up=up, topk=prob,
                              interpret=cfg.pallas_interpret)
    fused = {}
    if "down" in out:
        bp, bi = out["down"]
        fused["down"] = (bi, bp < BIG, bp)
    if "up" in out:
        bp, bi = out["up"]
        fused["up"] = (bi, bp < BIG, bp)
    if "topk" in out:
        fused["topk"] = out["topk"]
    return st, grant_st, fused


def step_fn(cfg: SimConfig, proto: Protocol, S, n_sched: int, st, now):
    """One link-time slot: policy-agnostic orchestration of receivers,
    uplinks, the network, and the priority-queue downlinks.

    Each stage runs under a ``jax.named_scope`` (``STAGES``, in slot
    order), so its ops carry the stage name in their metadata and a
    profiler trace of the scan can be split by stage."""
    H, cap, Dg = cfg.n_hosts, cfg.ring_cap, cfg.grant_delay_slots
    M = S["size"].shape[0]
    scope = jax.named_scope

    # pre-step references for telemetry event deltas (DESIGN.md §8)
    tr_prev = telemetry.snapshot(cfg, st) if cfg.trace_on else None

    # ---- 0. fused backend: one kernel for ALL of this slot's
    # arbitration (DESIGN.md §11); {} when nothing is fusable
    grant_st, fused = st, {}
    if cfg.fused_on:
        with scope("fused_precompute"):
            st, grant_st, fused = _fused_precompute(cfg, proto, S, n_sched,
                                                    st, now)

    # ---- 1. receiver policy (current state), store into delay history
    with scope("grants"):
        grant_r, sched_prio, active, withheld = proto.receiver.grants(
            cfg, grant_st, S, now, n_sched, topk=fused.get("topk"))
        st = {**st, "grant_r": grant_r, "sched_prio": sched_prio}
        hist_grant = st["hist_grant"].at[now % Dg].set(grant_r)
        hist_prio = st["hist_prio"].at[now % Dg].set(sched_prio)
        # sender sees the entry written Dg-1 slots ago
        vis_idx = (now + 1) % Dg
        grant_vis = hist_grant[vis_idx]
        prio_vis = hist_prio[vis_idx]

        arrived = S["arrival"] <= now
        blind = jnp.where(arrived, S["unsched"], 0)
        granted_s = jnp.maximum(jnp.maximum(st["granted_s"], blind),
                                grant_vis)
        st = {**st, "granted_s": granted_s, "hist_grant": hist_grant,
              "hist_prio": hist_prio,
              "sched_prio": jnp.where(arrived, prio_vis, st["sched_prio"])}
        # NOTE: sender uses delayed sched_prio (the grant packet's priority)

    # ---- 2. senders pick + transmit one chunk (sender policy)
    with scope("sender_select"):
        chosen, has = _sender_select(cfg, proto, st, S, now)
        if cfg.host_tx_on:
            # host/NIC stage (DESIGN.md §10): the selected chunk only
            # makes the wire if the host's TX CPU budget covers it
            has, st = cfg.host_model.host_tx(cfg, st, has, now)
        cm = jnp.minimum(chosen, M - 1)
        unsched_chunk = st["sent"][cm] < S["unsched"][cm]
        prio_chunk = proto.sender.chunk_prio(cfg, st, S, cm, unsched_chunk,
                                             n_sched)
        sent = st["sent"].at[cm].add(jnp.where(has, 1, 0), mode="drop")
        st = {**st, "sent": sent,
              "uplink_busy": st["uplink_busy"] + has.astype(I32)}
        st = proto.sender.on_send(cfg, st, S, cm, has, now)

    # ---- 3. route chunks into the first queueing tier. Single switch:
    # straight into the destination downlink ring (true occupancy-based
    # buffering; a chunk drops only when the ring is actually full).
    # Leaf-spine fabric: same-rack chunks switch at the leaf, cross-rack
    # chunks enter their TOR's hashed uplink queue, and each uplink
    # drains one chunk per slot toward the destination downlink.
    with scope("route"):
        dsts = jnp.where(has, S["dst"][cm], H)               # sentinel H
        if not cfg.fabric_on:
            r_msg, r_prio, r_seq, r_valid, n_drop = ring_insert(
                st["r_msg"], st["r_prio"], st["r_seq"], st["r_valid"],
                dsts, has, cm, prio_chunk, jnp.full_like(dsts, now))
            st = {**st, "r_msg": r_msg, "r_prio": r_prio, "r_seq": r_seq,
                  "r_valid": r_valid, "lost": st["lost"] + n_drop}
        else:
            st = route_chunks(cfg, st, S, cm, has, dsts, prio_chunk, now)
    if cfg.fabric_on:
        with scope("uplink_drain"):
            st = uplink_drain(cfg, st, S, now, pre=fused.get("up"))

    # ---- 4. downlink drain: strict priority, FIFO within level
    # (backend-dispatched: cfg.backend="pallas" runs the priority_arbiter
    # kernel, bit-identical to the reference math — DESIGN.md §6)
    with scope("downlink_drain"):
        eligible = st["r_valid"] & (st["r_seq"] + cfg.net_delay_slots
                                    <= now)
        if cfg.faults_on and cfg.fabric.faults.tor_fail:
            # hosts behind a failed TOR drain nothing for the window;
            # their buffered chunks survive and resume when it lifts
            eligible = eligible & ~host_down_mask(cfg, now)[:, None]
        q_eligible = eligible                   # backlog incl. stalled rows
        if "down" in fused:
            # winner pre-solved at slot start by the fused kernel (incl.
            # the RX delivery / room gate — _fused_precompute); this
            # slot's insertions carry seq == now and can't be eligible
            # yet, so the hoisted selection is bit-identical (DESIGN.md
            # §11). q_eligible above is provably the kernel's pre-room
            # eligibility input.
            slot_idx, any_elig, pmin = fused["down"]
        else:
            if cfg.host_rx_on:
                # host/NIC RX stage (DESIGN.md §10): finish service on
                # ring entries whose CPU time elapsed (feeds recv ->
                # grants AND completions), then gate the downlink on
                # RX-ring room — a full ring backpressures the network
                # (chunks stay queued, not lost)
                hm = cfg.host_model
                st = hm.rx_deliver(cfg, st, S, now)
                room = hm.rx_room(cfg, st)
                st = {**st, "h_rx_stall": st["h_rx_stall"]
                      + (eligible.any(axis=1) & ~room).astype(I32)}
                eligible = eligible & room[:, None]
            slot_idx, any_elig, pmin = drain_select(
                st["r_prio"], st["r_seq"], eligible, backend=cfg.backend,
                interpret=cfg.pallas_interpret)
        hidx = (jnp.arange(H), slot_idx)
        drained_msg = jnp.where(any_elig, st["r_msg"][hidx], M)
        if cfg.host_rx_on:
            # drained chunks enter the RX ring; recv advances in rx_deliver
            st = cfg.host_model.rx_accept(cfg, st, S, drained_msg,
                                          any_elig, now)
            recv = st["recv"]
        else:
            recv = st["recv"].at[jnp.minimum(drained_msg, M - 1)].add(
                jnp.where(any_elig, 1, 0), mode="drop")
        r_valid = st["r_valid"].at[hidx].set(
            jnp.where(any_elig, False, st["r_valid"][hidx]))
        st = proto.on_drain(cfg, st, S, drained_msg, any_elig, now)

    # ---- 5. stats
    with scope("stats"):
        completion = jnp.where((recv >= S["size"]) & (st["completion"] < 0),
                               now, st["completion"])
        qlen = (q_eligible.sum(axis=1) - any_elig.astype(I32))
        drained_prio = jnp.where(any_elig, jnp.minimum(
            pmin, cfg.n_prios - 1), 0)
        prio_drained = st["prio_drained"].at[drained_prio].add(
            jnp.where(any_elig, 1, 0), mode="drop")
        known_inc = (recv > 0) & (completion < 0)
        has_known = (S["dst_onehot"] & known_inc[None, :]).any(axis=1)
        wasted = st["wasted"] + (~any_elig & withheld
                                 & has_known).astype(I32)

        st = {**st, "recv": recv, "r_valid": r_valid,
              "completion": completion,
              "busy": st["busy"] + any_elig.astype(I32),
              "q_sum": st["q_sum"] + qlen.astype(jnp.float32),
              "q_max": jnp.maximum(st["q_max"], qlen),
              "wasted": wasted, "prio_drained": prio_drained}

    # ---- 5b. loss recovery (fault-enabled fabrics only, DESIGN.md §7):
    # receiver RESENDs + sender fallback timeouts rewind quiet messages'
    # send offsets so fault-dropped chunks get retransmitted
    if cfg.faults_on:
        with scope("recovery"):
            st = apply_recovery(cfg, proto, st, S, now, drained_msg,
                                any_elig)

    # ---- 6. protocol end-of-slot hook (e.g. pHost sender timeouts)
    with scope("post_step"):
        st = proto.post_step(cfg, st, S, now, active, drained_msg, any_elig)

    # ---- 7. telemetry capture (ledger append + strided series rows)
    if cfg.trace_on:
        with scope("telemetry"):
            st = telemetry.capture_slot(cfg, st, S, now, tr_prev, active,
                                        qlen)

    return st, None


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _run(cfg: SimConfig, proto: Protocol, S, st0, n_sched: int):
    body = functools.partial(step_fn, cfg, proto, S, n_sched)
    st, _ = lax.scan(body, st0, jnp.arange(cfg.max_slots, dtype=I32))
    return st


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _run_batch(cfg: SimConfig, proto: Protocol, S_stack, n_sched: int):
    """N independent runs in one trace: vmap over the leading table axis."""
    M = S_stack["size"].shape[1]
    st0 = _init_state(cfg, proto, M)

    def one(S):
        body = functools.partial(step_fn, cfg, proto, S, n_sched)
        st, _ = lax.scan(body, st0, jnp.arange(cfg.max_slots, dtype=I32))
        return st

    return jax.vmap(one)(S_stack)


def _finalize(cfg: SimConfig, table: MessageTable, S, alloc, st,
              return_state: bool, reduce_trace: bool = False,
              timings: dict | None = None) -> SimResult:
    """Numpy post-processing of one run's final scan state.

    ``reduce_trace=True`` (the ``run_sweep`` path) keeps only the
    streaming-stat scalars of a captured trace — vmapped sweeps never
    hold N full ``SimTrace`` histories at once (DESIGN.md §8)."""
    size_slots = np.asarray(S["size"])
    arrival = np.asarray(S["arrival"])
    done = st["completion"] >= 0
    elapsed = np.where(done, st["completion"] - arrival + 1, -1)
    ideal = np.asarray(S["ideal"]).astype(np.int64)   # set by prepare()
    slowdown = np.where(done, elapsed / ideal, np.nan)

    fabric = None
    tor_kw = {}
    if cfg.fabric_on:
        fab = cfg.fabric
        fabric = {"racks": fab.racks,
                  "rack_size": fab.rack_size(cfg.n_hosts),
                  "n_uplinks": fab.n_uplinks(cfg.n_hosts),
                  "oversub": fab.oversub, "seed": fab.seed,
                  "routing": fab.routing}
        tor_kw = dict(
            tor_up_busy_frac=st["u_busy"] / cfg.max_slots,
            tor_up_q_mean_bytes=st["u_q_sum"] / cfg.max_slots
            * cfg.slot_bytes,
            tor_up_q_max_bytes=st["u_q_max"] * cfg.slot_bytes,
            tor_up_lost_chunks=int(st["u_lost"]))
    if cfg.faults_on:
        fl = cfg.fabric.faults
        first_loss = np.asarray(st["first_loss"])
        affected = first_loss < 2 ** 30
        # recovery time: first fault-drop on the message -> completion;
        # -1 for messages never hit (or never finished)
        tor_kw.update(
            faults=dataclasses.asdict(fl),
            retx_chunks=np.asarray(st["retx"]),
            msg_lost_chunks=np.asarray(st["msg_lost"]),
            recovery_slots=np.where(done & affected,
                                    np.asarray(st["completion"])
                                    - first_loss, -1),
            fault_lost_chunks=int(st["f_lost"]))
    if cfg.host_on:
        from repro.core.hostmodel import QSCALE
        tor_kw["host"] = dataclasses.asdict(cfg.host)
        if cfg.host_tx_on:
            tor_kw.update(
                host_tx_busy_frac=st["h_tx_work_q"]
                / (cfg.max_slots * QSCALE),
                host_tx_defer_frac=st["h_tx_defer"] / cfg.max_slots)
        if cfg.host_rx_on:
            tor_kw.update(
                host_rx_stall_frac=st["h_rx_stall"] / cfg.max_slots,
                host_rx_q_mean_chunks=st["h_rx_q_sum"] / cfg.max_slots,
                host_rx_q_max_chunks=np.asarray(st["h_rx_q_max"]))

    trace = trace_summary = None
    if cfg.trace_on:
        tr = telemetry.finalize_trace(cfg, st, timings)
        trace_summary = tr.reduce()
        if not reduce_trace:
            trace = tr
    elif timings is not None:
        # wallclock-only run (capture disabled): keep the stage split
        trace_summary = {"timings": timings}

    return SimResult(
        protocol=cfg.protocol, alloc=alloc,
        completion=st["completion"], elapsed=elapsed, ideal=ideal,
        slowdown=slowdown, done=done,
        size_slots=size_slots, size_bytes=np.asarray(table.size),
        busy_frac=st["busy"] / cfg.max_slots,
        wasted_frac=st["wasted"] / cfg.max_slots,
        uplink_busy_frac=st["uplink_busy"] / cfg.max_slots,
        q_mean_bytes=st["q_sum"] / cfg.max_slots * cfg.slot_bytes,
        q_max_bytes=st["q_max"] * cfg.slot_bytes,
        prio_drained_bytes=st["prio_drained"] * cfg.slot_bytes,
        lost_chunks=int(st["lost"]) + int(st.get("u_lost", 0)),
        n_complete=int(done.sum()), n_messages=len(size_slots),
        fabric=fabric, **tor_kw,
        trace=trace, trace_summary=trace_summary,
        state=st if return_state else None,
        static=jax.tree.map(np.asarray, S) if return_state else None,
    )


def simulate(cfg: SimConfig, table: MessageTable,
             alloc: PriorityAllocation | None = None,
             unsched_limit_bytes=None,
             return_state: bool = False) -> SimResult:
    """Run one simulation; returns a structured :class:`SimResult`.

    The call's host work is recorded as spans (``telemetry.span``): one
    ``sim.simulate`` (counters ``slots`` and ``grant_topk_rounds``, the
    selection rounds of the grant top-K per slot) holding ``sim.prepare``,
    ``sim.init_state``, ``sim.dispatch``, ``sim.scan_wait``,
    ``sim.fetch`` and ``sim.finalize``. With ``cfg.trace =
    TraceConfig(wallclock=True)`` the scan runs through jax's AOT path
    instead (``sim.lower`` / ``sim.compile`` / ``sim.execute``) and the
    split lands in ``result.trace.timings``."""
    proto = get_protocol(cfg.protocol)
    span = telemetry.span
    with span("sim.simulate", slots=cfg.max_slots) as top:
        with span("sim.prepare"):
            S, alloc = prepare(cfg, table, alloc, unsched_limit_bytes)
            n_sched = proto.n_sched(cfg, alloc)
        top["counts"]["grant_topk_rounds"] = proto.receiver.topk_rounds(
            cfg, n_sched, len(table.size))
        with span("sim.init_state"):
            st0 = _init_state(cfg, proto, len(table.size))
        timings = None
        if cfg.trace is not None and cfg.trace.wallclock:
            # wallclock instrumentation works with capture disabled too
            # (TraceConfig(enabled=False, wallclock=True)): the timings
            # of the UNTRACED program, for capture-overhead measurement
            st, timings = telemetry.timed_aot_run(
                _run, (cfg, proto, S, st0, n_sched), (S, st0),
                repeats=cfg.trace.wallclock_repeats)
        else:
            with span("sim.dispatch"):
                st = _run(cfg, proto, S, st0, n_sched)
            with span("sim.scan_wait"):
                jax.block_until_ready(st)
        with span("sim.fetch"):
            st = jax.tree.map(np.asarray, st)
        with span("sim.finalize"):
            return _finalize(cfg, table, S, alloc, st, return_state,
                             timings=timings)


def run_sweep(cfg: SimConfig, spec) -> list:
    """Run N independent simulations batched inside one jit trace per
    static-parameter group, optionally sharded across devices with
    chunked scans and streaming statistics.

    The sweep is described by a single :class:`repro.core.sweep.SweepSpec`
    (DESIGN.md §9)::

        run_sweep(cfg, SweepSpec(seeds=(0, 1, 2, 3), workload="W1",
                                 load=0.8, shared_alloc=True,
                                 shard=True, chunk_slots=512,
                                 streaming=True))

    Returns one result per run, in input order: :class:`SimResult` for
    exact sweeps, :class:`repro.core.sweep.SweepStats` (bounded-memory
    streaming accumulators) when ``spec.streaming`` is set. Runs are
    grouped by ``(table length, scheduled levels)`` — the scan's static
    parameters — and each group compiles once; ``shared_alloc=True``
    derives one priority allocation from the union of all tables' sizes
    (the paper's workload-knowledge model, §4) so a same-length sweep
    compiles exactly once. With chunking/sharding/streaming off, results
    are bit-identical to sequential :func:`simulate` calls.
    """
    from repro.core import sweep as sweep_mod
    if not isinstance(spec, sweep_mod.SweepSpec):
        raise TypeError(
            f"run_sweep(cfg, spec) takes a SweepSpec, got "
            f"{type(spec).__name__}. The legacy kwargs signature (and "
            f"run_sim) were removed after their deprecation release; "
            f"build a SweepSpec: run_sweep(cfg, SweepSpec(seeds=..., "
            f"workload=..., load=...)) — or pass tables=(...).")
    return sweep_mod.run_spec(cfg, spec)


def slowdown_percentiles(stats: dict | SimResult, pct: float = 99.0,
                         n_buckets: int = 10) -> dict:
    """Percentile slowdown bucketed by message size (paper Figs. 8/12).
    Accepts a :class:`SimResult` or the legacy stats dict."""
    if isinstance(stats, SimResult):
        return stats.percentiles_by_size(pct, n_buckets)
    return bucketed_percentiles(stats["size_bytes"], stats["slowdown"],
                                stats["done"], pct, n_buckets)


__all__ = ["SimConfig", "FabricConfig", "TraceConfig", "SimTrace",
           "HostConfig", "simulate", "run_sweep",
           "slowdown_percentiles", "prepare", "step_fn", "SimResult",
           "registered_protocols"]


def __getattr__(name):
    # late-bound so `from repro.core.sim import SweepSpec` works without
    # importing the sweep engine at module load (sweep imports sim)
    if name in ("SweepSpec", "StreamSpec", "SweepStats"):
        from repro.core import sweep as sweep_mod
        return getattr(sweep_mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
