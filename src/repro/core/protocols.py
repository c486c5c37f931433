"""Transport protocols as composable policies (DESIGN.md §1).

The paper decomposes receiver-driven transport into independent policies:
grant scheduling (§3.3), priority allocation (§3.4), and controlled
overcommitment (§3.5). This module mirrors that decomposition so
``sim.step_fn`` stays policy-agnostic orchestration of uplinks, network
delay, and downlink priority queues:

  ``SenderPolicy``    which message each host transmits next (chunk
                      selection order) and the priority stamped on the
                      outgoing chunk — honoured by every queueing tier
                      the chunk crosses (TOR uplinks under a leaf-spine
                      ``FabricConfig``, and the receiver downlink).
  ``ReceiverPolicy``  which messages are granted this slot, the scheduled
                      priority assigned to each, and the overcommitment
                      degree (how many senders are granted concurrently).
  ``Protocol``        one named sender+receiver pair plus per-message
                      static preparation (unscheduled window + priority)
                      and optional per-slot hooks (drain bookkeeping,
                      timeout handling).

All policy objects are frozen dataclasses: hashable, comparable, and
therefore usable as static arguments to ``jax.jit`` — a protocol choice is
compile-time structure, not runtime data.

The six paper protocols (homa, basic, phost, pias, pfabric, ndp) are
registered here; their approximations are documented in DESIGN.md §3.
Register new variants with :func:`register`; :func:`get_protocol` raises
``ValueError`` naming the registry on an unknown name.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.kernels.arbiter.dispatch import (topk as backend_topk,
                                            topk_rounds as backend_topk_rounds)

I32 = jnp.int32
BIG = jnp.int32(2 ** 30)
MSG_BITS = 13
MSG_MOD = 1 << MSG_BITS          # max messages per sim
ORDER_CAP = (1 << 17) - 1        # sender-order keys clamp here


# --------------------------------------------------------------- senders ---

@dataclasses.dataclass(frozen=True)
class SenderPolicy:
    """Chunk selection order + priority stamping at the sending host."""

    def order(self, cfg, st, S, now, remaining):
        """(M,) int32 key; per host, the sendable message with the smallest
        key transmits this slot (ties break toward the smallest msg id)."""
        raise NotImplementedError

    def chunk_prio(self, cfg, st, S, cm, unsched, n_sched):
        """(H,) int32 wire priority for each host's chosen chunk
        (smaller = served first). This is the priority stamped in the
        packet header, so EVERY queueing tier honours it: the receiver
        downlink always, and — when ``cfg.fabric`` models a leaf-spine
        network — the TOR uplink queues too (DESIGN.md §5). ``cm`` is
        the chosen message per host (clamped), ``unsched`` marks chunks
        inside the blind window."""
        raise NotImplementedError

    def on_send(self, cfg, st, S, cm, has, now):
        """Post-transmit bookkeeping hook (default: none) — policies that
        need per-send state (e.g. fair-share ordering) update it here, so
        other protocols don't pay the scatter."""
        return st


@dataclasses.dataclass(frozen=True)
class SrptSender(SenderPolicy):
    """Shortest-remaining-processing-time chunk order (paper §3.2)."""

    def order(self, cfg, st, S, now, remaining):
        return jnp.minimum(remaining, ORDER_CAP)


@dataclasses.dataclass(frozen=True)
class FifoSender(SenderPolicy):
    """Arrival-order senders (NDP's per-message FIFO pull queues)."""

    def order(self, cfg, st, S, now, remaining):
        return jnp.minimum(S["arrival"], ORDER_CAP)


@dataclasses.dataclass(frozen=True)
class FairShareSender(SenderPolicy):
    """Least-recently-served round robin (DCTCP-style fair sharing)."""

    def order(self, cfg, st, S, now, remaining):
        return jnp.minimum(st["last_sent"], ORDER_CAP)

    def on_send(self, cfg, st, S, cm, has, now):
        last_sent = st["last_sent"].at[cm].set(
            jnp.where(has, now, st["last_sent"][cm]), mode="drop")
        return {**st, "last_sent": last_sent}


# ------------------------------------------------------------- receivers ---

@dataclasses.dataclass(frozen=True)
class ReceiverPolicy:
    """Grant issue + scheduled-priority assignment + overcommit degree."""

    def grants(self, cfg, st, S, now, n_sched, topk=None):
        """Returns ``(grant_r, sched_prio, active, withheld)``:
        (M,) granted slots, (M,) scheduled priority, (M,) bool mask of
        messages the receivers actively schedule, and (H,) bool — hosts
        with known-but-ungranted traffic (wasted-bandwidth accounting).

        ``topk`` is the precomputed ``(vals, idx)`` answer to this
        policy's :meth:`grant_problem` — supplied by the ``pallas_fused``
        backend, which solves it inside the fused per-slot kernel
        (DESIGN.md §11). Policies without a grant problem ignore it."""
        raise NotImplementedError

    def grant_problem(self, cfg, st, S, now, n_sched):
        """The top-K selection this policy would issue this slot, as
        ``(keys (H, M), K)`` for the fused kernel — or ``None`` if the
        policy selects no grant set (window receivers). Must read exactly
        the state :meth:`grants` reads, so solving it at slot start is
        bit-identical to solving it inside :meth:`grants`."""
        return None

    def topk_rounds(self, cfg, n_sched, M):
        """Selection rounds a slot's grant top-K over M messages runs
        (``dispatch.topk_rounds``): 0 for a policy that selects no grant
        set, or where the reference top-K sorts."""
        return 0

    def resend(self, cfg, st, S, now, known, quiet):
        """Receiver-side loss detection (paper §3.7): (M,) bool mask of
        messages whose sender should rewind to the receiver's high-water
        mark this slot. ``known`` marks messages the receiver has heard
        from (recv > 0); ``quiet`` is slots since the last chunk arrival
        (or rewind). Only called on fault-enabled fabrics; the default
        leaves recovery entirely to the sender fallback timeout — the
        honest model for window baselines with no receiver scheduler."""
        return jnp.zeros_like(known)


def window_grants(cfg, st, S, gate):
    """Shared helper: keep ``gate``-ed messages granted one RTT of data
    beyond what was received (classic receive-window clocking)."""
    grant_r = jnp.where(gate,
                        jnp.minimum(S["size"], st["recv"] + cfg.rtt_slots),
                        st["grant_r"])
    grant_r = jnp.maximum(grant_r, st["grant_r"])
    no_withheld = jnp.zeros((cfg.n_hosts,), bool)
    return grant_r, jnp.zeros_like(st["sched_prio"]), gate, no_withheld


def srpt_grant_matrix(cfg, st, S, eligible, K):
    """The receiver-side SRPT selection problem as a dense key matrix:
    ``(keys (H, M), K)`` where row h holds the grant key of every message
    destined to host h (0 = ineligible) and K is clamped to M. This is
    the ``(mat, K)`` that :func:`topk_srpt_grants` selects over — split
    out so the ``pallas_fused`` backend can pose the identical problem
    to the fused kernel at slot start (``ReceiverPolicy.grant_problem``).

    The key orders by (remaining, msg): smaller remaining wins, ties
    break toward the SMALLEST msg id. A stable active set is what gives
    SRPT its run-to-completion behaviour — an unstable tie-break churns
    the active message and leaks grants to every tied message
    (catastrophic under incast, where all messages are the same size)."""
    size, dst_oh = S["size"], S["dst_onehot"]
    remaining = jnp.maximum(size - st["recv"], 0)
    K = min(K, size.shape[0])        # can't select more than M messages
    keyval = ((jnp.int32(1 << 17) - jnp.minimum(remaining, (1 << 17) - 1))
              << MSG_BITS) | (MSG_MOD - 1 - S["msg_ids"])
    mat = jnp.where(dst_oh & eligible[None, :], keyval[None, :], 0)  # (H, M)
    return mat, K


def topk_srpt_grants(cfg, st, S, eligible, K, n_sched, topk=None):
    """Shared helper: each receiver grants its top-K SRPT messages one RTT
    ahead and assigns scheduled priorities lowest-levels-first (paper
    §3.4/Fig. 5), shortest message on the highest scheduled level. The
    top-K selection is backend-dispatched (``SimConfig.backend``,
    DESIGN.md §6): the pallas path runs the ``srpt_topk`` kernel, whose
    index output IS the winning message id (columns of ``mat``), so no
    key-decoding or re-matching scan is needed on either backend. The
    ``pallas_fused`` backend passes the selection in pre-solved
    (``topk=(vals, idx)``, from the fused slot kernel — DESIGN.md §11)."""
    size, dst_oh = S["size"], S["dst_onehot"]
    if topk is None:
        mat, K = srpt_grant_matrix(cfg, st, S, eligible, K)
        vals, idx = backend_topk(mat, K, backend=cfg.backend,
                                 interpret=cfg.pallas_interpret)     # (H, K)
    else:
        vals, idx = topk
        K = vals.shape[1]
    valid = vals > 0
    msgs = jnp.where(valid, idx, MSG_MOD)                            # sentinel
    n_active = valid.sum(axis=1)                                     # (H,)
    # scheduled priority: rank r (0 = fewest remaining) among A active gets
    # level (A-1-r): lowest levels used first, shortest on top (paper §3.4)
    ranks = jnp.arange(K)[None, :]
    prio = jnp.clip(n_active[:, None] - 1 - ranks, 0, max(n_sched - 1, 0))

    flat_msgs = msgs.reshape(-1)
    new_grant = jnp.minimum(size, st["recv"] + cfg.rtt_slots)
    grant_r = st["grant_r"]
    grant_r = grant_r.at[flat_msgs].max(
        jnp.where(valid.reshape(-1), new_grant[
            jnp.minimum(flat_msgs, len(size) - 1)], 0), mode="drop")
    sched_prio = st["sched_prio"].at[flat_msgs].set(
        prio.reshape(-1), mode="drop")

    active = jnp.zeros_like(eligible).at[flat_msgs].set(
        valid.reshape(-1), mode="drop")
    withheld = (dst_oh & eligible[None, :] & ~active[None, :]).any(axis=1)
    return grant_r, sched_prio, active, withheld


def grant_preempted(prev_active, active, completion):
    """(M,) bool: messages evicted from the receiver's active grant set
    this slot while still incomplete — i.e. preempted for better (shorter)
    messages under SRPT overcommitment (paper §3.5), not retired by
    completion. Used by the telemetry event ledger."""
    return prev_active & ~active & (completion < 0)


@dataclasses.dataclass(frozen=True)
class WindowReceiver(ReceiverPolicy):
    """RTT-window grants to every known (``blind=False``) or merely arrived
    (``blind=True``) incomplete message; no receiver-side scheduling."""
    blind: bool = False

    def grants(self, cfg, st, S, now, n_sched, topk=None):
        if self.blind:
            gate = (S["arrival"] <= now) & (st["completion"] < 0)
        else:
            gate = (st["recv"] > 0) & (st["completion"] < 0)
        return window_grants(cfg, st, S, gate)


@dataclasses.dataclass(frozen=True)
class OvercommitSrptReceiver(ReceiverPolicy):
    """Homa's receiver: top-K SRPT with controlled overcommitment
    (paper §3.5). K defaults to the number of scheduled priority levels;
    ``cfg.overcommit`` overrides it. ``max_k=1`` models single-grant
    receivers (pHost); ``stall_aware`` honours the sender-timeout
    blacklist maintained by :class:`Phost.post_step`."""
    max_k: int | None = None
    stall_aware: bool = False

    def _k(self, cfg, n_sched):
        if self.max_k is not None:
            return self.max_k
        return cfg.overcommit or max(n_sched, 1)

    def _eligible(self, cfg, st, now):
        eligible = (st["recv"] > 0) & (st["completion"] < 0)
        if self.stall_aware:
            eligible = eligible & (st["stall_until"] <= now)
        return eligible

    def grants(self, cfg, st, S, now, n_sched, topk=None):
        eligible = self._eligible(cfg, st, now)
        return topk_srpt_grants(cfg, st, S, eligible,
                                self._k(cfg, n_sched), n_sched, topk=topk)

    def grant_problem(self, cfg, st, S, now, n_sched):
        return srpt_grant_matrix(cfg, st, S, self._eligible(cfg, st, now),
                                 self._k(cfg, n_sched))

    def topk_rounds(self, cfg, n_sched, M):
        # K clamped to M as srpt_grant_matrix clamps it
        return backend_topk_rounds(min(self._k(cfg, n_sched), M), M,
                                   cfg.backend)

    def resend(self, cfg, st, S, now, known, quiet):
        # Homa's receiver timeout (paper §3.7): a receiver that actively
        # schedules its inbound messages RESENDs any known message that
        # has gone quiet for ~2 RTT — much faster than the sender
        # fallback, which is the point of receiver-driven recovery.
        return known & (quiet >= cfg.fabric.faults.resend_slots)


# ------------------------------------------------------------- protocols ---

@dataclasses.dataclass(frozen=True)
class Protocol:
    """One named transport protocol = sender policy + receiver policy +
    static per-message preparation + optional per-slot hooks."""
    name: str = ""
    sender: SenderPolicy = dataclasses.field(default_factory=SrptSender)
    receiver: ReceiverPolicy = dataclasses.field(
        default_factory=WindowReceiver)

    # ---- static preparation (numpy, once per table) ----

    def unsched_limit(self, cfg, M, unsched_limit_bytes):
        """Per-message unscheduled (blind) byte budget."""
        if unsched_limit_bytes is None:
            unsched_limit_bytes = cfg.rtt_bytes
        return np.broadcast_to(np.asarray(unsched_limit_bytes), (M,))

    def unsched_prio(self, cfg, sizes, alloc):
        """Per-message priority level for unscheduled chunks."""
        return np.zeros((len(sizes),))

    def n_sched(self, cfg, alloc):
        """Number of scheduled priority levels (static scan parameter)."""
        return max(cfg.overcommit or alloc.n_sched, 1)

    def extra_state(self, cfg, M):
        """Protocol-private scan state, merged into the carry — only the
        protocols that need an array pay for hauling it."""
        return {}

    # ---- per-slot hooks (traced) ----

    def on_drain(self, cfg, st, S, drained_msg, any_elig, now):
        """Called after the downlink drains a chunk; returns updated state."""
        return st

    def post_step(self, cfg, st, S, now, active, drained_msg, any_elig):
        """End-of-slot hook (e.g. timeout bookkeeping); returns state."""
        return st


@dataclasses.dataclass(frozen=True)
class ConstPrioSender(SrptSender):
    """SRPT order, all chunks on one fixed priority level."""
    level: int = 0

    def chunk_prio(self, cfg, st, S, cm, unsched, n_sched):
        return jnp.full_like(cm, self.level)


@dataclasses.dataclass(frozen=True)
class NdpSender(FifoSender):
    """FIFO order; unscheduled chunks above scheduled, two static levels."""

    def chunk_prio(self, cfg, st, S, cm, unsched, n_sched):
        return jnp.where(unsched, 0, 1).astype(I32)


@dataclasses.dataclass(frozen=True)
class HomaSender(SrptSender):
    """Receiver-allocated priorities (paper §3.4): unscheduled levels from
    the workload CDF, scheduled levels from the grant's priority field."""

    def chunk_prio(self, cfg, st, S, cm, unsched, n_sched):
        up = (cfg.n_prios - 1 - S["uprio"][cm])      # inverted: smaller=better
        sp = (n_sched - 1 - st["sched_prio"][cm])    # within scheduled band
        sched_inv = (cfg.n_prios - n_sched) + sp     # scheduled below unsched
        # unscheduled levels sit above (smaller inv value) all scheduled
        return jnp.where(unsched, up, sched_inv).astype(I32)


@dataclasses.dataclass(frozen=True)
class Homa(Protocol):
    name: str = "homa"
    sender: SenderPolicy = dataclasses.field(default_factory=HomaSender)
    receiver: ReceiverPolicy = dataclasses.field(
        default_factory=OvercommitSrptReceiver)

    def unsched_prio(self, cfg, sizes, alloc):
        return alloc.unsched_prio(sizes)

    def n_sched(self, cfg, alloc):
        return max(alloc.n_sched, 1)


@dataclasses.dataclass(frozen=True)
class Basic(Protocol):
    """Receiver-window transport with no priorities (the paper's 'basic'
    receiver-driven baseline)."""
    name: str = "basic"
    sender: SenderPolicy = dataclasses.field(default_factory=ConstPrioSender)
    receiver: ReceiverPolicy = dataclasses.field(
        default_factory=WindowReceiver)


@dataclasses.dataclass(frozen=True)
class PhostTwoLevelSender(SrptSender):
    """SRPT order; RTS/unscheduled packets above scheduled data."""

    def chunk_prio(self, cfg, st, S, cm, unsched, n_sched):
        return jnp.where(unsched, 0, 1).astype(I32)


@dataclasses.dataclass(frozen=True)
class Phost(Protocol):
    """pHost: single-message grants (token per RTT, K=1) with a sender
    timeout that blacklists unresponsive messages (DESIGN.md §3)."""
    name: str = "phost"
    sender: SenderPolicy = dataclasses.field(
        default_factory=PhostTwoLevelSender)
    receiver: ReceiverPolicy = dataclasses.field(
        default_factory=lambda: OvercommitSrptReceiver(max_k=1,
                                                       stall_aware=True))

    def unsched_prio(self, cfg, sizes, alloc):
        return np.full((len(sizes),), cfg.n_prios - 1)

    def extra_state(self, cfg, M):
        return {"stall_until": jnp.zeros((M,), I32),   # timeout blacklist
                "last_progress": jnp.zeros((M,), I32)}

    def post_step(self, cfg, st, S, now, active, drained_msg, any_elig):
        # if the single granted message makes no progress for `timeout`
        # slots, blacklist it briefly so the receiver switches to another
        # message (approximates pHost's sender-timeout mechanism).
        M = S["size"].shape[0]
        lp = st["last_progress"]
        lp = jnp.maximum(lp, S["arrival"])            # clock starts at arrival
        lp = lp.at[jnp.minimum(drained_msg, M - 1)].max(
            jnp.where(any_elig, now, 0), mode="drop")
        timed_out = active & (st["grant_r"] > st["recv"]) & \
            (now - lp > cfg.phost_timeout_slots)
        new_stall = jnp.where(timed_out, now + cfg.phost_timeout_slots,
                              st["stall_until"])
        return {**st, "stall_until": new_stall, "last_progress": lp}


@dataclasses.dataclass(frozen=True)
class PiasSender(FairShareSender):
    """MLFQ: chunks demote to lower levels as the flow's sent bytes cross
    the precomputed thresholds (level 0 first, demoted upward)."""

    def chunk_prio(self, cfg, st, S, cm, unsched, n_sched):
        sent = st["sent"][cm]
        lvl = jnp.searchsorted(S["pias_cuts"], sent, side="right")
        return lvl.astype(I32)


@dataclasses.dataclass(frozen=True)
class Pias(Protocol):
    name: str = "pias"
    sender: SenderPolicy = dataclasses.field(default_factory=PiasSender)

    def extra_state(self, cfg, M):
        return {"last_sent": jnp.zeros((M,), I32)}     # round-robin clock

    receiver: ReceiverPolicy = dataclasses.field(
        default_factory=lambda: WindowReceiver(blind=True))

    def unsched_limit(self, cfg, M, unsched_limit_bytes):
        return np.full((M,), cfg.rtt_bytes)          # blind first window


@dataclasses.dataclass(frozen=True)
class PfabricSender(SrptSender):
    """Continuous priority = remaining slots (pFabric's ideal SRPT wire)."""

    def chunk_prio(self, cfg, st, S, cm, unsched, n_sched):
        return jnp.maximum(S["size"][cm] - st["sent"][cm], 0)


@dataclasses.dataclass(frozen=True)
class Pfabric(Protocol):
    name: str = "pfabric"
    sender: SenderPolicy = dataclasses.field(default_factory=PfabricSender)
    receiver: ReceiverPolicy = dataclasses.field(
        default_factory=lambda: WindowReceiver(blind=True))

    def unsched_limit(self, cfg, M, unsched_limit_bytes):
        return np.full((M,), cfg.rtt_bytes)          # blind first window


@dataclasses.dataclass(frozen=True)
class Ndp(Protocol):
    """NDP: FIFO pull queues per receiver, two static priority levels
    (header/retransmit above bulk), per-message round-robin service."""
    name: str = "ndp"
    sender: SenderPolicy = dataclasses.field(default_factory=NdpSender)
    receiver: ReceiverPolicy = dataclasses.field(
        default_factory=WindowReceiver)

    def unsched_prio(self, cfg, sizes, alloc):
        return np.full((len(sizes),), cfg.n_prios - 1)

    def extra_state(self, cfg, M):
        return {"last_served": jnp.zeros((M,), I32)}   # fair-share clock

    def on_drain(self, cfg, st, S, drained_msg, any_elig, now):
        # fair-share bookkeeping: round-robin via last-served ordering
        M = S["size"].shape[0]
        ls = st["last_served"].at[jnp.minimum(drained_msg, M - 1)].set(
            now, mode="drop")
        return {**st, "last_served": ls}


# --------------------------------------------------------------- registry ---

_REGISTRY: dict[str, Protocol] = {}


def register(proto: Protocol) -> Protocol:
    """Register a protocol under ``proto.name`` (overwrites silently so a
    variant can shadow a builtin during experiments)."""
    if not proto.name:
        raise ValueError("protocol needs a non-empty name")
    _REGISTRY[proto.name] = proto
    return proto


def registered_protocols() -> list[str]:
    return sorted(_REGISTRY)


def get_protocol(name: str) -> Protocol:
    """Look up a registered protocol; unknown names raise ``ValueError``
    listing what is available (satellite: no silent fall-through)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown protocol {name!r}; registered protocols: "
            f"{registered_protocols()}") from None


for _p in (Homa(), Basic(), Phost(), Pias(), Pfabric(), Ndp()):
    register(_p)
