"""Two-tier leaf-spine fabric model (paper §5.2 topology; DESIGN.md §5).

The paper's large-scale results run on a two-level leaf-spine network:
144 hosts in 9 racks, each TOR connected to every spine, with a
configurable oversubscription ratio at the TOR uplinks. This module
models that fabric as an extra, fully vectorized queueing tier inside
the simulator's ``lax.scan``:

  host NIC ──> TOR ──(same rack: leaf switching)──> dst downlink queue
                └──(cross rack: UPLINK PRIORITY QUEUE ──> spine)──┘

- **Uplink queues.** Each TOR has ``n_uplinks = max(1, round(rack_size
  / oversub))`` uplinks, one per spine, each draining one chunk per
  slot with the same strict-priority-then-FIFO arbitration as the
  receiver downlinks. ``oversub`` > 1 therefore means cross-rack
  traffic contends for less aggregate uplink bandwidth than the rack's
  hosts can offer — the congestion point Homa's grant scheduling cannot
  see directly.
- **Spine selection.** A chunk's uplink (= spine) is chosen by a
  seeded, deterministic integer hash of ``(src, dst, msg_id, seed)``
  computed once per message in ``prepare`` — flow-level ECMP at
  per-message granularity. Same table + same seed => bit-identical
  runs; changing ``FabricConfig.seed`` reshuffles spine placement only.
- **Delays.** Intra-rack chunks keep the single-switch latency
  (``cfg.net_delay_slots``). Cross-rack chunks wait ``leaf_delay_slots``
  before uplink service (the service slot is the last wait slot), then
  ``spine_delay_slots`` more before downlink service, so an unloaded
  cross-rack chunk completes ``leaf_delay_slots + spine_delay_slots``
  after transmission. The defaults (6 + 6) equal the default
  ``net_delay_slots = 12``: an unloaded fabric reproduces the
  single-switch timing exactly.
- **Priorities.** Uplink queues honour the same wire priority the
  sender policy stamped on the chunk (``SenderPolicy.chunk_prio``), so
  Homa's unscheduled/scheduled levels shape queueing at *both* tiers.

``FabricConfig(None)`` (or ``SimConfig.fabric=None``, the default)
disables the tier entirely: the scan carries no uplink state and the
program is bit-identical to the single-switch simulator (tested against
a golden snapshot in ``tests/test_fabric.py``).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.protocols import BIG, I32
from repro.core.faults import (FaultConfig, inject_losses, forward_losses,
                               link_down_mask, select_uplink)
from repro.kernels.arbiter import dispatch
from repro.kernels.arbiter.ref import priority_arbiter_ref

ROUTING_POLICIES = ("ecmp", "flowlet", "adaptive")


@dataclasses.dataclass(frozen=True)
class FabricConfig:
    """Leaf-spine topology parameters (hashable: a static jit argument).

    ``FabricConfig(None)`` is the disabled sentinel — single-switch
    behavior, bit-identical to ``SimConfig.fabric=None``.
    """
    racks: int | None = None        # None disables the fabric tier
    oversub: float = 2.0            # rack offered bw : uplink bw ratio
    leaf_delay_slots: int = 6       # host NIC -> TOR uplink service
    spine_delay_slots: int = 6      # uplink service -> dst downlink service
    up_cap: int = 512               # per-uplink buffered chunks
    seed: int = 0                   # spine-hash seed (ECMP placement)
    # spine selection policy (DESIGN.md §7): "ecmp" is the static
    # per-message hash (today's behavior); "flowlet" re-hashes every
    # flowlet_slots; "adaptive" picks the least-loaded live uplink
    routing: str = "ecmp"
    flowlet_slots: int = 64         # flowlet epoch length (~1.7 RTT)
    # fault injection + loss recovery (repro.core.faults); None keeps
    # the scan loss-free and bit-identical to the pre-fault simulator
    faults: FaultConfig | None = None

    def __post_init__(self):
        # JSON round-trip convenience: accept a plain dict for faults
        if isinstance(self.faults, dict):
            object.__setattr__(self, "faults", FaultConfig(**self.faults))

    @property
    def enabled(self) -> bool:
        return self.racks is not None

    def validate(self, n_hosts: int) -> None:
        if not self.enabled:
            return
        if self.racks < 1:
            raise ValueError(f"FabricConfig.racks must be >= 1, got "
                             f"{self.racks}")
        if n_hosts % self.racks:
            raise ValueError(
                f"n_hosts={n_hosts} is not divisible by racks={self.racks}; "
                f"the leaf-spine model needs equal-size racks")
        if self.oversub <= 0:
            raise ValueError(f"FabricConfig.oversub must be > 0, got "
                             f"{self.oversub}")
        if self.leaf_delay_slots < 0:
            raise ValueError("FabricConfig.leaf_delay_slots must be >= 0")
        if self.spine_delay_slots < 1:
            raise ValueError(
                "FabricConfig.spine_delay_slots must be >= 1 (a chunk "
                "cannot traverse uplink and downlink in the same slot)")
        if self.up_cap < 1:
            raise ValueError("FabricConfig.up_cap must be >= 1")
        if self.routing not in ROUTING_POLICIES:
            raise ValueError(
                f"unknown routing policy {self.routing!r}; available: "
                f"{list(ROUTING_POLICIES)}")
        if self.flowlet_slots < 1:
            raise ValueError("FabricConfig.flowlet_slots must be >= 1")
        if self.faults is not None:
            self.faults.validate(self, n_hosts)

    # ---- derived topology (python ints: shape parameters for the scan)

    def rack_size(self, n_hosts: int) -> int:
        return n_hosts // self.racks

    def n_uplinks(self, n_hosts: int) -> int:
        """Uplinks per TOR (= number of spines each TOR reaches). The
        oversubscription ratio is rack_size : n_uplinks."""
        return max(1, int(round(self.rack_size(n_hosts) / self.oversub)))

    def n_uplinks_total(self, n_hosts: int) -> int:
        return self.racks * self.n_uplinks(n_hosts)

    # ---- failure-scenario constructors (DESIGN.md §7). Each returns a
    # new frozen config with the fault layered onto any existing
    # FaultConfig; re-exported as module functions from
    # repro.core.scenarios for compatibility.

    def with_faults(self, **fault_kw) -> "FabricConfig":
        """New config with ``fault_kw`` merged into the fault layer."""
        if not self.enabled:
            raise ValueError("failure scenarios need an enabled fabric "
                             "(FabricConfig with racks set): faults model "
                             "loss on leaf-spine links")
        base = dataclasses.asdict(self.faults) \
            if self.faults is not None else {}
        return dataclasses.replace(
            self, faults=FaultConfig(**{**base, **fault_kw}))

    def with_lossy(self, *, up_loss: float = 0.0, down_loss: float = 0.0,
                   ge_p_gb: float = 0.0, ge_p_bg: float = 0.05,
                   ge_loss: float = 0.5, seed: int = 0) -> "FabricConfig":
        """Steady-state lossy links: Bernoulli uplink/downlink chunk
        loss, optionally with a Gilbert-Elliott burst component."""
        return self.with_faults(up_loss=up_loss, down_loss=down_loss,
                                ge_p_gb=ge_p_gb, ge_p_bg=ge_p_bg,
                                ge_loss=ge_loss, seed=seed)

    def with_uplink_failure(self, *, uplink: int, start: int,
                            end: int) -> "FabricConfig":
        """One TOR uplink black-holes all traffic for ``[start, end)``
        slots — the scenario where routing policy dominates: static ECMP
        keeps hashing flows into the dead spine until the window lifts."""
        prior = self.faults.link_fail if self.faults is not None else ()
        return self.with_faults(link_fail=prior + ((uplink, start, end),))

    def with_tor_failure(self, *, rack: int, start: int,
                         end: int) -> "FabricConfig":
        """A whole TOR fails for ``[start, end)`` slots: the rack's
        uplinks and host downlinks all go dark; recovery timeouts must
        carry every in-flight message across the window."""
        prior = self.faults.tor_fail if self.faults is not None else ()
        return self.with_faults(tor_fail=prior + ((rack, start, end),))


def spine_hash(src: np.ndarray, dst: np.ndarray, msg_id: np.ndarray,
               seed: int, n_uplinks: int) -> np.ndarray:
    """Deterministic per-message spine choice in ``[0, n_uplinks)``.

    An xorshift-multiply mix of (src, dst, msg_id, seed) — the model's
    stand-in for ECMP 5-tuple hashing, at per-message granularity so
    repeated src->dst pairs spread across spines like distinct RPCs do.
    """
    # seed term mixed in python ints then masked: a numpy scalar-scalar
    # uint32 product warns on the (intended) wraparound
    seed_mix = np.uint32((seed * 0x27D4EB2F) & 0xFFFFFFFF)
    h = (np.asarray(src, np.uint32) * np.uint32(0x9E3779B1)
         ^ np.asarray(dst, np.uint32) * np.uint32(0x85EBCA77)
         ^ np.asarray(msg_id, np.uint32) * np.uint32(0xC2B2AE3D)
         ^ seed_mix)
    h ^= h >> np.uint32(15)
    h *= np.uint32(0x2C1B3C6D)
    h ^= h >> np.uint32(12)
    return (h % np.uint32(n_uplinks)).astype(np.int32)


# ------------------------------------------------------- ring primitives ---
# Shared by downlink and uplink tiers: a (R, cap) pool of ring buffers
# with occupancy-based insertion and strict-priority / FIFO drain.

# Block width of ring_insert's two-level search: one TPU lane row. JAX
# lowers cumsum as a reduce_window as wide as its axis (O(width^2) adds
# a row), so both running sums stay short: at cap 4096, 32 blocks of 128.
RING_BLOCK = 128


def ring_insert(msg_a, prio_a, seq_a, valid_a, row, ok, msg, prio, seq):
    """Insert up to ``len(row)`` chunks into per-row rings.

    Item i goes into ring ``row[i]`` iff ``ok[i]``; multiple items may
    target the same row in one slot (they take consecutive free slots in
    input order). A chunk is dropped only when its ring is actually
    full. Returns the four updated ring arrays plus the dropped count.

    The item of rank r among its row's items takes the row's (r+1)-th
    free buffer, the lowest-numbered one first. It is found in two
    levels: the free counts of the row's ``RING_BLOCK``-wide blocks and
    their running sum name the block that holds it, and a running sum
    inside that one block names the buffer. A ring whose ``cap`` is not
    a multiple of the block is padded with occupied buffers, which are
    never taken.
    """
    R, cap = valid_a.shape
    n = row.shape[0]
    rows = jnp.where(ok, row, R)                              # sentinel R
    same = (rows[:, None] == rows[None, :]) & ok[None, :] & ok[:, None]
    ar = jnp.arange(n)
    rank = jnp.sum(same & (ar[None, :] < ar[:, None]), axis=1)
    nb = -(-cap // RING_BLOCK)
    free = jnp.pad(~valid_a, ((0, 0), (0, nb * RING_BLOCK - cap)))
    free = free.reshape(R, nb, RING_BLOCK)
    r = jnp.minimum(rows, R - 1)
    cb_row = jnp.cumsum(jnp.sum(free, axis=2, dtype=I32), axis=1)[r]
    room = cb_row[:, -1] > rank                               # (n,)
    okw = ok & room
    # block of the (rank+1)-th free buffer, and the free buffers before it
    below = cb_row <= rank[:, None]                           # (n, nb)
    blk = jnp.minimum(jnp.sum(below, axis=1), nb - 1)
    want = rank + 1 - jnp.max(jnp.where(below, cb_row, 0), axis=1)
    ci = jnp.cumsum(free[r, blk].astype(I32), axis=1)         # (n, B)
    pos = blk * RING_BLOCK + jnp.sum(ci < want[:, None], axis=1)
    # suppressed writes go out of bounds (mode="drop"): an in-bounds
    # no-op write could race a genuine insertion at the same location
    idx = (jnp.where(okw, rows, R), jnp.where(okw, pos, 0))
    return (msg_a.at[idx].set(msg, mode="drop"),
            prio_a.at[idx].set(prio, mode="drop"),
            seq_a.at[idx].set(seq, mode="drop"),
            valid_a.at[idx].set(jnp.ones_like(okw), mode="drop"),
            jnp.sum(ok & ~room))


def ring_drain_select(prio_a, seq_a, eligible):
    """Pick one chunk per row: strict priority, FIFO (seq) within level.
    Returns ``(slot_idx, any_elig, pmin)`` — the winning slot per row,
    whether the row drained anything, and the winning priority. The
    math lives in ``kernels.arbiter.ref.priority_arbiter_ref`` — ONE
    reference oracle, shared with the backend dispatcher, so the sim
    and the standalone kernel tests cannot drift apart."""
    pmin, slot_idx = priority_arbiter_ref(prio_a, seq_a, eligible)
    return slot_idx, pmin < BIG, pmin


def drain_select(prio_a, seq_a, eligible, *, backend: str = "reference",
                 interpret: bool | None = None):
    """Backend-dispatched :func:`ring_drain_select` (DESIGN.md §6): the
    simulator's per-slot arbitration hot spot, routable to the Pallas
    ``priority_arbiter`` kernel via ``SimConfig.backend``. Both paths
    are bit-identical — winner slot, eligibility, and priority — for
    ragged shapes and all-ineligible rows (property-tested in
    ``tests/test_kernels.py``)."""
    bp, bi = dispatch.arbitrate(prio_a, seq_a, eligible, backend=backend,
                                interpret=interpret)
    return bi, bp < BIG, bp


# ------------------------------------------------------- fabric stages -----

def init_fabric_state(cfg) -> dict:
    """Uplink-tier scan state; only fabric-enabled configs carry it."""
    fab = cfg.fabric
    U, ucap = fab.n_uplinks_total(cfg.n_hosts), fab.up_cap
    return {
        "u_msg": jnp.full((U, ucap), -1, I32),
        "u_prio": jnp.full((U, ucap), BIG, I32),
        "u_seq": jnp.full((U, ucap), BIG, I32),
        "u_valid": jnp.zeros((U, ucap), bool),
        "u_busy": jnp.zeros((U,), I32),
        "u_q_sum": jnp.zeros((U,), jnp.float32),
        "u_q_max": jnp.zeros((U,), I32),
        "u_lost": jnp.zeros((), I32),
    }


def route_chunks(cfg, st, S, cm, has, dsts, prio_chunk, now):
    """Route this slot's transmitted chunks into the first queueing tier:
    same-rack chunks switch at the leaf straight into the destination
    downlink ring; cross-rack chunks enter their TOR's hashed uplink
    queue. Returns updated state."""
    fab = cfg.fabric
    H = cfg.n_hosts
    rs = fab.rack_size(H)
    n_up = fab.n_uplinks(H)
    src_rack = jnp.arange(H, dtype=I32) // rs
    dst_rack = jnp.minimum(dsts, H - 1) // rs
    local = has & (src_rack == dst_rack)
    remote = has & (src_rack != dst_rack)

    if fab.routing == "ecmp":
        urow = src_rack * n_up + S["spine"][cm]
    else:
        urow = select_uplink(cfg, st, S, cm, src_rack, now)
    if fab.faults is not None:
        local, remote, st = inject_losses(cfg, st, cm, local, remote,
                                          dsts, urow, now)

    r_msg, r_prio, r_seq, r_valid, d_drop = ring_insert(
        st["r_msg"], st["r_prio"], st["r_seq"], st["r_valid"],
        dsts, local, cm, prio_chunk, jnp.full_like(dsts, now))

    u_msg, u_prio, u_seq, u_valid, u_drop = ring_insert(
        st["u_msg"], st["u_prio"], st["u_seq"], st["u_valid"],
        urow, remote, cm, prio_chunk, jnp.full_like(urow, now))

    return {**st,
            "r_msg": r_msg, "r_prio": r_prio, "r_seq": r_seq,
            "r_valid": r_valid,
            "u_msg": u_msg, "u_prio": u_prio, "u_seq": u_seq,
            "u_valid": u_valid,
            "lost": st["lost"] + d_drop,
            "u_lost": st["u_lost"] + u_drop}


def uplink_drain(cfg, st, S, now, pre=None):
    """Drain at most one chunk per TOR uplink (strict priority, FIFO
    within level) and forward it across its spine into the destination
    downlink ring, where it becomes eligible after ``spine_delay_slots``.
    Returns updated state.

    ``pre`` is an optional pre-solved ``(slot_idx, any_e, prio)`` winner
    triple from the ``pallas_fused`` backend, which arbitrates all of a
    slot's stages in one kernel at slot start (DESIGN.md §11). The hoist
    is bit-identical because this slot's ``route_chunks`` insertions
    carry ``u_seq == now`` and ``leaf_delay_slots >= 1`` (enforced by
    ``sim._fused_precompute``) keeps them ineligible until the next
    slot — and ``ring_insert`` never overwrites a valid (winning) slot."""
    fab = cfg.fabric
    H = cfg.n_hosts
    M = S["size"].shape[0]
    U = st["u_valid"].shape[0]

    eligible = st["u_valid"] & (st["u_seq"] + fab.leaf_delay_slots <= now)
    fl = fab.faults
    if fl is not None and (fl.link_fail or fl.tor_fail):
        # a failed uplink black-holes its queue for the window: chunks
        # already buffered there neither drain nor get re-routed
        eligible = eligible & ~link_down_mask(cfg, now)[:, None]
    if pre is not None:
        slot_idx, any_e, _ = pre
    else:
        slot_idx, any_e, _ = drain_select(st["u_prio"], st["u_seq"],
                                          eligible, backend=cfg.backend,
                                          interpret=cfg.pallas_interpret)
    uidx = (jnp.arange(U), slot_idx)
    msg = jnp.where(any_e, st["u_msg"][uidx], M)
    prio = st["u_prio"][uidx]
    u_valid = st["u_valid"].at[uidx].set(
        jnp.where(any_e, False, st["u_valid"][uidx]))

    # forward into the downlink ring with a *virtual* enqueue time such
    # that (seq + net_delay_slots <= t) fires at t = now + spine_delay:
    # the downlink's single eligibility rule then covers both tiers, and
    # FIFO order within a priority level remains arrival-time order at
    # the destination TOR.
    dst = jnp.where(any_e, S["dst"][jnp.minimum(msg, M - 1)], H)
    vseq = jnp.full((U,), now + fab.spine_delay_slots - cfg.net_delay_slots,
                    I32)
    ins_ok = any_e
    if fl is not None and (fl.down_loss > 0 or fl.tor_fail):
        # last-hop loss point: the chunk left the uplink (it still counts
        # toward u_busy) but dies on the spine->TOR->host leg
        ins_ok, st = forward_losses(cfg, st, msg, dst, any_e, now)
    r_msg, r_prio, r_seq, r_valid, d_drop = ring_insert(
        st["r_msg"], st["r_prio"], st["r_seq"], st["r_valid"],
        dst, ins_ok, msg, prio, vseq)

    qlen = eligible.sum(axis=1) - any_e.astype(I32)
    out = {**st,
           "r_msg": r_msg, "r_prio": r_prio, "r_seq": r_seq,
           "r_valid": r_valid, "u_valid": u_valid,
           "lost": st["lost"] + d_drop,
           "u_busy": st["u_busy"] + any_e.astype(I32),
           "u_q_sum": st["u_q_sum"] + qlen.astype(jnp.float32),
           "u_q_max": jnp.maximum(st["u_q_max"], qlen)}
    if getattr(cfg, "trace_on", False):
        # telemetry tap (DESIGN.md §8): running uplink-tier per-priority
        # drain counter, sampled into the strided series by capture_slot
        dp = jnp.where(any_e, jnp.minimum(prio, cfg.n_prios - 1), 0)
        out["tr_uprio_c"] = st["tr_uprio_c"].at[dp].add(
            jnp.where(any_e, 1, 0), mode="drop")
    return out


__all__ = ["FabricConfig", "FaultConfig", "ROUTING_POLICIES", "spine_hash",
           "ring_insert", "ring_drain_select", "drain_select",
           "init_fabric_state", "route_chunks", "uplink_drain"]
