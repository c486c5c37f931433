"""Plain reference simulator: the semantics the benchmark holds the program to.

A straightforward re-implementation, in ``jax.numpy`` and int32, of one
slotted run of the deployments in ``bench/configs``, written from the
model's rules and not from the program's code (it imports nothing of
the program). Each slot, in this order:

1. Receivers decide grants on the slot-start state. Homa: each receiver
   grants its ``K`` incoming messages with the fewest remaining slots
   (ties to the lower message id) that it has heard from and that are
   incomplete, one RTT beyond what it received, and gives the ``A``
   granted messages scheduled levels ``A-1-rank``, clipped to the
   scheduled band. pFabric: every arrived, incomplete message is
   granted one RTT beyond what was received, no scheduled levels.
   A grant becomes visible to the sender ``grant_delay_slots - 1``
   slots later; the scheduled level likewise.
2. Each sender transmits one chunk of its sendable message with the
   fewest remaining slots (ties to the lower id). The chunk carries its
   wire priority (smaller is served first): Homa's unscheduled level from
   the priority allocation inside the blind window, its scheduled level
   below those after it; pFabric's remaining slots.
3. Same-rack chunks enter the destination downlink; cross-rack chunks
   enter their TOR uplink, picked per message by the ECMP hash.
4. Each uplink serves one eligible chunk and forwards it to the
   destination downlink, where it is eligible ``spine_delay_slots``
   later.
5. Each downlink serves one eligible chunk to its host; a message
   completes in the slot its last chunk is served.

Every queue is a pool of ``cap`` buffers: a chunk takes the
lowest-numbered free buffer, chunks that arrive at one queue in one slot
are placed in order of their sending host (or uplink), and a chunk that
finds no free buffer is lost. A queue serves strict priority, then the
earliest enqueue slot, then the lowest buffer number. Setting
``strict_priority=False`` drops the first of those rules: the control
that has to fail the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

I32 = jnp.int32
INF = np.int32(np.iinfo(np.int32).max)
SIM_KEYS = {"protocol", "n_hosts", "slot_bytes", "n_prios", "rtt_slots",
            "net_delay_slots", "grant_delay_slots", "ring_cap"}
FABRIC_KEYS = {"racks", "oversub", "up_cap", "leaf_delay_slots",
               "spine_delay_slots", "seed", "routing"}


def to_slots(nbytes, slot_bytes: int) -> np.ndarray:
    """Link slots a byte count occupies (at least one)."""
    nbytes = np.asarray(nbytes, np.int64)
    return np.maximum((nbytes + slot_bytes - 1) // slot_bytes,
                      1).astype(np.int32)


def priority_allocation(sizes, unsched_bytes: int, n_prios: int) -> dict:
    """Homa's receiver-side allocation (paper §3.4): the share of bytes
    sent unscheduled sets how many of the levels are unscheduled, and
    size cut-offs split the unscheduled bytes into equal shares per
    level, the shortest messages on the highest level."""
    sizes = np.asarray(sizes, np.int64)
    blind = np.minimum(sizes, unsched_bytes).astype(np.float64)
    frac = float(blind.sum() / max(sizes.sum(), 1))
    n_unsched = min(max(int(round(frac * n_prios)), 1), n_prios - 1)
    cutoffs = []
    if n_unsched > 1:
        order = np.argsort(sizes, kind="stable")
        s_sorted, w_cum = sizes[order], np.cumsum(blind[order])
        for i in range(1, n_unsched):
            j = min(int(np.searchsorted(w_cum, w_cum[-1] * i / n_unsched)),
                    len(s_sorted) - 1)
            cutoffs.append(max(int(s_sorted[j]), cutoffs[-1] if cutoffs
                               else 0))
    return {"n_unsched": n_unsched, "n_sched": n_prios - n_unsched,
            "cutoffs": cutoffs}


def unsched_level(sizes, alloc: dict, n_prios: int) -> np.ndarray:
    """Unscheduled priority level of each message (n_prios-1 highest)."""
    lvl = np.searchsorted(np.asarray(alloc["cutoffs"], np.int64),
                          np.asarray(sizes, np.int64), side="left")
    return (n_prios - 1 - lvl).astype(np.int32)


def ecmp_spine(src, dst, msg, seed: int, n_uplinks: int) -> np.ndarray:
    """Per-message spine choice: an xorshift-multiply hash of
    ``(src, dst, message id, seed)`` modulo the TOR's uplinks."""
    with np.errstate(over="ignore"):
        u = np.uint32
        h = (np.asarray(src, u) * u(0x9E3779B1)
             ^ np.asarray(dst, u) * u(0x85EBCA77)
             ^ np.asarray(msg, u) * u(0xC2B2AE3D)
             ^ u((seed * 0x27D4EB2F) & 0xFFFFFFFF))
        h ^= h >> u(15)
        h = h * u(0x2C1B3C6D)
        h ^= h >> u(12)
    return (h % u(n_uplinks)).astype(np.int32)


def static_inputs(config: dict, table: dict, alloc: dict) -> dict:
    """Per-message arrays of one run, computed on the host."""
    sim, fab = config["sim"], config.get("fabric")
    H, sb = sim["n_hosts"], sim["slot_bytes"]
    size = to_slots(table["size"], sb)
    M = len(size)
    rtt_bytes = sim["rtt_slots"] * sb
    out = {"src": np.asarray(table["src"], np.int32),
           "dst": np.asarray(table["dst"], np.int32),
           "arrival": np.asarray(table["arrival"], np.int32),
           "size": size,
           "unsched": np.minimum(to_slots(rtt_bytes, sb), size),
           "uprio": unsched_level(table["size"], alloc, sim["n_prios"]),
           "spine": np.zeros(M, np.int32)}
    if fab is not None:
        rs = H // fab["racks"]
        out["spine"] = ecmp_spine(out["src"], out["dst"], np.arange(M),
                                  fab["seed"], n_uplinks(config))
        cross = out["src"] // rs != out["dst"] // rs
        out["delay"] = np.where(cross, fab["leaf_delay_slots"]
                                + fab["spine_delay_slots"],
                                sim["net_delay_slots"]).astype(np.int32)
    else:
        out["delay"] = np.full(M, sim["net_delay_slots"], np.int32)
    return out


def n_uplinks(config: dict) -> int:
    """Uplinks per TOR: rack size over the oversubscription ratio."""
    fab = config["fabric"]
    rs = config["sim"]["n_hosts"] // fab["racks"]
    return max(1, int(round(rs / fab["oversub"])))


def check_bounds(config: dict, S: dict) -> None:
    """The int32 SRPT keys below hold every value the run can reach."""
    M = len(S["size"])
    if int(S["size"].max()) * M + M >= INF:
        raise ValueError("message sizes too large for the int32 SRPT key")
    fab = config.get("fabric")
    if fab is not None and (fab["leaf_delay_slots"]
                            + fab["spine_delay_slots"]
                            < config["sim"]["net_delay_slots"]):
        raise ValueError("spine forwarding would enqueue in the past")


def _queue(R: int, cap: int) -> dict:
    return {"msg": jnp.zeros((R, cap), I32), "prio": jnp.zeros((R, cap), I32),
            "seq": jnp.zeros((R, cap), I32),
            "full": jnp.zeros((R, cap), bool)}


def _enqueue(q: dict, rows, ok, msg, prio, seq):
    """Put chunk ``i`` (if ``ok[i]``) into queue ``rows[i]``: chunks for
    one queue take its free buffers lowest first, in order of ``i``; a
    chunk whose turn comes when its queue is full is lost."""
    n = rows.shape[0]
    i = jnp.arange(n)
    same = (rows[:, None] == rows[None, :]) & ok[:, None] & ok[None, :]
    order = jnp.sum(same & (i[None, :] < i[:, None]), axis=1)
    order = jnp.where(ok, order, n)

    def place(state):
        q, k = state
        first_free = jnp.argmin(q["full"], axis=1)          # (R,)
        room = ~jnp.all(q["full"], axis=1)
        go = (order == k) & room[rows]
        r = jnp.where(go, rows, q["full"].shape[0])         # off the end
        c = first_free[jnp.minimum(rows, q["full"].shape[0] - 1)]
        q = {"msg": q["msg"].at[r, c].set(msg, mode="drop"),
             "prio": q["prio"].at[r, c].set(prio, mode="drop"),
             "seq": q["seq"].at[r, c].set(seq, mode="drop"),
             "full": q["full"].at[r, c].set(True, mode="drop")}
        return q, k + 1

    last = jnp.max(jnp.where(ok, order, -1))
    q, _ = lax.while_loop(lambda s: s[1] <= last, place, (q, jnp.int32(0)))
    return q


def _serve(q: dict, eligible, strict: bool):
    """One chunk per queue: strict priority, then enqueue slot, then
    buffer number. Returns ``(queue, served, msg, prio)``."""
    cand = eligible
    keys = ("prio", "seq") if strict else ("seq",)
    for k in keys:
        best = jnp.min(jnp.where(cand, q[k], INF), axis=1, keepdims=True)
        cand = cand & (q[k] == best)
    col = jnp.argmax(cand, axis=1)
    rows = jnp.arange(cand.shape[0])
    served = cand[rows, col]
    msg = q["msg"][rows, col]
    prio = q["prio"][rows, col]
    full = q["full"].at[rows, col].set(q["full"][rows, col] & ~served)
    return {**q, "full": full}, served, msg, prio


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _simulate(protocol: str, dims: tuple, params: tuple, strict: bool, S):
    (H, M, cap, ucap, racks, n_up, max_slots) = dims
    (n_prios, n_sched, rtt, net_delay, grant_delay, leaf_delay,
     spine_delay) = params
    fabric = racks > 0
    ids = jnp.arange(M, dtype=I32)
    K = min(n_sched, M)
    rs = H // racks if fabric else H

    st0 = {"sent": jnp.zeros(M, I32), "granted": jnp.zeros(M, I32),
           "grant_r": jnp.zeros(M, I32), "recv": jnp.zeros(M, I32),
           "level": jnp.zeros(M, I32),
           "completion": jnp.full(M, -1, I32),
           "hist_grant": jnp.zeros((grant_delay, M), I32),
           "hist_level": jnp.zeros((grant_delay, M), I32),
           "down": _queue(H, cap),
           "up": _queue(racks * n_up if fabric else 1, ucap)}

    def slot(st, now):
        arrived = S["arrival"] <= now
        incomplete = st["completion"] < 0
        # 1. receivers
        if protocol == "homa":
            cand = (st["recv"] > 0) & incomplete
            key = (S["size"] - st["recv"]) * M + ids
            rank = jnp.full(M, -1, I32)
            for r in range(K):
                best = jax.ops.segment_min(jnp.where(cand, key, INF),
                                           S["dst"], num_segments=H)
                win = cand & (key == best[S["dst"]])
                rank = jnp.where(win, r, rank)
                cand = cand & ~win
            chosen = rank >= 0
            n_active = jax.ops.segment_sum(chosen.astype(I32), S["dst"],
                                           num_segments=H)
            lvl = jnp.clip(n_active[S["dst"]] - 1 - rank, 0, n_sched - 1)
            grant_r = jnp.where(chosen, jnp.maximum(
                st["grant_r"], jnp.minimum(S["size"], st["recv"] + rtt)),
                st["grant_r"])
            level = jnp.where(chosen, lvl, st["level"])
        else:                                   # pfabric
            gate = arrived & incomplete
            grant_r = jnp.where(gate, jnp.maximum(
                st["grant_r"], jnp.minimum(S["size"], st["recv"] + rtt)),
                st["grant_r"])
            level = jnp.zeros(M, I32)
        hist_grant = st["hist_grant"].at[now % grant_delay].set(grant_r)
        hist_level = st["hist_level"].at[now % grant_delay].set(level)
        seen = (now + 1) % grant_delay
        granted = jnp.maximum(jnp.maximum(
            st["granted"], jnp.where(arrived, S["unsched"], 0)),
            hist_grant[seen])
        level = jnp.where(arrived, hist_level[seen], level)

        # 2. senders
        sendable = arrived & (st["sent"] < granted) \
            & (st["sent"] < S["size"])
        skey = jnp.where(sendable, (S["size"] - st["sent"]) * M + ids, INF)
        best = jax.ops.segment_min(skey, S["src"], num_segments=H)
        has = best < INF
        cm = jnp.where(has, best % M, 0)
        sent_cm = st["sent"][cm]
        if protocol == "homa":
            blind = sent_cm < S["unsched"][cm]
            wire = jnp.where(blind, n_prios - 1 - S["uprio"][cm],
                             n_prios - 1 - level[cm])
        else:
            wire = S["size"][cm] - sent_cm
        sent = st["sent"].at[cm].add(has.astype(I32))
        dst = S["dst"][cm]
        host = jnp.arange(H, dtype=I32)
        down, up = st["down"], st["up"]

        # 3. leaf switching / uplink queues
        if fabric:
            local = has & (host // rs == dst // rs)
            remote = has & ~local
            urow = (host // rs) * n_up + S["spine"][cm]
            down = _enqueue(down, dst, local, cm, wire,
                            jnp.full(H, now, I32))
            up = _enqueue(up, urow, remote, cm, wire, jnp.full(H, now, I32))
            # 4. uplinks serve and forward across the spine
            up, fwd, fmsg, fprio = _serve(
                up, up["full"] & (up["seq"] + leaf_delay <= now), strict)
            fdst = S["dst"][fmsg]
            down = _enqueue(down, fdst, fwd, fmsg, fprio,
                            jnp.full(fwd.shape, now + spine_delay
                                     - net_delay, I32))
        else:
            down = _enqueue(down, dst, has, cm, wire, jnp.full(H, now, I32))

        # 5. downlinks serve their hosts
        down, got, dmsg, _ = _serve(
            down, down["full"] & (down["seq"] + net_delay <= now), strict)
        recv = st["recv"].at[dmsg].add(got.astype(I32))
        completion = jnp.where((recv >= S["size"]) & incomplete, now,
                               st["completion"])
        return {"sent": sent, "granted": granted, "grant_r": grant_r,
                "recv": recv, "level": level, "completion": completion,
                "hist_grant": hist_grant, "hist_level": hist_level,
                "down": down, "up": up}, None

    st, _ = lax.scan(slot, st0, jnp.arange(max_slots, dtype=I32))
    return st["completion"]


def simulate(config: dict, table: dict, alloc: dict, max_slots: int, *,
             strict_priority: bool = True, device=None):
    """Completion slot of every message of one run (-1: incomplete), as
    a device array (not yet waited for)."""
    sim, fab = config["sim"], config.get("fabric")
    protocol = sim["protocol"]
    if protocol not in ("homa", "pfabric"):
        raise ValueError(f"the reference models homa and pfabric, not "
                         f"{protocol!r}")
    extra = set(sim) - SIM_KEYS | set(fab or {}) - FABRIC_KEYS
    if extra or (fab and fab["routing"] != "ecmp"):
        raise ValueError(f"the reference does not model {sorted(extra)} "
                         f"or routing other than ecmp")
    S = static_inputs(config, table, alloc)
    check_bounds(config, S)
    H, M = sim["n_hosts"], len(S["size"])
    dims = (H, M, sim["ring_cap"], fab["up_cap"] if fab else 1,
            fab["racks"] if fab else 0, n_uplinks(config) if fab else 1,
            int(max_slots))
    params = (sim["n_prios"], alloc["n_sched"], sim["rtt_slots"],
              sim["net_delay_slots"], sim["grant_delay_slots"],
              fab["leaf_delay_slots"] if fab else 0,
              fab["spine_delay_slots"] if fab else 0)
    S = {k: jax.device_put(v, device) for k, v in S.items()
         if k != "delay"}
    return _simulate(protocol, dims, params, strict_priority, S)


def slowdown_hist(config: dict, table: dict, completion, max_slots: int,
                  stream: dict):
    """Completions binned by (message size, slowdown) as a
    ``(len(size_edges) + 1, n_buckets)`` count table: slowdown is the
    completion time over the unloaded time (size plus path delay), in
    float32, bucketed on log-spaced edges ``r**1 .. r**(B-1)`` with
    ``r = max_slowdown ** (1 / (B - 1))``."""
    S = static_inputs(config, table, {"cutoffs": [], "n_sched": 1})
    B = int(stream["n_buckets"])
    r = float(stream["max_slowdown"]) ** (1.0 / (B - 1))
    edges = jnp.asarray((r ** np.arange(1, B, dtype=np.float64))
                        .astype(np.float32))
    size_edges = np.asarray(stream["size_edges"], np.int64)
    szb = np.sum(np.asarray(table["size"], np.int64)[:, None]
                 >= size_edges[None, :], axis=1)
    comp = jnp.asarray(completion)
    done = (comp >= 0) & (comp < max_slots)
    ideal = jnp.asarray(S["size"] + S["delay"])
    sd = (comp - jnp.asarray(S["arrival"]) + 1).astype(jnp.float32) \
        / ideal.astype(jnp.float32)
    b = jnp.sum(sd[:, None] >= edges[None, :], axis=1)
    flat = jnp.asarray(szb) * B + b
    hist = jax.ops.segment_sum(done.astype(I32), flat,
                               num_segments=(len(size_edges) + 1) * B)
    return np.asarray(hist).reshape(len(size_edges) + 1, B)
