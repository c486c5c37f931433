"""Device-sharded mega-sweeps with streaming statistics (DESIGN.md §9).

``run_sweep`` historically vmapped every run onto one device and pulled
each run's full per-message arrays back to the host before computing
percentiles — a paper-scale grid (6 protocols x loads x oversubs x
seeds, Figs. 10/11/14) neither fits in memory nor uses more than one
accelerator. This module is the scale layer behind ``run_sweep(cfg,
spec)``:

**SweepSpec** — one frozen description of the whole sweep (tables or
``seeds`` + ``workload`` + ``load``, per-table alloc/unsched ablation
lists, ``shared_alloc``) plus the scale knobs: ``shard`` (device count
for a ``shard_map`` layer over the run axis), ``chunk_slots`` (the time
scan runs chunk-by-chunk so streaming accumulators fold at bounded
intervals), and ``streaming`` (a :class:`StreamSpec`).

**Sharding.** Runs are grouped by the scan's static parameters
``(table length, scheduled levels)`` — :func:`group_runs`, the single
grouping implementation shared with ``benchmarks/common.sim_sweep`` —
stacked, padded to a device multiple (replicating the last run; padding
rows are dropped after the gather), and executed as
``shard_map(vmap(one_run))`` over a 1-D ``runs`` mesh. Every run is
independent, so sharded results are bit-identical to the single-device
vmap, which is itself bit-identical to sequential ``simulate`` calls.
Validated on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

**Chunked scan.** ``chunk_slots=c`` nests the slot scan: an outer
``lax.scan`` over chunks, an inner scan over the ``c`` slots of each
chunk, with the global slot index reconstructed as ``chunk * c + i`` so
every mechanism (grant history rings, telemetry strides, fault windows)
sees exactly the slot numbers the flat scan would — the chunked program
is the same step sequence and therefore bit-identical. Streaming
accumulators ride the outer carry and fold once per chunk.

**Fused backend under vmap.** With ``backend="pallas_fused"``
(DESIGN.md §11) the vmap over the run axis does NOT un-fuse the
per-slot mega-kernel into per-lane calls: the fused entry point
carries a ``jax.custom_batching.custom_vmap`` rule that rewrites the
batched call into a single ``grid=(B,)`` kernel — one launch per slot
for the whole run batch, on both the fast path and this chunked path.
Nothing in this module special-cases it; the rule lives in
``kernels.arbiter.fused``.

**Streaming stats.** With ``streaming`` on, a run's slowdowns are binned
into a fixed log-spaced histogram *inside* the compiled program (size
bucket x slowdown bucket), and only O(buckets) scalars per run are
gathered to the host — never the (N, M) per-message arrays. Percentile
estimates from the histogram carry a documented relative error bound of
half a bucket in log space (:meth:`StreamSpec.rel_err_bound`, ~0.9% at
the defaults), regression-gated in tests/test_sweep.py. Queue/busy/
priority stats reduce exactly (they are already running counters in the
scan state), and captured traces reduce device-side via
``telemetry.reduce_state``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from repro.core.priorities import allocate_priorities
from repro.core.protocols import get_protocol, I32
from repro.core.workloads import MessageTable, WorkloadSpec, make_messages
from repro.core import telemetry

# message-size bucket upper bounds (bytes) for streaming per-size
# percentiles; 1000 B is the "small message" boundary every summary uses
DEFAULT_SIZE_EDGES = (256, 1_000, 4_096, 16_384, 65_536, 262_144,
                      1_048_576)


# ================================================================ specs ==

@dataclasses.dataclass(frozen=True)
class StreamSpec:
    """Streaming-accumulator parameters (hashable: rides the jit cache
    key). Slowdowns are binned into ``n_buckets`` log-spaced buckets
    spanning ``[1, max_slowdown)`` (the last bucket absorbs anything
    larger); sizes into ``len(size_edges) + 1`` buckets."""
    n_buckets: int = 512
    max_slowdown: float = 1e4
    size_edges: tuple = DEFAULT_SIZE_EDGES
    small_bytes: int = 1_000            # must be one of size_edges
    warmup_frac: float = 0.0            # drop first fraction of arrivals

    def __post_init__(self):
        if self.n_buckets < 2:
            raise ValueError(f"StreamSpec.n_buckets must be >= 2, got "
                             f"{self.n_buckets}")
        if self.max_slowdown <= 1.0:
            raise ValueError(f"StreamSpec.max_slowdown must be > 1, got "
                             f"{self.max_slowdown}")
        edges = tuple(int(e) for e in self.size_edges)
        if list(edges) != sorted(set(edges)):
            raise ValueError(f"StreamSpec.size_edges must be strictly "
                             f"increasing, got {self.size_edges}")
        object.__setattr__(self, "size_edges", edges)
        if self.small_bytes not in edges:
            raise ValueError(
                f"StreamSpec.small_bytes={self.small_bytes} must be one "
                f"of size_edges {edges} so the small-message percentile "
                f"is a bucket boundary, not an approximation")
        if not 0.0 <= self.warmup_frac < 1.0:
            raise ValueError(f"StreamSpec.warmup_frac must be in [0, 1), "
                             f"got {self.warmup_frac}")

    @property
    def n_size_buckets(self) -> int:
        return len(self.size_edges) + 1

    @property
    def bucket_ratio(self) -> float:
        """Multiplicative width of one slowdown bucket."""
        return self.max_slowdown ** (1.0 / (self.n_buckets - 1))

    @property
    def rel_err_bound(self) -> float:
        """Documented relative error of a percentile estimate vs any
        sample in its bucket: half a bucket in log space."""
        return math.sqrt(self.bucket_ratio) - 1.0


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """One frozen description of a whole sweep — the single argument of
    ``run_sweep(cfg, spec)`` (DESIGN.md §9).

    Exactly one run source: ``tables`` (MessageTables, lengths may
    differ — runs group by static parameters), or ``seeds`` +
    ``workload`` + ``load`` (one synthesized table per seed).
    ``workload`` also accepts a full
    :class:`~repro.core.workloads.WorkloadSpec` (any kind — scenarios
    included); it carries its own load/shape parameters, so ``load``
    must then stay ``None`` and each seed re-seeds the spec.
    ``alloc`` / ``unsched_limit_bytes`` accept a single value or one
    entry per table (priority-ablation sweeps, Figs. 17/18/20).

    Scale knobs: ``shard`` = False (one device) | True (all available
    devices) | int (first n devices); ``chunk_slots`` nests the time
    scan (bit-identical; required for streaming folds at bounded
    intervals); ``streaming`` = False | True (default StreamSpec) | a
    StreamSpec — results become :class:`SweepStats` instead of
    ``SimResult`` and only O(buckets) per run ever reaches the host.
    """
    tables: tuple[MessageTable, ...] | None = None
    seeds: tuple[int, ...] | None = None
    workload: str | WorkloadSpec | None = None
    load: float | None = None
    n_messages: int = 2000
    alloc: Any = None
    unsched_limit_bytes: Any = None
    shared_alloc: bool = False
    shard: bool | int = False
    chunk_slots: int | None = None
    streaming: bool | StreamSpec = False
    return_state: bool = False

    def __post_init__(self):
        if self.tables is not None:
            object.__setattr__(self, "tables", tuple(self.tables))
        elif self.seeds is None or self.workload is None \
                or (self.load is None
                    and not isinstance(self.workload, WorkloadSpec)):
            raise ValueError("SweepSpec needs `tables` or "
                             "(`seeds`, `workload`, `load`) — "
                             "`workload` may be a WorkloadSpec carrying "
                             "its own load/shape parameters")
        if isinstance(self.workload, WorkloadSpec) \
                and self.load is not None:
            raise ValueError("load is part of the WorkloadSpec; don't "
                             "pass SweepSpec.load alongside one")
        if self.seeds is not None:
            object.__setattr__(self, "seeds",
                               tuple(int(s) for s in self.seeds))
        if self.chunk_slots is not None and self.chunk_slots < 1:
            raise ValueError(f"SweepSpec.chunk_slots must be >= 1, got "
                             f"{self.chunk_slots}")
        if self.streaming is True:
            object.__setattr__(self, "streaming", StreamSpec())
        if self.stream is not None and self.return_state:
            raise ValueError("streaming sweeps never materialize scan "
                             "state; return_state=True needs an exact "
                             "(non-streaming) sweep")

    @property
    def stream(self) -> StreamSpec | None:
        return self.streaming if isinstance(self.streaming, StreamSpec) \
            else None

    def resolve_tables(self, cfg) -> list[MessageTable]:
        if self.tables is not None:
            return list(self.tables)
        if isinstance(self.workload, WorkloadSpec):
            return [self.workload.with_seed(s).build(
                n_hosts=cfg.n_hosts, slot_bytes=cfg.slot_bytes)
                for s in self.seeds]
        return [make_messages(self.workload, n_hosts=cfg.n_hosts,
                              load=self.load, n_messages=self.n_messages,
                              slot_bytes=cfg.slot_bytes, seed=s)
                for s in self.seeds]


def resolve_devices(shard: bool | int) -> int:
    """``shard`` knob -> concrete device count (validated)."""
    if shard is False or shard is None:
        return 1
    avail = len(jax.devices())
    n = avail if shard is True else int(shard)
    if n < 1 or n > avail:
        raise ValueError(f"SweepSpec.shard={shard!r} asks for {n} "
                         f"devices but {avail} are available "
                         f"(XLA_FLAGS=--xla_force_host_platform_"
                         f"device_count=N forces N virtual CPU devices)")
    return n


def group_runs(keys: list[tuple]) -> dict[tuple, list[int]]:
    """Group run indices by their static scan parameters — THE grouping
    implementation, shared by ``run_sweep`` and
    ``benchmarks/common.sim_sweep`` (each distinct key costs one jit
    compilation; input order is preserved within groups)."""
    groups: dict[tuple, list[int]] = {}
    for i, k in enumerate(keys):
        groups.setdefault(k, []).append(i)
    return groups


# ================================================= streaming primitives ==

def sd_bucket_edges(stream: StreamSpec) -> np.ndarray:
    """Interior bucket edges (n_buckets - 1,): bucket b spans
    ``[r^b, r^(b+1))`` with r = :meth:`StreamSpec.bucket_ratio` (bucket 0
    starts at slowdown 1.0; the last bucket is open-ended)."""
    B = stream.n_buckets
    return (stream.bucket_ratio
            ** np.arange(1, B, dtype=np.float64)).astype(np.float32)


def bucket_mid(stream: StreamSpec, b) -> np.ndarray:
    """Geometric midpoint of slowdown bucket ``b`` (the estimator's
    representative value; error vs any member <= rel_err_bound)."""
    return stream.bucket_ratio ** (np.asarray(b, np.float64) + 0.5)


def streaming_hist(slowdowns, stream: StreamSpec) -> np.ndarray:
    """Host-side mirror of the device binning (float32 + searchsorted,
    exactly as the scan computes it) — the reference for the property
    tests pinning estimator error."""
    sd = np.asarray(slowdowns, np.float32)
    b = np.searchsorted(sd_bucket_edges(stream), sd, side="right")
    b = np.clip(b, 0, stream.n_buckets - 1)
    return np.bincount(b, minlength=stream.n_buckets).astype(np.int64)


def percentile_from_hist(hist, stream: StreamSpec, q: float
                         ) -> float | None:
    """Percentile estimate from a slowdown histogram: the geometric
    midpoint of the bucket holding rank ``q/100 * (n-1)`` (numpy's
    linear-interpolation position). Relative error vs the exact
    percentile is bounded by :meth:`StreamSpec.rel_err_bound` plus
    interpolation discreteness at small counts."""
    h = np.asarray(hist)
    n = int(h.sum())
    if n == 0:
        return None
    rank = q / 100.0 * (n - 1)
    b = int(np.searchsorted(np.cumsum(h), rank, side="right"))
    return float(bucket_mid(stream, min(b, len(h) - 1)))


def streaming_percentile(slowdowns, q: float, stream: StreamSpec
                         ) -> float | None:
    """End-to-end host mirror: bin then estimate (test surface)."""
    return percentile_from_hist(streaming_hist(slowdowns, stream),
                                stream, q)


def _pack_aux(stream: StreamSpec | None, table: MessageTable) -> dict:
    """Per-run static arrays the streaming fold needs beside S: the
    size-bucket index of every message and the warmup-window mask."""
    if stream is None:
        return {}
    M = len(table.size)
    szb = np.searchsorted(np.asarray(stream.size_edges, np.int64),
                          table.size, side="right").astype(np.int32)
    counted = np.arange(M) >= int(M * stream.warmup_frac)
    return {"szb": jnp.asarray(szb), "counted": jnp.asarray(counted)}


def _fold_hist(stream: StreamSpec, acc, st, S, aux, lo, hi):
    """Fold messages that completed in slot window ``[lo, hi)`` into the
    flat (size-bucket x slowdown-bucket) count histogram. Completion
    slots are immutable once set, so across chunk folds every message is
    counted exactly once."""
    B, K = stream.n_buckets, stream.n_size_buckets
    with jax.named_scope("stream_fold"):
        comp = st["completion"]
        m = (comp >= lo) & (comp < hi) & aux["counted"]
        sd = (comp - S["arrival"] + 1).astype(jnp.float32) \
            / S["ideal"].astype(jnp.float32)
        b = jnp.searchsorted(jnp.asarray(sd_bucket_edges(stream)), sd,
                             side="right")
        flat = aux["szb"] * B + jnp.clip(b, 0, B - 1)
        return acc + jax.ops.segment_sum(m.astype(I32), flat,
                                         num_segments=K * B)


def _device_summary(cfg, st, acc) -> dict:
    """Reduce one run's final scan state to the streaming gather set —
    O(buckets) scalars; the (M,) / (H, cap) state never leaves the
    device. Counter reductions are exact (ints); only the histogram is
    an approximation."""
    out = {
        "hist": acc,
        "n_complete": (st["completion"] >= 0).sum().astype(I32),
        "busy": st["busy"].sum(), "wasted": st["wasted"].sum(),
        "uplink_busy": st["uplink_busy"].sum(),
        "q_sum": st["q_sum"].sum(), "q_max": st["q_max"].max(),
        "prio_drained": st["prio_drained"],
        "lost": st["lost"] + (st["u_lost"] if cfg.fabric_on else 0),
    }
    if cfg.fabric_on:
        out["u_busy"] = st["u_busy"].sum()
    if cfg.faults_on:
        out["f_lost"] = st["f_lost"]
        out["retx"] = st["retx"].sum()
    if cfg.host_tx_on:
        # float32: summed micro-slot work across hosts can pass 2**31
        out["h_tx_work"] = st["h_tx_work_q"].sum(dtype=jnp.float32)
        out["h_tx_defer"] = st["h_tx_defer"].sum()
    if cfg.host_rx_on:
        out["h_rx_stall"] = st["h_rx_stall"].sum()
        out["h_rx_q_max"] = st["h_rx_q_max"].max()
    if cfg.trace_on:
        out.update(telemetry.reduce_state(cfg, st))
    return out


# ======================================================= chunked runner ==

def _scan_chunks(cfg, proto, S, aux, n_sched, st0, chunk, stream):
    """The chunked time scan: same step sequence as the flat scan (the
    global slot index is reconstructed, so bit-identity holds), with the
    streaming histogram folding once per chunk on the outer carry —
    per-fold work is O(M), carry stays O(buckets)."""
    from repro.core import sim as sim_mod
    body = functools.partial(sim_mod.step_fn, cfg, proto, S, n_sched)

    def seg(st, start, length):
        st, _ = lax.scan(lambda s, i: body(s, start + i), st,
                         jnp.arange(length, dtype=I32))
        return st

    acc0 = jnp.zeros(stream.n_size_buckets * stream.n_buckets, I32) \
        if stream is not None else ()
    if not chunk or chunk >= cfg.max_slots:
        st = seg(st0, jnp.int32(0), cfg.max_slots)
        if stream is not None:
            acc0 = _fold_hist(stream, acc0, st, S, aux, 0, cfg.max_slots)
        return st, acc0

    n_full, rem = divmod(cfg.max_slots, chunk)

    def chunk_body(carry, c):
        st, acc = carry
        start = c * chunk
        st = seg(st, start, chunk)
        if stream is not None:
            acc = _fold_hist(stream, acc, st, S, aux, start,
                             start + chunk)
        return (st, acc), None

    (st, acc), _ = lax.scan(chunk_body, (st0, acc0),
                            jnp.arange(n_full, dtype=I32))
    if rem:
        st = seg(st, jnp.int32(n_full * chunk), rem)
        if stream is not None:
            acc = _fold_hist(stream, acc, st, S, aux, n_full * chunk,
                             cfg.max_slots)
    return st, acc


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5, 6, 7))
def _sweep_batch(cfg, proto, S_stack, aux_stack, n_sched: int,
                 chunk: int | None, stream: StreamSpec | None,
                 n_dev: int):
    """One group's runs: vmap over the run axis, shard_map over the
    first ``n_dev`` devices (leading axis pre-padded to a multiple).
    Streaming runs return the reduced gather set; exact runs the full
    final states."""
    from repro.core import sim as sim_mod
    M = S_stack["size"].shape[1]
    st0 = sim_mod._init_state(cfg, proto, M)

    def one(S, aux):
        st, acc = _scan_chunks(cfg, proto, S, aux, n_sched, st0, chunk,
                               stream)
        return _device_summary(cfg, st, acc) if stream is not None else st

    def local(Ss, auxs):
        return jax.vmap(one)(Ss, auxs)

    if n_dev <= 1:
        return local(S_stack, aux_stack)
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("runs",))
    P = PartitionSpec("runs")
    # check_vma=False: pallas_call has no varying-manual-axes rule, and
    # every array here is fully partitioned along "runs" anyway.
    return jax.shard_map(local, mesh=mesh, in_specs=(P, P),
                         out_specs=P, check_vma=False)(S_stack, aux_stack)


# ============================================================== results ==

@dataclasses.dataclass
class SweepStats:
    """One streaming run's bounded-size statistics (the SweepSpec
    ``streaming`` result type). ``hist`` is the (size buckets, slowdown
    buckets) completion-count table; everything else reduced exactly
    from the scan's running counters."""
    protocol: str
    stream: StreamSpec
    alloc: Any
    n_messages: int
    n_complete: int
    hist: np.ndarray                 # (K, B) int counts
    busy_frac: float
    wasted_frac: float
    uplink_busy_frac: float
    q_mean_bytes: float
    q_max_bytes: float
    prio_drained_bytes: np.ndarray   # (n_prios,)
    lost_chunks: int
    tor_up_busy_frac: float | None = None
    fault_lost_chunks: int | None = None
    retx_chunks: int | None = None
    host_tx_busy_frac: float | None = None
    host_tx_defer_frac: float | None = None
    host_rx_stall_frac: float | None = None
    host_rx_q_max_chunks: int | None = None
    trace_summary: dict | None = None

    @property
    def completion_rate(self) -> float:
        return self.n_complete / self.n_messages if self.n_messages \
            else 0.0

    @property
    def n_counted(self) -> int:
        """Completions inside the warmup-trimmed window (hist mass)."""
        return int(self.hist.sum())

    def percentile(self, q: float) -> float | None:
        """Streaming slowdown percentile over all counted messages
        (error <= ``stream.rel_err_bound`` in the relative sense)."""
        return percentile_from_hist(self.hist.sum(axis=0), self.stream,
                                    q)

    def percentile_small(self, q: float) -> float | None:
        """Percentile over messages smaller than ``stream.small_bytes``
        (exact split: small_bytes is a size-bucket edge)."""
        ks = int(np.searchsorted(np.asarray(self.stream.size_edges),
                                 self.stream.small_bytes, "left")) + 1
        return percentile_from_hist(self.hist[:ks].sum(axis=0),
                                    self.stream, q)

    def percentiles_by_size(self, pct: float = 99.0) -> dict:
        """Per-size-bucket percentile curve (the streaming stand-in for
        ``SimResult.percentiles_by_size``; buckets are the static
        ``size_edges``, not per-run equal-count deciles)."""
        edges = (1,) + self.stream.size_edges + (None,)
        out = {"sizes": [], "p": [], "median": [], "count": []}
        for k in range(self.stream.n_size_buckets):
            h = self.hist[k]
            cnt = int(h.sum())
            if cnt == 0:
                continue
            lo = edges[k]
            hi = edges[k + 1] or lo * 4
            out["sizes"].append(float(math.sqrt(lo * hi)))
            out["p"].append(percentile_from_hist(h, self.stream, pct))
            out["median"].append(percentile_from_hist(h, self.stream,
                                                      50.0))
            out["count"].append(cnt)
        return out

    def summary(self, *, pct: float = 99.0) -> dict:
        """JSON-safe aggregate summary (the benchmark-cache schema for
        streaming sweeps; mirrors ``SimResult.summary`` keys where the
        quantity survives reduction)."""
        r = lambda v: None if v is None else round(float(v), 6)  # noqa: E731
        return {
            "protocol": self.protocol,
            "n_complete": int(self.n_complete),
            "n_messages": int(self.n_messages),
            "completion_rate": r(self.completion_rate),
            "p99_by_size": self.percentiles_by_size(pct),
            "busy_frac": r(self.busy_frac),
            "wasted_frac": r(self.wasted_frac),
            "uplink_busy_frac": r(self.uplink_busy_frac),
            "q_mean_bytes": r(self.q_mean_bytes),
            "q_max_bytes": r(self.q_max_bytes),
            "prio_drained_bytes": [int(x) for x in
                                   self.prio_drained_bytes],
            "lost_chunks": int(self.lost_chunks),
            "p99_small": r(self.percentile_small(pct)),
            "p50_small": r(self.percentile_small(50.0)),
            "p99_all": r(self.percentile(pct)),
            "p50_all": r(self.percentile(50.0)),
            "streaming": {
                "n_buckets": self.stream.n_buckets,
                "max_slowdown": self.stream.max_slowdown,
                "rel_err_bound": r(self.stream.rel_err_bound),
                "n_counted": self.n_counted,
                "warmup_frac": self.stream.warmup_frac,
            },
            "host": None
            if self.host_tx_busy_frac is None
            and self.host_rx_stall_frac is None else {
                "tx_busy_frac": r(self.host_tx_busy_frac),
                "tx_defer_frac": r(self.host_tx_defer_frac),
                "rx_stall_frac": r(self.host_rx_stall_frac),
                "rx_q_max_chunks": self.host_rx_q_max_chunks,
            },
            "trace": self.trace_summary,
        }


def _stats_from_row(cfg, stream: StreamSpec, row: dict, alloc,
                    n_messages: int) -> SweepStats:
    """Host-side assembly of one gathered streaming row."""
    H, ms, sb = cfg.n_hosts, cfg.max_slots, cfg.slot_bytes
    trace_summary = None
    if cfg.trace_on:
        seen = int(row.get("tr_ev_seen", 0))
        cap = cfg.trace.ledger_cap
        trace_summary = {
            "stride": cfg.trace.stride,
            "samples": telemetry.n_samples(cfg),
            "n_events": min(seen, cap), "n_events_seen": seen,
            "events_dropped": max(0, seen - cap), "ledger_cap": cap,
            "q_peak_bytes": int(row["tr_q_peak"]) * sb,
            "grant_out_peak_bytes": int(row["tr_go_peak"]) * sb,
            "up_q_peak_bytes": int(row["tr_uq_peak"]) * sb
            if "tr_uq_peak" in row else None,
            "host_rx_q_peak_chunks": int(row["tr_hq_peak"])
            if "tr_hq_peak" in row else None,
            "timings": None,
        }
    from repro.core.hostmodel import QSCALE
    return SweepStats(
        protocol=cfg.protocol, stream=stream, alloc=alloc,
        n_messages=n_messages, n_complete=int(row["n_complete"]),
        hist=np.asarray(row["hist"]).reshape(stream.n_size_buckets,
                                             stream.n_buckets),
        busy_frac=float(row["busy"]) / (H * ms),
        wasted_frac=float(row["wasted"]) / (H * ms),
        uplink_busy_frac=float(row["uplink_busy"]) / (H * ms),
        q_mean_bytes=float(row["q_sum"]) / (H * ms) * sb,
        q_max_bytes=float(row["q_max"]) * sb,
        prio_drained_bytes=np.asarray(row["prio_drained"],
                                      np.int64) * sb,
        lost_chunks=int(row["lost"]),
        tor_up_busy_frac=float(row["u_busy"])
        / (cfg.fabric.n_uplinks(cfg.n_hosts) * ms)
        if cfg.fabric_on else None,
        fault_lost_chunks=int(row["f_lost"]) if cfg.faults_on else None,
        retx_chunks=int(row["retx"]) if cfg.faults_on else None,
        host_tx_busy_frac=float(row["h_tx_work"]) / (H * ms * QSCALE)
        if cfg.host_tx_on else None,
        host_tx_defer_frac=float(row["h_tx_defer"]) / (H * ms)
        if cfg.host_tx_on else None,
        host_rx_stall_frac=float(row["h_rx_stall"]) / (H * ms)
        if cfg.host_rx_on else None,
        host_rx_q_max_chunks=int(row["h_rx_q_max"])
        if cfg.host_rx_on else None,
        trace_summary=trace_summary,
    )


# =============================================================== engine ==

def run_spec(cfg, spec: SweepSpec) -> list:
    """Execute a :class:`SweepSpec`: prepare, group by static scan
    parameters, shard/chunk/stream as configured, gather, and finalize —
    results in input order. (Public entry point: ``run_sweep(cfg,
    spec)``; see that docstring for semantics.) The host work is recorded
    as spans (``telemetry.span``): one ``sweep.run`` (counters ``slots``:
    runs x ``max_slots``; ``grant_topk_rounds``: the grant top-K's
    selection rounds per slot, the most of any group) holding
    ``sweep.prepare`` and, per group, ``sweep.stack``, ``sweep.dispatch``,
    ``sweep.scan_wait``, ``sweep.fetch`` and ``sweep.stats``."""
    from repro.core import sim as sim_mod
    span = telemetry.span
    with span("sweep.run") as run:
        with span("sweep.prepare"):
            tables = spec.resolve_tables(cfg)
            if not tables:
                return []
            proto = get_protocol(cfg.protocol)
            N = len(tables)
            stream = spec.stream

            alloc = spec.alloc
            if spec.shared_alloc and alloc is None:
                alloc = allocate_priorities(
                    np.concatenate([t.size for t in tables]),
                    unsched_limit=cfg.rtt_bytes, n_prios=cfg.n_prios)
            allocs = list(alloc) if isinstance(alloc, (list, tuple)) \
                else [alloc] * N
            uls = list(spec.unsched_limit_bytes) \
                if isinstance(spec.unsched_limit_bytes, (list, tuple)) \
                else [spec.unsched_limit_bytes] * N
            if len(allocs) != N or len(uls) != N:
                raise ValueError("per-table alloc/unsched_limit lists must "
                                 "match the number of tables")

            prepped = []
            for t, al_i, ul_i in zip(tables, allocs, uls):
                S, al = sim_mod.prepare(cfg, t, al_i, ul_i)
                prepped.append((S, al, proto.n_sched(cfg, al)))
        run["counts"]["slots"] = N * cfg.max_slots
        run["counts"]["grant_topk_rounds"] = max(
            proto.receiver.topk_rounds(cfg, ns, len(t.size))
            for t, (_, _, ns) in zip(tables, prepped))

        groups = group_runs([(len(t.size), ns)
                             for t, (_, _, ns) in zip(tables, prepped)])
        n_dev = resolve_devices(spec.shard)
        fast = n_dev == 1 and spec.chunk_slots is None and stream is None

        def stack(rows):
            return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)

        results: list = [None] * N
        for (_, n_sched), idxs in groups.items():
            if fast:
                # the pre-SweepSpec program, byte for byte: one vmapped
                # jit per group, full states gathered (bit-identity
                # anchor)
                with span("sweep.stack"):
                    S_stack = stack([prepped[i][0] for i in idxs])
                with span("sweep.dispatch"):
                    out = sim_mod._run_batch(cfg, proto, S_stack, n_sched)
            else:
                pad = (-len(idxs)) % n_dev
                padded = idxs + [idxs[-1]] * pad
                with span("sweep.stack"):
                    S_stack = stack([prepped[i][0] for i in padded])
                    aux_stack = stack([_pack_aux(stream, tables[i])
                                       for i in padded]) \
                        if stream is not None else {}
                with span("sweep.dispatch"):
                    out = _sweep_batch(cfg, proto, S_stack, aux_stack,
                                       n_sched, spec.chunk_slots, stream,
                                       n_dev)
            with span("sweep.scan_wait"):
                jax.block_until_ready(out)
            with span("sweep.fetch"):
                st_batch = jax.tree.map(np.asarray, out)

            # padding rows (sharded groups) are simply never read
            with span("sweep.stats"):
                for k, i in enumerate(idxs):
                    row = jax.tree.map(lambda x: x[k], st_batch)
                    if stream is not None:
                        results[i] = _stats_from_row(
                            cfg, stream, row, prepped[i][1],
                            len(tables[i].size))
                    else:
                        results[i] = sim_mod._finalize(
                            cfg, tables[i], prepped[i][0], prepped[i][1],
                            row, spec.return_state, reduce_trace=True)
        return results


__all__ = ["SweepSpec", "StreamSpec", "SweepStats", "run_spec",
           "group_runs", "resolve_devices", "streaming_hist",
           "streaming_percentile", "percentile_from_hist",
           "sd_bucket_edges", "bucket_mid", "DEFAULT_SIZE_EDGES"]
