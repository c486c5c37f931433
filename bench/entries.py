"""The program's entries that a window drives, and the answers it checks.

A mix names its entry: ``simulate`` (one run per call, the answer is the
completion slot of every message) or ``run_sweep`` (a batch of runs per
call, streaming statistics: the answer per run is its count of completed
messages and its (size, slowdown) histogram). The same answers come from
the configuration's plain reference (``cells.reference_module``:
``bench/reference.py`` unless the configuration names another) for the
comparison that decides ``correct``.

No backend is named here: the program runs the arbitration backend it
resolves by default, which is what its users get.
"""
from __future__ import annotations

import numpy as np

from bench import cells


def sim_config(config: dict, mix: dict):
    """The program's ``SimConfig`` for a configuration file and mix."""
    from repro.core import FabricConfig, SimConfig
    fab = config.get("fabric")
    return SimConfig(max_slots=int(mix["max_slots"]),
                     fabric=FabricConfig(**fab) if fab else None,
                     **config["sim"])


def stream_spec(mix: dict):
    from repro.core import StreamSpec
    s = dict(mix["stream"])
    s["size_edges"] = tuple(s["size_edges"])
    return StreamSpec(**s)


class Program:
    """The system under test, set up for one cell: its configuration and
    the priority allocation drawn once from the run's size sample."""

    def __init__(self, config: dict, mix: dict, alloc_sizes: np.ndarray):
        from repro.core import allocate_priorities
        self.mix = mix
        self.entry = mix["entry"]
        if self.entry not in ("simulate", "run_sweep"):
            raise ValueError(f"unknown entry {self.entry!r}")
        self.cfg = sim_config(config, mix)
        self.alloc = allocate_priorities(alloc_sizes,
                                         unsched_limit=self.cfg.rtt_bytes,
                                         n_prios=self.cfg.n_prios)

    def table(self, t: dict):
        from repro.core.workloads import MessageTable
        return MessageTable(t["src"], t["dst"], t["size"], t["arrival"],
                            "bench", float(self.mix["load"]),
                            self.cfg.slot_bytes)

    def call(self, tables: list[dict]) -> list[dict]:
        """One call of the cell's entry; returns with every answer on the
        host."""
        from repro.core import SweepSpec, run_sweep, simulate
        if self.entry == "simulate":
            return [{"completion": np.asarray(simulate(
                self.cfg, self.table(t), self.alloc).completion)}
                for t in tables]
        spec = SweepSpec(tables=tuple(self.table(t) for t in tables),
                         alloc=self.alloc,
                         chunk_slots=self.mix.get("chunk_slots"),
                         streaming=stream_spec(self.mix),
                         shard=self.mix.get("shard", False))
        return [{"n_complete": int(s.n_complete), "hist": np.asarray(s.hist)}
                for s in run_sweep(self.cfg, spec)]

    def prepare(self, t: dict) -> None:
        """The program's host-side preparation of one run, waited for."""
        import jax

        from repro.core.sim import prepare
        S, _ = prepare(self.cfg, self.table(t), self.alloc)
        jax.block_until_ready(S)


def reference_answers(config: dict, mix: dict, alloc_sizes, tables,
                      devices, *, strict_priority: bool = True,
                      ref=None) -> list:
    """The answers of the plain reference ``ref`` (default: the
    configuration's, ``cells.reference_module(config)``) for ``tables``,
    the runs spread over ``devices`` so that they run side by side."""
    reference = ref or cells.reference_module(config)
    sim = config["sim"]
    alloc = reference.priority_allocation(
        alloc_sizes, sim["rtt_slots"] * sim["slot_bytes"], sim["n_prios"])
    slots = int(mix["max_slots"])
    comps = [reference.simulate(config, t, alloc, slots,
                                strict_priority=strict_priority,
                                device=devices[i % len(devices)])
             for i, t in enumerate(tables)]
    if mix["entry"] == "simulate":
        return [{"completion": np.asarray(c)} for c in comps]
    return [{"n_complete": int((np.asarray(c) >= 0).sum()),
             "hist": reference.slowdown_hist(config, t, c, slots,
                                             mix["stream"])}
            for t, c in zip(tables, comps)]


def compare(entry: str, got: list, want: list) -> tuple[dict, int]:
    """The numbers compared, each beside its limit, and how many runs
    disagree. Every comparison is exact: the simulation is
    deterministic, so its limit is 0."""
    if len(got) != len(want):
        raise ValueError(f"{len(got)} answers for {len(want)} runs")
    if entry == "simulate":
        off = [int(np.sum(g["completion"] != w["completion"]))
               if g["completion"].shape == w["completion"].shape
               else w["completion"].size for g, w in zip(got, want)]
        checks = {"completion_mismatch": {"value": sum(off), "limit": 0}}
    else:
        n_off = [abs(g["n_complete"] - w["n_complete"])
                 for g, w in zip(got, want)]
        h_off = [int(np.abs(g["hist"].astype(np.int64)
                            - w["hist"].astype(np.int64)).sum())
                 if g["hist"].shape == w["hist"].shape
                 else int(w["hist"].sum()) + 1
                 for g, w in zip(got, want)]
        off = [a + b for a, b in zip(n_off, h_off)]
        checks = {"n_complete_off": {"value": sum(n_off), "limit": 0},
                  "hist_off": {"value": sum(h_off), "limit": 0}}
    return checks, sum(1 for x in off if x)
