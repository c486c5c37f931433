"""Traffic generation: open-loop Poisson message tables from a mix file.

A mix file (``bench/traffic/<name>.json``) holds the parameters; this
module is the generator of its ``kind``, ``poisson``. A mix of any other
kind ``K`` is drawn by ``table`` of ``bench/kinds/K.py``, which takes
the arguments of ``poisson_table`` and returns the same four arrays; the
streams it is given, and the allocation sample from ``size_bins``, are
the same for every kind. Message sizes are drawn
from a mixture of log-uniform bins (``size_bins``: probability, lowest
and highest byte count), arrivals are Poisson at ``load`` times the
aggregate host link rate, and sources and destinations are uniform with
``dst != src``. The arithmetic is the repository's W1-W5 generator
(``repro.core.workloads``), copied so that the yardstick does not move
with the program under test: the same seed gives the same table here and
there.

Table ``k`` of a run is drawn from ``(seed, k)``, so every call of the
measured window simulates fresh traffic of the same distribution. The
size sample that fixes the priority allocation is drawn once per run,
from its own stream.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from bench import cells

WARMUP_CALL = 1 << 32           # call index of the set-up's warm-up call
ALLOC_STREAM = (1 << 32) + 1    # rng stream of the allocation size sample
SAMPLE_STREAM = (1 << 32) + 2   # rng stream that picks the checked call


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Generator for one stream of one run seed (any whole number)."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def sample_sizes(bins, n: int, gen: np.random.Generator) -> np.ndarray:
    """``n`` message sizes in bytes from log-uniform ``bins``."""
    ps = np.array([b[0] for b in bins], np.float64)
    ps = ps / ps.sum()
    which = gen.choice(len(bins), size=n, p=ps)
    lo = np.array([b[1] for b in bins])[which].astype(np.float64)
    hi = np.array([b[2] for b in bins])[which].astype(np.float64)
    u = gen.random(n)
    sizes = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    return np.maximum(sizes.astype(np.int64), 1)


def poisson_table(mix: dict, n_hosts: int, slot_bytes: int,
                  gen: np.random.Generator) -> dict:
    """One message table: ``src``, ``dst``, ``size`` (bytes) and
    ``arrival`` (slot) arrays of ``mix["n_messages"]`` messages."""
    if mix.get("kind", "poisson") != "poisson":
        raise ValueError(f"unknown traffic kind {mix.get('kind')!r}")
    n = int(mix["n_messages"])
    sizes = sample_sizes(mix["size_bins"], n, gen)
    slots = np.maximum((sizes + slot_bytes - 1) // slot_bytes, 1)
    mean_gap = slots.mean() / (float(mix["load"]) * n_hosts)
    gaps = gen.exponential(mean_gap, n)
    arrivals = np.floor(np.cumsum(gaps)).astype(np.int64)
    src = gen.integers(0, n_hosts, n)
    dst = gen.integers(0, n_hosts - 1, n)
    dst = np.where(dst >= src, dst + 1, dst)
    return {"src": src.astype(np.int32), "dst": dst.astype(np.int32),
            "size": sizes, "arrival": arrivals.astype(np.int32)}


def table_fn(mix: dict, root: Path = cells.ROOT):
    """The table generator of the mix's ``kind``: ``poisson_table``, or
    ``table`` of ``bench/kinds/<kind>.py`` under ``root``."""
    kind = mix.get("kind", "poisson")
    return poisson_table if kind == "poisson" \
        else cells.kind_table(kind, root)


def call_tables(mix: dict, n_hosts: int, slot_bytes: int, seed: int,
                call: int, table=None) -> list[dict]:
    """The ``mix["runs_per_call"]`` tables of call ``call`` of a run, drawn
    by ``table`` (default: ``table_fn(mix)``)."""
    table = table or table_fn(mix)
    return [table(mix, n_hosts, slot_bytes, rng(seed, call, i))
            for i in range(int(mix.get("runs_per_call", 1)))]


def alloc_sample(mix: dict, seed: int) -> np.ndarray:
    """Message sizes from which the run's priority allocation is drawn
    (the paper's workload-knowledge model, §4)."""
    return sample_sizes(mix["size_bins"], int(mix["alloc_messages"]),
                        rng(seed, ALLOC_STREAM))
