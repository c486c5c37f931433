"""Shared benchmark machinery: cached simulator runs + CSV emission.

All paper-figure benchmarks run the JAX packet-level simulator at reduced
scale (CPU budget): 8 hosts instead of 144, ~2000 messages per run. The
qualitative claims being validated (protocol ordering, slowdown bands,
utilization ceilings, queue bounds) are scale-robust; EXPERIMENTS.md
discusses the deltas. `--full` increases scale.

Two entry points, both returning the same JSON-safe summary schema
(:meth:`repro.core.SimResult.summary` plus the run's parameters):

  ``sim_run``    one cached point (legacy path, still used where points
                 differ in compile-time config such as slot size)
  ``sim_sweep``  a list of points sharing the protocol/topology config,
                 batched through ``run_sweep`` so the whole group costs
                 one jit trace instead of one per point
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import numpy as np

from repro.core.sim import SimConfig, simulate, run_sweep
from repro.core.sweep import SweepSpec
from repro.core.fabric import FabricConfig
from repro.core.hostmodel import HostConfig
from repro.core.workloads import WorkloadSpec, make_messages
from repro.core import scenarios
from repro.core.priorities import PriorityAllocation
from repro.kernels.arbiter.dispatch import resolve_backend

ART = Path(__file__).resolve().parents[1] / "artifacts" / "bench"
ART.mkdir(parents=True, exist_ok=True)

DEFAULT = dict(n_hosts=8, n_messages=2000, max_slots=60_000, ring_cap=2048,
               slot_bytes=256)


def _merge_params(n_hosts, n_messages, max_slots, ring_cap, slot_bytes,
                  fabric=None):
    p = {**DEFAULT, "fabric": fabric}
    for k, v in dict(n_hosts=n_hosts, n_messages=n_messages,
                     max_slots=max_slots, ring_cap=ring_cap,
                     slot_bytes=slot_bytes).items():
        if v is not None:
            p[k] = v
    return p


def _fabric_cfg(fabric: dict | None) -> FabricConfig | None:
    """JSON-able fabric spec (the cache-key form) -> FabricConfig."""
    return FabricConfig(**fabric) if fabric else None


def _host_key(host) -> str | dict | None:
    """Host spec -> its JSON-able cache-key form (preset name, kwargs
    dict, or a full HostConfig flattened to kwargs)."""
    if isinstance(host, HostConfig):
        return dataclasses.asdict(host)
    return host


def _spec_key(spec) -> dict | None:
    """WorkloadSpec (or its kwargs dict) -> JSON-able cache-key form."""
    if spec is None:
        return None
    if isinstance(spec, WorkloadSpec):
        spec = dataclasses.asdict(spec)
    return {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in spec.items()}


def _point_table(pt: dict, p: dict):
    """Synthesize one point's MessageTable: a Poisson workload point
    (``workload`` + ``load``), a structured scenario (``scenario`` =
    {"kind": "incast" | "hotspot" | "shuffle", ...kwargs}), or a full
    ``spec`` (:class:`WorkloadSpec` instance or its kwargs dict) —
    the unified form the other two reduce to."""
    sp = pt.get("spec")
    if sp is not None:
        if any(k in pt for k in ("workload", "load", "scenario")):
            raise ValueError(
                "a sweep point combines 'spec' with 'workload'/'load'/"
                "'scenario'; a WorkloadSpec already carries the whole "
                "generation recipe — pass exactly one form")
        if not isinstance(sp, WorkloadSpec):
            sp = WorkloadSpec(**sp)
        if "seed" in pt:
            sp = sp.with_seed(pt["seed"])
        return sp.build(n_hosts=p["n_hosts"], slot_bytes=p["slot_bytes"])
    sc = pt.get("scenario")
    if sc is not None and ("workload" in pt or "load" in pt):
        raise ValueError(
            "a sweep point combines 'scenario' with 'workload'/'load', but "
            "scenario points ignore those fields — they would enter the "
            "cache key and masquerade as distinct data points; put "
            "background traffic inside the scenario spec instead")
    if sc is None:
        return make_messages(pt["workload"], n_hosts=p["n_hosts"],
                             load=pt["load"], n_messages=p["n_messages"],
                             slot_bytes=p["slot_bytes"],
                             seed=pt.get("seed", 0))
    sc = dict(sc)
    kind = sc.pop("kind")
    common = dict(n_hosts=p["n_hosts"], slot_bytes=p["slot_bytes"],
                  seed=pt.get("seed", 0))
    # a spec may spell seed (etc.) inside the scenario dict itself —
    # those win over the point/topology defaults, never collide
    common.update({k: sc.pop(k) for k in ("n_hosts", "slot_bytes", "seed")
                   if k in sc})
    if kind == "incast":
        return scenarios.incast(sc.pop("fan_in"), sc.pop("burst_bytes"),
                                **common, **sc)
    if kind == "hotspot":
        return scenarios.hotspot(sc.pop("workload"), **common, **sc)
    if kind == "shuffle":
        return scenarios.shuffle(**common, **sc)
    raise ValueError(f"unknown scenario kind {kind!r}; expected "
                     f"incast | hotspot | shuffle")


def _point_key(*, workload, protocol, load, seed, overcommit, alloc,
               unsched_limit_bytes, params, scenario=None, spec=None,
               host=None) -> tuple[dict, Path]:
    # platform + resolved backend: a checkout copied to another machine
    # must never serve one platform's (or backend's) results on another
    keyd = dict(workload=workload, protocol=protocol, load=load, seed=seed,
                overcommit=overcommit, alloc=alloc, scenario=scenario,
                ul=(unsched_limit_bytes if not isinstance(
                    unsched_limit_bytes, np.ndarray) else "array"),
                platform=jax.default_backend(),
                backend=resolve_backend(None), **params)
    # optional axes join the key ONLY when set
    if spec is not None:
        keyd["spec"] = _spec_key(spec)
    if host is not None:
        keyd["host"] = _host_key(host)
    h = hashlib.sha1(json.dumps(keyd, sort_keys=True).encode()).hexdigest()[:16]
    return keyd, ART / f"sim_{h}.json"


def _alloc_from_dict(alloc: dict | None) -> PriorityAllocation | None:
    if not alloc:
        return None
    return PriorityAllocation(n_prios=alloc.get("n_prios", 8),
                              n_unsched=alloc["n_unsched"],
                              cutoffs=tuple(alloc.get("cutoffs", ())),
                              unsched_bytes_frac=0.0)


def _summarize(result, keyd) -> dict:
    return {"params": keyd, **result.summary(warmup_frac=0.1)}


def sim_run(*, workload: str, protocol: str, load: float, seed: int = 0,
            n_hosts=None, n_messages=None, max_slots=None, ring_cap=None,
            slot_bytes=None, overcommit=None, alloc: dict | None = None,
            unsched_limit_bytes=None, fabric: dict | None = None,
            host: dict | str | None = None, cache: bool = True) -> dict:
    """Run (or fetch cached) one simulation; returns JSON-safe summary.
    ``fabric`` is a JSON-able FabricConfig kwargs dict (cache-key form);
    ``host`` a preset name or HostConfig kwargs dict (DESIGN.md §10)."""
    p = _merge_params(n_hosts, n_messages, max_slots, ring_cap, slot_bytes,
                      fabric)
    keyd, fp = _point_key(workload=workload, protocol=protocol, load=load,
                          seed=seed, overcommit=overcommit, alloc=alloc,
                          unsched_limit_bytes=unsched_limit_bytes, params=p,
                          host=host)
    if cache and fp.exists():
        return json.loads(fp.read_text())

    tbl = make_messages(workload, n_hosts=p["n_hosts"], load=load,
                        n_messages=p["n_messages"],
                        slot_bytes=p["slot_bytes"], seed=seed)
    cfg = SimConfig(n_hosts=p["n_hosts"], slot_bytes=p["slot_bytes"],
                    protocol=protocol, overcommit=overcommit,
                    ring_cap=p["ring_cap"], fabric=_fabric_cfg(fabric),
                    host=host,
                    max_slots=min(p["max_slots"],
                                  int(tbl.arrival_slot.max()) + 20_000))
    res = simulate(cfg, tbl, alloc=_alloc_from_dict(alloc),
                   unsched_limit_bytes=unsched_limit_bytes)
    out = _summarize(res, keyd)
    fp.write_text(json.dumps(out))
    return out


def sim_sweep(points: list[dict], *, protocol: str, overcommit=None,
              n_hosts=None, n_messages=None, max_slots=None, ring_cap=None,
              slot_bytes=None, fabric: dict | None = None,
              host: dict | str | None = None,
              cache: bool = True) -> list[dict]:
    """Cached batched runner: each point is a dict with ``workload`` and
    ``load`` (or a ``scenario``/``spec`` form, see :func:`_point_table`)
    plus optional ``seed`` / ``alloc`` / ``unsched_limit_bytes``. All
    points share the protocol/topology config — including the optional
    leaf-spine ``fabric`` spec (a FabricConfig kwargs dict) and ``host``
    model (preset name or HostConfig kwargs dict); uncached
    points run through ``run_sweep(cfg, SweepSpec(...))``, which groups
    runs by their static scan parameters internally (one jit trace per
    group — scenario sweeps legitimately vary the message count).
    Returns one summary per point, in order.

    Cache keys use the *configured* ``max_slots`` cap (exactly like
    ``sim_run``), never the realized group horizon, so a point's cache
    identity does not depend on which other points share its sweep and
    fully-cached reruns skip table synthesis entirely. Uncached points
    run at a shared horizon — the longest uncached table's, clamped to
    the cap — recorded in the stored summary as ``max_slots_used``."""
    p = _merge_params(n_hosts, n_messages, max_slots, ring_cap, slot_bytes,
                      fabric)
    keys = [_point_key(workload=pt.get("workload"), protocol=protocol,
                       load=pt.get("load"), seed=pt.get("seed", 0),
                       overcommit=overcommit, alloc=pt.get("alloc"),
                       unsched_limit_bytes=pt.get("unsched_limit_bytes"),
                       scenario=pt.get("scenario"), spec=pt.get("spec"),
                       host=host, params=p)
            for pt in points]
    out: list[dict | None] = [None] * len(points)
    todo = []
    for i, (keyd, fp) in enumerate(keys):
        if cache and fp.exists():
            out[i] = json.loads(fp.read_text())
        else:
            todo.append(i)
    if todo:
        tables = {i: _point_table(points[i], p) for i in todo}
        horizon = max(int(t.arrival_slot.max()) for t in tables.values())
        ms = min(p["max_slots"], horizon + 20_000)
        cfg = SimConfig(n_hosts=p["n_hosts"], slot_bytes=p["slot_bytes"],
                        protocol=protocol, overcommit=overcommit,
                        ring_cap=p["ring_cap"], fabric=_fabric_cfg(fabric),
                        host=host, max_slots=ms)
        # mixed table lengths are fine: run_sweep groups runs by their
        # static scan parameters internally (core/sweep.group_runs — the
        # same grouping this function used to reimplement)
        spec = SweepSpec(
            tables=[tables[i] for i in todo],
            alloc=[_alloc_from_dict(points[i].get("alloc")) for i in todo],
            unsched_limit_bytes=[points[i].get("unsched_limit_bytes")
                                 for i in todo])
        for i, res in zip(todo, run_sweep(cfg, spec)):
            keyd, fp = keys[i]
            out[i] = {**_summarize(res, keyd), "max_slots_used": ms}
            fp.write_text(json.dumps(out[i]))
    return out


def emit(name: str, rows: list[dict]):
    """Print CSV rows and save them under artifacts/bench/<name>.json."""
    if not rows:
        print(f"# {name}: no rows")
        return
    cols = list(rows[0].keys())
    print(f"# --- {name} ---")
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r.get(c, "")) for c in cols))
    (ART / f"{name}.json").write_text(json.dumps(rows, indent=1))
