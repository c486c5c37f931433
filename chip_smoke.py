"""Bring-up check: run the simulator's main path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

One chip:

  golden   the committed CPU golden (``tests/golden/fabric_enabled.json``,
           homa) replayed on every backend: completions must match it
  paper    the paper's §5.2 simulated network — 144 hosts in 9 racks,
           W4 at load 0.8, 6,000 messages, ring/uplink capacity 4096,
           120,000 slots (``benchmarks/fabric_figs._topo(full=True)``) —
           one ``simulate`` per backend; the ``completion`` arrays of
           ``reference``, ``pallas`` and ``pallas_fused`` must be equal
  fused16  the ``fused_speed`` fabric (16 hosts, 4 racks), where the
           fused kernel runs: one ``simulate`` on ``pallas_fused`` vs
           ``reference``, then a 4-seed streaming ``run_sweep`` on both
           (the batched ``grid=(B,)`` kernel); histograms must match

Four chips: the paper network as an 8-seed streaming ``run_sweep`` on
``pallas_fused``, sharded over 4 devices, against the same sweep on one
device. Its horizon is cut to ``SHARDED_SLOTS``, which still covers
every arrival: on a v5e the unsharded 8-run batch costs about 5.8 ms a
slot, so the full 120,000 slots would take over 11 minutes for the
comparison alone.

The pallas backends must run compiled (``SIM_PALLAS_INTERPRET`` set is an
error) and their programs must hold ``tpu_custom_call``. Timings printed
here are bring-up figures, not benchmark numbers. The last line is one
JSON object: ``{"ok": true, "device": {...}}``. With no TPU, or on any
failed phase, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

BACKENDS = ("reference", "pallas", "pallas_fused")
PAPER = dict(n_hosts=144, racks=9, oversub=1.0, ring_cap=4096, up_cap=4096,
             max_slots=120_000, workload="W4", load=0.8, n_messages=6000)
SHARDED_SLOTS = 16 * 2048       # seeds 0-7: last arrival at slot 31,496
FUSED16 = dict(n_hosts=16, racks=4, oversub=2.0, ring_cap=512, up_cap=256,
               max_slots=12_000, workload="W2", load=0.7, n_messages=1200)


def log(msg: str) -> None:
    print(f"[bring-up] {msg}", flush=True)


def kernel_names(hlo_text: str) -> list[str]:
    """Names of the Pallas TPU kernels a lowered program calls."""
    return sorted(set(re.findall(r'kernel_name = "([^"]+)"', hlo_text)))


def sim_config(d: dict, backend: str, **kw):
    from repro.core import FabricConfig, SimConfig
    cfg = SimConfig(protocol="homa", n_hosts=d["n_hosts"],
                    ring_cap=d["ring_cap"], max_slots=d["max_slots"],
                    fabric=FabricConfig(racks=d["racks"],
                                        oversub=d["oversub"],
                                        up_cap=d["up_cap"]),
                    backend=backend, **kw)
    if backend != "reference" and cfg.pallas_interpret:
        raise RuntimeError(f"{backend} resolved to interpret mode on "
                           f"{cfg}")
    return cfg


def table(d: dict, seed: int = 0):
    from repro.core import make_messages
    return make_messages(d["workload"], n_hosts=d["n_hosts"], load=d["load"],
                         n_messages=d["n_messages"], slot_bytes=256,
                         seed=seed)


def check_kernels(label: str, backend: str, hlo_text: str,
                  need: tuple = ()) -> None:
    names = kernel_names(hlo_text)
    log(f"{label} [{backend}] tpu_custom_call="
        f"{'tpu_custom_call' in hlo_text} kernels={names}")
    if backend == "reference":
        return
    if "tpu_custom_call" not in hlo_text:
        raise RuntimeError(f"{label} [{backend}]: no tpu_custom_call in "
                           f"the lowered program")
    missing = [n for n in need if n not in names]
    if missing:
        raise RuntimeError(f"{label} [{backend}]: kernels {missing} "
                           f"missing from {names}")


KERNELS = {"reference": (), "pallas": ("priority_arbiter", "srpt_topk"),
           "pallas_fused": ("fused_slot",)}


def run_simulate(label: str, cfg, tbl):
    """One ``simulate`` with the AOT wall-clock split; returns the result
    after checking the lowered program's kernels."""
    import dataclasses

    import numpy as np

    from repro.core import TraceConfig, get_protocol, simulate
    from repro.core import sim as sim_mod
    proto = get_protocol(cfg.protocol)
    S, alloc = sim_mod.prepare(cfg, tbl)
    n_sched = proto.n_sched(cfg, alloc)
    st0 = sim_mod._init_state(cfg, proto, len(tbl.size))
    check_kernels(label, cfg.backend,
                  sim_mod._run.lower(cfg, proto, S, st0, n_sched).as_text(),
                  KERNELS[cfg.backend])
    timed = dataclasses.replace(
        cfg, trace=TraceConfig(enabled=False, wallclock=True))
    res = simulate(timed, tbl)
    t = res.trace_summary["timings"]
    comp = np.asarray(res.completion, np.int64)
    log(f"{label} [{cfg.backend}] compile_s={t['compile_s']} "
        f"run_s={t['execute_s']} slots={cfg.max_slots} "
        f"n_complete={res.n_complete}/{res.n_messages} "
        f"completion_sum={int(comp.sum())}")
    if res.n_complete == 0:
        raise RuntimeError(f"{label} [{cfg.backend}]: nothing completed")
    return res


def same_completions(label: str, results: dict) -> None:
    import numpy as np
    ref = results["reference"].completion
    for b, r in results.items():
        if not np.array_equal(r.completion, ref):
            n = int((np.asarray(r.completion) != np.asarray(ref)).sum())
            raise RuntimeError(f"{label}: {b} completions differ from "
                               f"reference in {n} messages")
    log(f"{label}: completion arrays bit-identical across "
        f"{sorted(results)}")


def phase_golden() -> None:
    """Replay the committed CPU golden (homa, fabric enabled)."""
    sys.path.insert(0, str(REPO / "scripts"))
    import numpy as np
    from make_golden import ENABLED_META, GOLDEN_DIR, _table
    from repro.core import FabricConfig, SimConfig, simulate
    want = json.loads((GOLDEN_DIR / "fabric_enabled.json").read_text())
    want = np.asarray(want["protocols"]["homa"]["completion"])
    m = ENABLED_META
    fab = FabricConfig(racks=m["racks"], oversub=m["oversub"],
                       up_cap=m["up_cap"])
    for b in BACKENDS:
        cfg = SimConfig(protocol="homa", n_hosts=m["n_hosts"],
                        max_slots=m["max_slots"], ring_cap=m["ring_cap"],
                        fabric=fab, backend=b)
        got = np.asarray(simulate(cfg, _table(m)).completion)
        if not np.array_equal(got, want):
            raise RuntimeError(f"golden [{b}]: {int((got != want).sum())} "
                               f"completions differ from the CPU golden")
        log(f"golden [{b}]: matches fabric_enabled.json (homa, "
            f"{len(want)} messages)")


def phase_paper() -> None:
    tbl = table(PAPER)
    log(f"paper: {PAPER['n_hosts']} hosts / {PAPER['racks']} racks, "
        f"{len(tbl.size)} x {PAPER['workload']} at load {PAPER['load']}, "
        f"arrivals to slot {int(tbl.arrival_slot.max())}, "
        f"{PAPER['max_slots']} slots")
    results = {b: run_simulate("paper", sim_config(PAPER, b), tbl)
               for b in BACKENDS}
    same_completions("paper", results)


def sweep_spec(d: dict, seeds, **kw):
    from repro.core import SweepSpec
    return SweepSpec(seeds=tuple(seeds), workload=d["workload"],
                     load=d["load"], n_messages=d["n_messages"],
                     shared_alloc=True, chunk_slots=2048, streaming=True,
                     **kw)


def lower_sweep(cfg, spec) -> str:
    """Lowered text of the program ``run_sweep`` runs for ``spec`` (one
    group: ``shared_alloc`` and equal table lengths)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import allocate_priorities, get_protocol
    from repro.core import sim as sim_mod
    from repro.core import sweep as sweep_mod
    tables = spec.resolve_tables(cfg)
    alloc = allocate_priorities(np.concatenate([t.size for t in tables]),
                                unsched_limit=cfg.rtt_bytes,
                                n_prios=cfg.n_prios)
    proto = get_protocol(cfg.protocol)
    stack = lambda *xs: jnp.stack(xs)  # noqa: E731
    S = jax.tree.map(stack, *[sim_mod.prepare(cfg, t, alloc)[0]
                              for t in tables])
    aux = jax.tree.map(stack, *[sweep_mod._pack_aux(spec.stream, t)
                                for t in tables])
    return sweep_mod._sweep_batch.lower(
        cfg, proto, S, aux, proto.n_sched(cfg, alloc), spec.chunk_slots,
        spec.stream, 1).as_text()


def run_stream_sweep(label: str, cfg, spec):
    from repro.core import run_sweep
    t0 = time.perf_counter()
    out = run_sweep(cfg, spec)          # gathers to host: work is done
    dt = time.perf_counter() - t0
    log(f"{label} [{cfg.backend}] shard={spec.shard} runs={len(out)} "
        f"wall_s={dt:.3f} (compile included) "
        f"n_complete={[s.n_complete for s in out]} "
        f"hist_sums={[int(s.hist.sum()) for s in out]}")
    return out


def same_stats(label: str, a, b) -> None:
    import numpy as np
    for i, (x, y) in enumerate(zip(a, b, strict=True)):
        if x.n_complete != y.n_complete or not np.array_equal(x.hist,
                                                              y.hist):
            raise RuntimeError(f"{label}: run {i} differs "
                               f"(n_complete {x.n_complete} vs "
                               f"{y.n_complete})")
    log(f"{label}: histograms and n_complete identical over {len(a)} runs")


def phase_fused16() -> None:
    tbl = table(FUSED16)
    results = {b: run_simulate("fused16", sim_config(FUSED16, b), tbl)
               for b in ("reference", "pallas_fused")}
    same_completions("fused16", results)
    spec = sweep_spec(FUSED16, range(4))
    sweeps = {}
    for b in ("reference", "pallas_fused"):
        cfg = sim_config(FUSED16, b)
        if b == "pallas_fused":
            check_kernels("fused16 sweep", b, lower_sweep(cfg, spec),
                          need=("fused_slot_batch",))
        sweeps[b] = run_stream_sweep("fused16 sweep", cfg, spec)
    same_stats("fused16 sweep", sweeps["reference"], sweeps["pallas_fused"])


def phase_sharded(n_dev: int) -> None:
    import jax
    cfg = sim_config(dict(PAPER, max_slots=SHARDED_SLOTS), "pallas_fused")
    seeds = range(8)
    sharded = run_stream_sweep("paper sweep", cfg,
                               sweep_spec(PAPER, seeds, shard=n_dev))
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"device {d.id}: bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    single = run_stream_sweep("paper sweep", cfg, sweep_spec(PAPER, seeds))
    same_stats(f"paper sweep shard={n_dev} vs 1 device", sharded, single)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded paper sweep")
    args = ap.parse_args()
    if os.environ.get("SIM_PALLAS_INTERPRET"):
        raise SystemExit("SIM_PALLAS_INTERPRET is set: the chip run must "
                         "use compiled kernels")

    import jax

    from repro.jax_cache import enable_compile_cache
    cache = enable_compile_cache()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU found (JAX sees {dev}); this check runs "
                         f"on the chip only")
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} but {len(devs)} devices")
    log(f"device {dev}; compile cache {cache}")

    t0 = time.perf_counter()
    if args.chips == 1:
        for phase in (phase_golden, phase_paper, phase_fused16):
            tp = time.perf_counter()
            phase()
            log(f"{phase.__name__} done in "
                f"{time.perf_counter() - tp:.1f} s")
    else:
        phase_sharded(args.chips)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
