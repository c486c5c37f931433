"""Benchmark orchestrator: one harness per paper table/figure + the
framework-side benchmarks. Prints ``name,us_per_call,derived`` CSV blocks
(per-figure CSVs are emitted by each harness; this prints a roll-up).

    PYTHONPATH=src python -m benchmarks.run [--full] [--only figNN,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated harness names")
    args = ap.parse_args()

    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import paper_figs as F
    from benchmarks import collective_sched as C
    from benchmarks import fabric_figs as FF
    from benchmarks import faults_figs as FL
    from benchmarks import hostmodel_figs as HM
    from benchmarks import telemetry_figs as TF
    from benchmarks.roofline import backend_compare, fused_speed
    from benchmarks.sweep_speed import sweep_speed

    harnesses = {
        "sweep_speed": sweep_speed,
        "backend_compare": backend_compare,
        "fused_speed": fused_speed,
        "fabric_smoke": FF.fabric_smoke,
        "fabric_oversub": FF.fabric_oversub,
        "fig14_fabric_incast": FF.fig14_fabric_incast,
        "faults_smoke": FL.faults_smoke,
        "fig_faults": FL.fig_faults,
        "hostmodel_smoke": HM.hostmodel_smoke,
        "fig_hostmodel": HM.fig_hostmodel,
        "trace_smoke": TF.trace_smoke,
        "fig13_prio_usage_time": TF.fig13_prio_usage_time,
        "fig10_incast": F.fig10_incast,
        "fig12_slowdown": F.fig12_slowdown,
        "fig13_median": F.fig13_median,
        "fig14_preemption_lag": F.fig14_preemption_lag,
        "fig15_utilization": F.fig15_utilization,
        "fig16_wasted_bandwidth": F.fig16_wasted_bandwidth,
        "fig17_unsched_prios": F.fig17_unsched_prios,
        "fig18_cutoffs": F.fig18_cutoffs,
        "fig19_sched_prios": F.fig19_sched_prios,
        "fig20_unsched_bytes": F.fig20_unsched_bytes,
        "fig21_prio_usage": F.fig21_prio_usage,
        "table1_queues": F.table1_queues,
        "collective_structural": C.structural,
        "collective_predicted": C.predicted,
    }
    only = set(args.only.split(",")) if args.only else None

    summary = []
    for name, fn in harnesses.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn(full=args.full)
            dt = time.time() - t0
            summary.append((name, dt * 1e6 / max(len(rows), 1),
                            f"rows={len(rows)}"))
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            summary.append((name, -1, "ERROR"))

    print("\n# --- roll-up: name,us_per_call,derived ---")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")
    if any(d == "ERROR" for _, _, d in summary):
        sys.exit(1)


if __name__ == "__main__":
    main()
