"""Run the paper-faithful Homa packet-level simulator and print a miniature
Figure-12: 99p slowdown by message size, for any registered protocols.

    PYTHONPATH=src python examples/homa_network_sim.py [--workload W3]
        [--protocols homa,basic,ndp]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core import (SimConfig, simulate, registered_protocols,
                        make_messages)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="W3")
    ap.add_argument("--load", type=float, default=0.8)
    ap.add_argument("--messages", type=int, default=1500)
    ap.add_argument("--protocols", default="homa,basic",
                    help=f"comma-separated; registered: "
                         f"{','.join(registered_protocols())}")
    a = ap.parse_args()
    protos = a.protocols.split(",")

    tbl = make_messages(a.workload, n_hosts=8, load=a.load,
                        n_messages=a.messages, slot_bytes=256, seed=1)
    print(f"workload {a.workload} @ {a.load:.0%} load, "
          f"{a.messages} messages, 8 hosts")
    results = {}
    for proto in protos:
        cfg = SimConfig(n_hosts=8, protocol=proto, max_slots=60_000,
                        ring_cap=2048)          # unknown proto -> ValueError
        res = simulate(cfg, tbl)
        results[proto] = res
        b = res.percentiles_by_size(99, n_buckets=8)
        print(f"\n{proto}: {res.n_complete}/{res.n_messages} complete, "
              f"priorities: {res.alloc.n_unsched} unsched / "
              f"{res.alloc.n_sched} sched, cutoffs {res.alloc.cutoffs}")
        print("  size_bytes   p99_slowdown   median")
        for sz, p, m in zip(b["sizes"], b["p"], b["median"]):
            bar = "#" * min(int(p * 2), 60)
            print(f"  {int(sz):>9}   {p:>7.2f} {bar}")

    if "homa" in results and "basic" in results:
        h, bsc = results["homa"], results["basic"]
        ph = h.percentile(99, h.done & (h.size_bytes < 1000))
        pb = bsc.percentile(99, bsc.done & (bsc.size_bytes < 1000))
        if ph is None or pb is None:    # e.g. W5 has no sub-1KB messages
            print("\nno completed sub-1KB messages to compare")
        else:
            print(f"\nsmall-message p99: homa {ph:.2f} vs basic {pb:.2f} "
                  f"({pb / ph:.1f}x better)")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    main()
