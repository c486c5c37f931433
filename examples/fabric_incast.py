"""Leaf-spine fabric demo: an oversubscribed incast, homa vs basic.

Builds the paper's Fig. 14 shape — repeated fan-in bursts into one
receiver, Poisson background underneath — on a 16-host / 4-rack fabric
with 2:1 TOR-uplink oversubscription, and prints how each protocol's
small-message tail and per-tier queues hold up.

    PYTHONPATH=src python examples/fabric_incast.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import SimConfig, FabricConfig, simulate, scenarios  # noqa: E402


def main():
    tbl = scenarios.incast(12, 2048, n_hosts=16, n_bursts=8,
                           period_slots=1500, background="W2",
                           background_load=0.5, n_background=600, seed=2)
    fab = FabricConfig(racks=4, oversub=2.0, up_cap=1024)
    print(f"topology: {fab.racks} racks x {fab.rack_size(16)} hosts, "
          f"{fab.n_uplinks(16)} uplinks/TOR (oversub {fab.oversub}:1)")
    print(f"traffic: {len(tbl.size)} messages "
          f"(12-way incast bursts of 2 KB + W2 background)\n")

    for proto in ("homa", "basic"):
        cfg = SimConfig(protocol=proto, n_hosts=16, max_slots=16_000,
                        ring_cap=1024, fabric=fab)
        r = simulate(cfg, tbl)
        s = r.summary()
        f = s["fabric"]
        print(f"{proto:6s} p99 small {s['p99_small']:6.2f}   "
              f"complete {r.n_complete}/{r.n_messages}   "
              f"downlink qmax {s['q_max_bytes'] / 1024:6.1f} KB   "
              f"uplink qmax {f['up_q_max_bytes'] / 1024:6.1f} KB   "
              f"lost {r.lost_chunks}")
    print("\nHoma's wire priorities shield small messages at BOTH queueing"
          "\ntiers; basic funnels everything through one FIFO level.")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    main()
