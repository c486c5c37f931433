"""Quickstart: end-to-end training with the public API — config, data
pipeline, AdamW, checkpointing, restart.

CPU-friendly default (reduced mamba2 config, 120 steps, ~2 min):

    PYTHONPATH=src python examples/quickstart.py

The real ~130M-parameter run (same driver, full config — sized for
accelerators):

    PYTHONPATH=src python examples/quickstart.py --full --steps 300
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def sim_quickstart():
    """30-second tour of the transport-policy API: one structured run,
    then a 4-seed sweep batched behind a single jit trace."""
    from repro.core import (SimConfig, SweepSpec, simulate, run_sweep,
                            registered_protocols, make_messages)

    print(f"registered protocols: {', '.join(registered_protocols())}")
    tbl = make_messages("W1", n_hosts=4, load=0.7, n_messages=200,
                        slot_bytes=256, seed=0)
    cfg = SimConfig(protocol="homa", n_hosts=4, max_slots=2000, ring_cap=256)
    res = simulate(cfg, tbl)                       # -> SimResult
    print(f"homa: {res.n_complete}/{res.n_messages} complete, "
          f"p99 slowdown {res.percentile(99):.2f}, "
          f"downlink busy {float(res.busy_frac.mean()):.2%}")

    sweep = run_sweep(cfg, SweepSpec(seeds=(0, 1, 2, 3), workload="W1",
                                     load=0.7, n_messages=200,
                                     shared_alloc=True))
    p99s = [r.percentile(99) for r in sweep]
    print(f"4-seed sweep (one jit trace): p99 = "
          f"{', '.join(f'{p:.2f}' for p in p99s)}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_quickstart")
    a = ap.parse_args()

    sim_quickstart()
    from repro.launch import train   # deferred: needs the training deps

    argv = ["--arch", "mamba2-130m", "--steps", str(a.steps),
            "--seq-len", "128" if not a.full else "1024",
            "--batch", "8", "--lr", "3e-3",
            "--ckpt-dir", a.ckpt_dir, "--ckpt-every", "50",
            "--log-every", "10"]
    if not a.full:
        argv.append("--smoke")
    res = train.main(argv)
    assert res["final_loss"] < res["first_loss"], "loss did not improve"
    print(f"quickstart OK: loss {res['first_loss']:.3f} -> "
          f"{res['final_loss']:.3f} over {res['steps']} steps")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    main()
