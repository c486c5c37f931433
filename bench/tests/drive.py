"""Drive a whole benchmark run on the CPU, optionally with a fault planted
in the program underneath, and print its result line.

    python bench/tests/drive.py <root> <workload> <seed> <seconds> <trace> <fault>

``root`` holds a ``BENCHMARK.json`` with its own cells (see
``bench/tests/data/tiny``). The look for a chip is skipped; everything
else is the run the benchmark makes. Faults:

  none       the program as it is
  answer     one answer altered where the scan produces it: a message's
             completion slot, or a sweep run's histogram
  half       half of a sweep's runs left out: every other run's row
             replaced by its neighbour's
  exchange   the rows of every device but the first left out of the
             sweep's gather (the first device's rows in their place)
  control    the plain reference, with strict priority switched off, put
             in the program's place
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def plant(fault: str):
    """Patch the program for ``fault``; returns the program factory."""
    import jax
    import numpy as np

    from bench import entries
    from repro.core import sim, sweep

    if fault == "answer":
        run, summary = sim._run, sweep._device_summary

        def broken_run(*args):
            st = run(*args)
            return {**st, "completion": st["completion"].at[0].add(1)}

        def broken_summary(cfg, st, acc):
            return summary(cfg, st, acc.at[0].add(1))
        sim._run, sweep._device_summary = broken_run, broken_summary
    elif fault in ("half", "exchange"):
        batch = sweep._sweep_batch

        def broken(cfg, proto, S, aux, n_sched, chunk, stream, n_dev):
            out = batch(cfg, proto, S, aux, n_sched, chunk, stream, n_dev)
            n = jax.tree.leaves(out)[0].shape[0]
            idx = np.arange(n) // 2 * 2 if fault == "half" \
                else np.arange(n) % (n // n_dev)
            return jax.tree.map(lambda x: np.asarray(x)[idx], out)
        sweep._sweep_batch = broken
    elif fault == "control":
        class Control(entries.Program):
            def __init__(self, config, mix, alloc_sizes):
                super().__init__(config, mix, alloc_sizes)
                self.config, self.alloc_sizes = config, alloc_sizes

            def call(self, tables):
                return entries.reference_answers(
                    self.config, self.mix, self.alloc_sizes, tables,
                    jax.devices()[:1], strict_priority=False)
        return Control
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")
    return entries.Program


def main() -> int:
    root, workload, seed, seconds, trace, fault = sys.argv[1:7]
    from bench import run
    factory = plant(fault)
    result = run.run(["--workload", workload, "--seed", seed, "--seconds",
                      seconds, "--trace", trace], root=Path(root),
                     require_tpu=False, program_factory=factory)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
