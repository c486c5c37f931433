"""Readings that the limits of ``correct`` are set from, at a cell's own size.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--control-seeds 1 2 3]

In one process on the chip: for each seed, the first call of the cell's
window (the same tables a run draws), the program's answers, the
configuration's plain reference's, and for the control seeds that
reference with strict priority switched off (the control, which has to
come out not correct). Prints one JSON line per seed with the numbers
compared for the program and for the control. The benchmark's own runs
do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import cells, entries, gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    c = cells.cell(args.workload)
    config, mix, ref = c["config"], c["mix"], c["reference"]

    import jax

    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    devs = jax.devices()[:int(c["workload"]["chips"])]
    H, sb = config["sim"]["n_hosts"], config["sim"]["slot_bytes"]
    for seed in args.seeds:
        sizes = gen.alloc_sample(mix, seed)
        tables = gen.call_tables(mix, H, sb, seed, 0, c["table"])
        t0 = time.perf_counter()
        got = entries.Program(config, mix, sizes).call(tables)
        t1 = time.perf_counter()
        want = entries.reference_answers(config, mix, sizes, tables, devs,
                                         ref=ref)
        t2 = time.perf_counter()
        line = {"workload": args.workload, "seed": seed,
                "program": entries.compare(mix["entry"], got, want)[0],
                "program_s": t1 - t0, "reference_s": t2 - t1}
        if seed in args.control_seeds:
            ctl = entries.reference_answers(config, mix, sizes, tables,
                                            devs, strict_priority=False,
                                            ref=ref)
            line["control"] = entries.compare(mix["entry"], ctl, want)[0]
        if mix["entry"] == "simulate":
            line["completed"] = [int((w["completion"] >= 0).sum())
                                 for w in want]
        else:
            line["completed"] = [w["n_complete"] for w in want]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
