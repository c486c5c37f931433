"""Run one cell of the on-chip benchmark and print one JSON result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the chips of the machine it starts on: it loads the
cell named in ``BENCHMARK.json``, sets up (imports, device check, the
priority allocation from the seed, one warm-up call of the cell's entry
at its own shapes, which compiles or reads JAX's persistent cache), then
drives whole calls of the entry back to back for ``--seconds``. It
starts no call after that and finishes the one in flight. After the
window it compares one call, drawn from the seed, with the
configuration's plain reference (``bench/reference.py`` unless the
configuration names another) and prints the numbers compared beside
their limits, last on standard error and last in the result. A named
reference or traffic kind that cannot be loaded stops the run before
set-up.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the window and reports the per-layer
metrics, ``busy_s``/``window_s`` and a breakdown. Traces and per-run
records go to ``artifacts/bench/<cell>/``. With no TPU, or fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def _process_start() -> float:
    """``time.perf_counter()`` reading of this process's start."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return now - max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return now


T_START = _process_start()
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
# libtpu's logs stay inside the checkout, never in a fixed /tmp directory.
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = str(ROOT / "artifacts" / "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from bench import cells, entries, gen, xtrace  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations (persistent-cache reads included)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"no TPU found (JAX sees {info}); the benchmark "
                         f"runs on the chip only")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return info


def peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
        program_factory=entries.Program) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    c = cells.cell(args.workload, root)
    work, config, mix, table = c["workload"], c["config"], c["mix"], \
        c["table"]
    chips = int(work["chips"])

    import jax

    from repro.jax_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = device_info(jax, chips, require_tpu)
    devs = jax.devices()[:chips]

    sim = config["sim"]
    H, sb = sim["n_hosts"], sim["slot_bytes"]
    alloc_sizes = gen.alloc_sample(mix, args.seed)
    program = program_factory(config, mix, alloc_sizes)
    runs = int(mix.get("runs_per_call", 1))
    slots = int(mix["max_slots"])
    log(f"cell {args.workload}: config {work['config']}, mix "
        f"{work['traffic']}, entry {mix['entry']}, backend "
        f"{program.cfg.backend}, device {device}, compile cache {cache_dir}")

    counter = CompileCounter()
    t0 = time.perf_counter()
    program.call(gen.call_tables(mix, H, sb, args.seed, gen.WARMUP_CALL,
                                 table))
    warm_s = time.perf_counter() - t0
    warm_compiles = counter.n

    out_dir = root / "artifacts" / "bench" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    tracer = xtrace.Recorder(out_dir / "trace") if args.trace else None

    # ---- the measured window
    setup_s = time.perf_counter() - T_START
    calls, prepare_s = [], []
    n_before = counter.n
    t_open = time.perf_counter()
    if tracer:
        tracer.start()
    while time.perf_counter() - t_open < args.seconds:
        tables = gen.call_tables(mix, H, sb, args.seed, len(calls), table)
        if tracer:
            for t in tables:
                tp = time.perf_counter()
                with tracer.span("bench.prepare"):
                    program.prepare(t)
                prepare_s.append(time.perf_counter() - tp)
            with tracer.span("bench.call"):
                answers = program.call(tables)
        else:
            answers = program.call(tables)
        calls.append((tables, answers))
    t_close = time.perf_counter()
    if tracer:
        tracer.stop()
        log(f"trace written in {time.perf_counter() - t_close:.1f} s")
    window_s = t_close - t_open
    in_window = counter.n - n_before
    n_runs = len(calls) * runs
    log(f"window {window_s:.4f} s: {len(calls)} calls, {n_runs} runs of "
        f"{slots} slots; compilations in window {in_window} (warm-up "
        f"{warm_compiles}, {warm_s:.3f} s)")
    device["memory_peak_bytes"] = peak_bytes(devs)

    record = {"workload": args.workload, "seed": args.seed,
              "seconds": args.seconds, "trace": args.trace,
              "entry": mix["entry"], "backend": program.cfg.backend,
              "calls": len(calls),
              "runs": n_runs, "slots_per_run": slots,
              "window_s": window_s, "setup_s": setup_s, "warm_s": warm_s,
              "compiles_in_window": in_window,
              "prepare_s": prepare_s}

    # ---- correctness: one call drawn from the seed, against the reference
    k = int(gen.rng(args.seed, gen.SAMPLE_STREAM).integers(len(calls)))
    tables, got = calls[k]
    del calls
    tr = time.perf_counter()
    want = entries.reference_answers(config, mix, alloc_sizes, tables, devs,
                                     ref=c["reference"])
    checks, failed = entries.compare(mix["entry"], got, want)
    record["reference_s"] = time.perf_counter() - tr
    record["checked_call"] = k
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    result = {"correct": correct, "attempted": n_runs, "failed": failed,
              "device": device}
    if args.trace:
        t_read = time.perf_counter()
        red = tracer.reduce()
        if device["platform"] != "cpu" and not red["summary"][
                "busy_s_by_device"]:
            raise RuntimeError(
                f"no device plane with an {xtrace.OPS_LINE!r} line in the "
                f"trace; planes: {red['summary']['planes']}")
        record["trace_summary"] = red["summary"]
        run_view = {"trace": red, "record": record}
        metrics = {}
        t_readers = time.perf_counter()
        for m in c["per_layer"]:
            v = cells.metric_reader(m["name"], root)(run_view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"trace reduced in {t_readers - t_read:.1f} s, read by the "
            f"metrics in {time.perf_counter() - t_readers:.1f} s")
        device["busy_s"] = red["summary"]["busy_s"]
        device["window_s"] = red["summary"]["window_s"]
        result["metrics"] = metrics
        result["breakdown"] = xtrace.breakdown(red)
    else:
        values = {"run_slots_per_s": n_runs * slots / window_s,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in c["end_to_end"]}
    result["checks"] = checks
    record["result"] = result
    name = f"seed{args.seed}_trace{args.trace}.json"
    (out_dir / name).write_text(json.dumps(record, indent=1, default=float))
    for key, v in checks.items():
        log(f"check {key}: {v['value']} (limit {v['limit']})")
    return result


def main(argv=None) -> int:
    print(json.dumps(run(argv)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
