"""scan_us_per_slot (scan layer): device microseconds of the scan program
per simulated run-slot, from the profiler trace of the window. The scan
program is found by its XLA module name (``sim._run`` for single runs,
``sweep._sweep_batch`` for sweeps); on several chips its time is summed
over the devices, as the run-slots are. A trace with no device plane (one
taken on the CPU) has nothing to read; a device trace without the scan
program is an error, never a silent gap."""

SCAN_MODULES = {"simulate": "jit__run", "run_sweep": "jit__sweep_batch"}


def read(run):
    rec, summary = run["record"], run["trace"]["summary"]
    if not summary["busy_s_by_device"]:
        return None
    want = SCAN_MODULES[rec["entry"]]
    secs = sum(s for name, s in summary["module_s"].items()
               if name.split("(")[0] == want)
    if secs <= 0:
        raise RuntimeError(f"no XLA module {want!r} on the trace's devices; "
                           f"modules: {sorted(summary['module_s'])[:20]}")
    return 1e6 * secs / (rec["runs"] * rec["slots_per_run"])
