"""scan_wall_us_per_slot (scan layer): host microseconds per simulated
run-slot from the scan's dispatch to its result being ready, summed over
the window's calls from the program's own spans (``sim.dispatch`` +
``sim.scan_wait``; ``sweep.dispatch`` + ``sweep.scan_wait`` for sweeps).
Spans are always recorded, so it does not depend on what the profiler
kept. A program without spans has nothing to read."""
from bench import stages


def read(run):
    return stages.scan_wall_us_per_slot(run["record"])
