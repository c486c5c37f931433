"""finalize_ms (entry layer): host milliseconds per run from the scan's
result being ready to the answers, from the program's own spans: the
copy to the host (``sim.fetch``) and the post-processing
(``sim.finalize``; ``sweep.fetch`` + ``sweep.stats`` for sweeps). Mean
over the window's runs. A program without spans has nothing to read."""
from bench import stages


def read(run):
    return stages.finalize_ms(run["record"])
