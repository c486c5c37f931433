"""prepare_ms (entry layer): host milliseconds per run in the program's
``repro.core.sim.prepare``, timed by the harness around its own call of
``prepare`` on each run's table in the traced window, ending in
``block_until_ready`` on the outputs. Mean over the window's runs."""


def read(run):
    times = run["record"].get("prepare_s") or []
    return 1000.0 * sum(times) / len(times) if times else None
