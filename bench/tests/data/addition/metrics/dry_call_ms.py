"""dry_call_ms (entry layer; a test fixture that a deployment added as
files brings): host milliseconds per call of the cell's entry in the
traced window, from the harness's record of the run."""


def read(run):
    calls = run["record"].get("calls")
    return 1000.0 * run["record"]["window_s"] / calls if calls else None
