"""device_idle_share (device layer): 1 - (union of the intervals in which
an operation runs on the device) / (the traced window), per device; on
several chips the largest of them. A trace with no device plane (one
taken on the CPU) has nothing to read; the harness refuses a device
trace without one."""


def read(run):
    shares = run["trace"]["summary"]["idle_share_by_device"]
    return max(shares.values()) if shares else None
