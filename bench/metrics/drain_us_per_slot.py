"""drain_us_per_slot (slot step layer): microseconds per simulated run-slot
in the uplink and downlink drains, host RX included (``uplink_drain`` +
``downlink_drain``): the stage's share of the scan's recorded device
self time, times ``scan_wall_us_per_slot`` (``bench/stages.py``). A
trace without a device plane, or a program whose ops carry no stage
scope, has nothing to read."""
from pathlib import Path

from bench import stages

ROOT = Path(__file__).resolve().parents[2]
SCOPES = ("uplink_drain", "downlink_drain")


def read(run):
    return stages.stage_us_per_slot(ROOT, run["record"], SCOPES)
