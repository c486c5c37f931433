"""sender_us_per_slot (slot step layer): microseconds per simulated run-
slot in sender select, host TX, chunk priority and ``on_send``
(``sender_select``): the stage's share of the scan's recorded device
self time, times ``scan_wall_us_per_slot`` (``bench/stages.py``). A
trace without a device plane, or a program whose ops carry no stage
scope, has nothing to read."""
from pathlib import Path

from bench import stages

ROOT = Path(__file__).resolve().parents[2]
SCOPES = ("sender_select",)


def read(run):
    return stages.stage_us_per_slot(ROOT, run["record"], SCOPES)
