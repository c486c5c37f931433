"""Test reference: ``bench/reference.py``'s answers with strict priority
switched off, whatever the caller asks. A configuration that names it
is judged against the control, so a sound program comes out not
correct."""
from bench import reference
from bench.reference import priority_allocation, slowdown_hist

__all__ = ["priority_allocation", "simulate", "slowdown_hist"]


def simulate(config, table, alloc, max_slots, *, strict_priority=True,
             device=None):
    return reference.simulate(config, table, alloc, max_slots,
                              strict_priority=False, device=device)
