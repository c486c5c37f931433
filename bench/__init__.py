"""On-chip benchmark of the slotted network simulator (``python bench/run.py``)."""
