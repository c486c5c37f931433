"""Test reference without ``simulate``: naming it stops the run."""
from bench.reference import priority_allocation, slowdown_hist

__all__ = ["priority_allocation", "slowdown_hist"]
