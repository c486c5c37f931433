"""Test reference: ``bench/reference.py`` itself, named by path. A
configuration that names it is judged as one that names none."""
from bench.reference import priority_allocation, simulate, slowdown_hist

__all__ = ["priority_allocation", "simulate", "slowdown_hist"]
