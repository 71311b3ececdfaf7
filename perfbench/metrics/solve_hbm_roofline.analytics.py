"""Least HBM time of the full-row solves answered in the traced window over
the device's busy time in it."""
from perfbench.layers import solve_hbm_roofline as read  # noqa: F401
