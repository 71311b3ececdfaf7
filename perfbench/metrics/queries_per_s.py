"""Requests answered in the window over the window's length (host clock)."""
from perfbench.layers import queries_per_s as read  # noqa: F401
