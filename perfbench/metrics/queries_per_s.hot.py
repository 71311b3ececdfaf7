"""Requests answered in the window over the window's length (host clock), in
the cell whose cache answers a share: its own bound, since where the window
closes in a mix of free cache answers and whole solves moves it more."""
from perfbench.layers import queries_per_s as read  # noqa: F401
