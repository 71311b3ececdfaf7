"""Process start to the first measured request, compiles included (host
clock)."""
from perfbench.layers import setup_s as read  # noqa: F401
