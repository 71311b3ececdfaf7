"""Device idle between consecutive executions of the step program, per
chunk (program trace)."""
from perfbench.layers import chunk_gap_ms as read  # noqa: F401
