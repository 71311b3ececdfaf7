"""Device time with a collective in flight, per chip, over the engine phases
advanced in the traced window."""
from perfbench.layers import collective_ms_per_phase as read  # noqa: F401
