"""The collectives' own device time (nothing else runs), per chip, over the
engine phases advanced in the traced window."""
from perfbench.layers import collective_exposed_ms_per_phase as read  # noqa: F401
