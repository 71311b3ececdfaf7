"""Device time under the program's sssp.relax scope in the traced window
over the engine phases advanced in it (program trace, trip counts)."""
from perfbench.layers import relax_ms_per_phase as read  # noqa: F401
