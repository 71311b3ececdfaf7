"""Host time per engine chunk inside ContinuousBatcher.step, outside the
backend's calls (benchmark spans)."""
from perfbench.layers import sched_host_ms_per_chunk as read  # noqa: F401
