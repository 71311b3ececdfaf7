"""Device busy time in the traced window over the engine phases advanced in
it (trace and trip counts)."""
from perfbench.layers import phase_device_ms as read  # noqa: F401
