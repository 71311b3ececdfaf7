"""Device idle share of the traced window: 1 - union of device op intervals
over the window (profiler trace)."""
from perfbench.layers import device_idle_share as read  # noqa: F401
