"""Host time of the program's serving.round spans less serving.sync and
serving.take_row, per dispatched chunk (program spans)."""
from perfbench.layers import round_host_ms_per_chunk as read  # noqa: F401
