"""Mean engine phases of the requests that an engine lane answered in the
window (Request.phases)."""
from perfbench.layers import phases_per_query as read  # noqa: F401
