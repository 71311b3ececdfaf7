"""Share of requests answered in the window by a cache hit or by coalescing
(Request flags)."""
from perfbench.layers import cache_answer_share as read  # noqa: F401
