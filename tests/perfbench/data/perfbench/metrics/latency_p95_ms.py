"""A test-only end-to-end metric: 95th percentile latency over all requests
of the window, from scheduled time to answer (host clock)."""

from perfbench.layers import latency_p95_ms as read  # noqa: F401
