"""The chip benchmark of the phased-SSSP service: cells, traffic, reference
and the reduction from traces to metrics (see ``perfbench/run.py``)."""
