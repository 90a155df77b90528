"""`startup.trace_s` in every cell: see `benchmark/reduce/startup.py`."""

from benchmark.reduce.startup import trace_s as read  # noqa: F401
