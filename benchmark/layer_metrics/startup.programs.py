"""`startup.programs` in every cell: see `benchmark/reduce/startup.py`."""

from benchmark.reduce.startup import programs as read  # noqa: F401
