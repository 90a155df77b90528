"""`startup.lower_s` in every cell: see `benchmark/reduce/startup.py`."""

from benchmark.reduce.startup import lower_s as read  # noqa: F401
