"""`startup.rest_s` in every cell: see `benchmark/reduce/startup.py`."""

from benchmark.reduce.startup import rest_s as read  # noqa: F401
