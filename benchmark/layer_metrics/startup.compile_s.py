"""`startup.compile_s` in every cell: see `benchmark/reduce/startup.py`."""

from benchmark.reduce.startup import compile_s as read  # noqa: F401
