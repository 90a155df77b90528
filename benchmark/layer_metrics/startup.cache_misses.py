"""`startup.cache_misses` in every cell: see `benchmark/reduce/startup.py`."""

from benchmark.reduce.startup import cache_misses as read  # noqa: F401
