"""`engine.host_ms_per_step` in the closed-loop cell: see `benchmark/reduce/steps.py`."""

from benchmark.reduce.steps import host_ms_per_step as read  # noqa: F401
