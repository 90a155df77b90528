"""`model.step_device_ms_p50` in the closed-loop cell: see `benchmark/reduce/steps.py`."""

from benchmark.reduce.steps import step_device_ms_p50 as read  # noqa: F401
