"""`engine.exposed_sample_ms_per_step` in the open-loop cell: see `benchmark/reduce/phases.py`."""

from benchmark.reduce.phases import exposed_sample_ms_per_step as read  # noqa: F401
