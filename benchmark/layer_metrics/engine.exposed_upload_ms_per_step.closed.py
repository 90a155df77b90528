"""`engine.exposed_upload_ms_per_step` in the closed-loop cell: see `benchmark/reduce/phases.py`."""

from benchmark.reduce.phases import exposed_upload_ms_per_step as read  # noqa: F401
