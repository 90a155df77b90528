"""`entry.request_tpot_p50_ms` in the closed-loop cell: see `benchmark/reduce/steps.py`."""

from benchmark.reduce.steps import request_tpot_p50_ms as read  # noqa: F401
