"""`kernel.ragged_share_of_step` in the closed-loop cell: see `benchmark/reduce/steps.py`."""

from benchmark.reduce.steps import ragged_share_of_step as read  # noqa: F401
