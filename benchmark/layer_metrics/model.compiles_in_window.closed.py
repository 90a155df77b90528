"""`model.compiles_in_window` in the closed-loop cell: see `benchmark/reduce/steps.py`."""

from benchmark.reduce.steps import compiles_in_window as read  # noqa: F401
