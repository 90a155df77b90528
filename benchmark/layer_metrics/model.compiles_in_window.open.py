"""`model.compiles_in_window` in the open-loop cell: see `benchmark/reduce/steps.py`."""

from benchmark.reduce.steps import compiles_in_window as read  # noqa: F401
