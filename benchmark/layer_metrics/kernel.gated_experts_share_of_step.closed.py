"""The gated experts' grouped-product kernel's share of the step's
device time, in a closed-loop cell: time of chip 0's ``gated_experts``
operations over the time of the step's programs
(`benchmark/reduce/steps.py`)."""

from benchmark.reduce import steps


def read(ctx):
    return steps.op_share_of_step(ctx, "gated_experts")
