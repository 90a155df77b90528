"""The selection's share of the step's device time, in a closed-loop
cell: time of chip 0's ``index_select`` operations (each query row's
threshold at its ``index_topk``-th score, by bisection) over the time
of the step's programs (`benchmark/reduce/steps.py`)."""

from benchmark.reduce import steps


def read(ctx):
    # the name the operation's text starts with: the attention
    # kernel's text holds ``index_select`` too, as its operand
    return steps.op_share_of_step(ctx, r"^%?index_select(\.\d+)?( |$)")
