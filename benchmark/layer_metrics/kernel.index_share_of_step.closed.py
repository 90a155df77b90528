"""The lightning indexer's scoring kernel's share of the step's device
time, in a closed-loop cell: time of chip 0's ``index_scores``
operations over the time of the step's programs
(`benchmark/reduce/steps.py`).  Linear in the context, where the
attention it chooses keys for is bounded by ``index_topk``."""

from benchmark.reduce import steps


def read(ctx):
    # the name the operation's text starts with: the selection's text
    # holds ``index_scores`` too, as its operand
    return steps.op_share_of_step(ctx, r"^%?index_scores(\.\d+)?( |$)")
