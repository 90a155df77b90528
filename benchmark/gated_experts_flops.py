"""Operations and bytes of the routed experts' gated feed-forward
``W_d (silu(W_g y) * (W_u y))``, computed from its shapes and the
step's routing: the work, not an implementation of it.

A token-expert pair whose expert is held here is three products of
``width x hidden``, 2 width hidden operations each.  Bytes: each held
expert that received a pair is read ONCE (three kernels, at the item
size the configuration states), and each pair moves its row in and its
result out at that item size.  Pairs of experts held elsewhere, pairs
of zero-compute experts, and held experts without a pair cost nothing.
"""

from __future__ import annotations


def gated_experts_flops(pairs: int, width: int, hidden: int) -> int:
    return 6 * width * hidden * pairs


def gated_experts_bytes(pairs: int, experts_reached: int, width: int,
                        hidden: int, *, itemsize: int) -> int:
    weights = experts_reached * 3 * width * hidden * itemsize
    rows = pairs * 2 * width * itemsize
    return weights + rows
