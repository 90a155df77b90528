"""Operations and bytes of the state-space (Mamba-2) recurrence,
computed from its shapes: the recurrent form's minimum, so that no
chunking overhead of a kernel can read over 100% of its roofline.

Per token and head the recurrence ``S <- a S + dt x B^T``, ``y = S C``
is two products of the ``(P, N)`` state's size, 2 P N operations each
(the outer-product update and the read-out); scaling the state by the
scalar ``a`` is not counted.  Per slot and step the state is read once
and written once in float32, however many tokens the slot's span has;
each token moves its x and y (H P each), its B and C (G N each) and
its step (H) at the model's item size.
"""

from __future__ import annotations


def ssm_flops(tokens: int, heads: int, p: int, n: int) -> int:
    return 4 * p * n * heads * tokens


def ssm_bytes(tokens: int, slot_steps: int, heads: int, p: int, n: int,
              groups: int, *, itemsize: int) -> int:
    state = 2 * 4 * slot_steps * heads * p * n
    rows = tokens * itemsize * (2 * heads * p + 2 * groups * n + heads)
    return state + rows
