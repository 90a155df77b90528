"""Operations and bytes of the gated delta rule, computed from its
shapes: the recurrent form's minimum, so that no chunking overhead of
a kernel can read over 100% of its roofline.

Per token and head the recurrence ``S <- a S + b k (v - a S^T k)^T``,
``o = S^T q`` is three products of the ``(dk, dv)`` state with a
vector, 2 dk dv operations each; scaling the state by ``a`` and the
sums are not counted.  Per slot and step the state is read once and
written once in float32, however many tokens the slot's span has; each
token moves its q, k, v and o at the model's item size and its two
gates in float32.
"""

from __future__ import annotations


def gated_delta_flops(tokens: int, heads: int, dk: int, dv: int) -> int:
    return 6 * dk * dv * heads * tokens


def gated_delta_bytes(tokens: int, slot_steps: int, heads: int, dk: int,
                      dv: int, *, itemsize: int) -> int:
    state = 2 * 4 * slot_steps * heads * dk * dv
    rows = tokens * heads * (itemsize * (2 * dk + 2 * dv) + 2 * 4)
    return state + rows
