"""Operations and bytes of the routed experts' feed-forward
``W2 relu(W1 u)^2`` in the latent space, computed from its shapes and
the step's routing: the work, not an implementation of it.

A token-expert pair whose expert is held here is two products of
``latent x hidden``, 2 latent hidden operations each.  Bytes: each
held expert that received a pair is read ONCE (both kernels, at the
item size the configuration states for them, not at the float32 the
program stores today), and each pair moves its latent row in and its
result out at that item size.  Pairs of experts held elsewhere, and
held experts without a pair, cost nothing.
"""

from __future__ import annotations


def experts_flops(pairs: int, latent: int, hidden: int) -> int:
    return 4 * latent * hidden * pairs


def experts_bytes(pairs: int, experts_reached: int, latent: int,
                  hidden: int, *, itemsize: int) -> int:
    weights = experts_reached * 2 * latent * hidden * itemsize
    rows = pairs * 2 * latent * itemsize
    return weights + rows
