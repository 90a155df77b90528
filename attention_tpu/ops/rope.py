"""Rotary position embeddings (RoPE).

The reference kernel is position-free (plain SDPA over given Q/K/V —
`attention.c:20-75`); a usable model family needs positions.  RoPE is
the TPU-friendly choice: a pure elementwise rotation of Q and K that
fuses into the surrounding projections under XLA, adds no parameters,
no attention-bias tensor, and keys can be cached *already rotated* (the
score depends only on relative position), so the decode path needs no
re-rotation of history.

Split-half convention (as in the original RoFormer paper and most JAX
implementations): the head dim is split into two halves that form the
(real, imag) components of dh/2 complex pairs.

YaRN (arXiv:2309.00071) stretches a trained context by ``factor``
without touching the fast-turning pairs: pair ``i`` of frequency ``f_i
= theta^(-2i/dh)`` turns ``original * f_i / 2 pi`` times over the
original context; pairs that turn more than ``beta_fast`` times keep
``f_i``, pairs that turn fewer than ``beta_slow`` times get ``f_i /
factor``, and those between blend linearly in the pair's index
(`yarn_inv_freq`).  The softmax scale a model multiplies in beside it
is `yarn_mscale`; cos and sin stay unscaled here.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class YarnScaling(NamedTuple):
    """A configuration's ``rope_scaling`` of type ``yarn`` (hashable:
    a module field)."""

    factor: float
    original: int            # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 mscale ln(factor) + 1``
    (1 without stretching)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float,
                  scaling: YarnScaling) -> jax.Array:
    """The ``head_dim // 2`` pair frequencies under YaRN, fp32."""
    half = head_dim // 2
    i = jnp.arange(half, dtype=jnp.float32)
    freq = theta ** (-i / half)

    def pair_of(turns):
        # the (real-valued) pair that turns ``turns`` times over the
        # original context
        return (head_dim * math.log(scaling.original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(scaling.beta_fast)), 0)
    high = min(math.ceil(pair_of(scaling.beta_slow)), head_dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return freq / scaling.factor * (1.0 - keep) + freq * keep


def rope_angles(positions: jax.Array, head_dim: int,
                theta: float = 10000.0,
                scaling: YarnScaling | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables for ``positions`` (any shape), fp32.

    Returns arrays of shape ``positions.shape + (head_dim // 2,)``.
    """
    if head_dim % 2:
        raise ValueError(f"RoPE requires an even head_dim, got {head_dim}")
    half = head_dim // 2
    if scaling is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq = yarn_inv_freq(head_dim, theta, scaling)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0,
               scaling: YarnScaling | None = None) -> jax.Array:
    """Rotate ``x`` (..., S, dh) by its per-row positions (..., S).

    ``positions`` broadcasts against x's leading axes (pass ``(S,)`` for
    shared positions, ``(B, 1, S)``-shaped for per-sequence offsets).
    Math runs in fp32; the result is cast back to ``x.dtype``.
    ``scaling`` stretches the frequencies by YaRN.
    """
    half = x.shape[-1] // 2
    cos, sin = rope_angles(positions, x.shape[-1], theta, scaling)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)
