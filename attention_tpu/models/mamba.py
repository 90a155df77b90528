"""Mamba-2: the state-space mixer of hybrid decoders.

A layer of `ops.ssm`'s recurrence (arXiv:2405.21060, as the Nemotron-H
family has it).  One input projection gives the gate ``z``, the
convolved channels ``xBC`` and a step ``dt`` per head:

    [z | xBC | dt] = W_in u            (d_inner | d_inner + 2 G N | H)
    xBC = silu(conv(xBC) + b)          depthwise, causal, WITH bias
    xBC -> x (H, P), B (G, N), C (G, N)    head h reads group h // (H / G)
    dt = softplus(dt + dt_bias),   a = exp(-dt exp(A_log))
    S_t = a_t S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D x_t
    out = W_out RMSNorm_groups(y * silu(z))

The norm is over ``G`` groups of ``d_inner / G`` channels with one
scale vector.  Where attention keeps a row of K and V per token, this
layer keeps per request ONE state ``(H, P, N)`` in float32 and the
last ``conv_width - 1`` rows of ``xBC``, however long the request
grows.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from attention_tpu.models.linear_attention import _a_log_init, _dt_bias_init
from attention_tpu.ops.gated_delta import RaggedStateStep, ragged_causal_conv
from attention_tpu.ops.ssm import ragged_ssm_scan, ssm_scan


class Mamba2Mixer(nn.Module):
    """(B, S, D) -> (B, S, D); with a `RaggedStateStep` cache, one
    packed serving step (B = 1) that returns ``(out, cache)``."""

    num_heads: int
    head_dim: int
    state_dim: int
    num_groups: int
    conv_width: int = 4
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def conv_channels(self) -> int:
        return (self.num_heads * self.head_dim
                + 2 * self.num_groups * self.state_dim)

    @nn.compact
    def __call__(self, u: jax.Array, cache: RaggedStateStep | None = None):
        if cache is not None and not isinstance(cache, RaggedStateStep):
            raise ValueError(
                "Mamba2Mixer serves through the packed step only "
                f"(RaggedStateStep); got {type(cache).__name__}")
        h, p, n, g = (self.num_heads, self.head_dim, self.state_dim,
                      self.num_groups)
        inner, f32 = h * p, jnp.float32
        batch, seq, _ = u.shape
        proj = nn.Dense(2 * inner + 2 * g * n + h, use_bias=False,
                        dtype=self.dtype, name="in_proj")(u)
        z, xbc, dt = jnp.split(proj, [inner, inner + self.conv_channels],
                               axis=-1)
        taps = self.param("conv_weight", nn.initializers.lecun_normal(),
                          (self.conv_width, self.conv_channels), f32)
        bias = self.param("conv_bias", nn.initializers.zeros,
                          (self.conv_channels,), f32)
        a_log = self.param("A_log", _a_log_init, (h,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), f32)
        skip = self.param("D", nn.initializers.ones, (h,), f32)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        log_a = -dt * jnp.exp(a_log)

        if cache is None:
            pad = jnp.pad(xbc, ((0, 0), (self.conv_width - 1, 0), (0, 0)))
            mixed = sum(pad[:, i:i + seq].astype(f32) * taps[i]
                        for i in range(self.conv_width))
            mixed = (mixed + bias).astype(self.dtype)
        else:
            if batch != 1:
                raise ValueError("a packed step has batch 1")
            mixed, conv_pool = ragged_causal_conv(xbc[0], taps, cache, bias)
            mixed = mixed[None]
        mixed = jax.nn.silu(mixed)
        x, b, c = jnp.split(mixed, [inner, inner + g * n], axis=-1)
        x = x.reshape(batch, seq, h, p)
        b = b.reshape(batch, seq, g, n)
        c = c.reshape(batch, seq, g, n)
        if cache is None:
            y = jax.vmap(lambda *a: ssm_scan(*a)[0])(x, dt, log_a, b, c)
        else:
            y, state_pool = ragged_ssm_scan(x[0], dt[0], log_a[0], b[0],
                                            c[0], cache)
            y = y[None]
            cache = cache._replace(state_pool=state_pool,
                                   conv_pool=conv_pool)
        y = y + skip[:, None] * x.astype(f32)
        y = (y.reshape(batch, seq, inner)
             * jax.nn.silu(z.astype(f32))).astype(self.dtype)
        # one scale vector, statistics per group of d_inner / G channels
        y = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                       reduction_axes=-1, feature_axes=(-2, -1),
                       name="norm")(y.reshape(batch, seq, g, inner // g))
        out = nn.Dense(u.shape[-1], use_bias=False, dtype=self.dtype,
                       name="out_proj")(y.reshape(batch, seq, inner))
        return out if cache is None else (out, cache)
