"""Gated DeltaNet: the linear-attention mixer of hybrid decoders.

A layer of `ops.gated_delta`'s recurrence (arXiv:2412.06464, as in
flash-linear-attention's ``GatedDeltaNet``): q, k and v projections,
each through a depthwise causal convolution and SiLU; q and k
L2-normalised per head; a write strength ``beta`` and a decay ``a`` per
token and head; the recurrent state read out through a gated RMSNorm.
Where attention keeps a row of K and V per token, this layer keeps per
request ONE state ``(heads, key_dim, value_dim)`` in float32 and the
last ``conv_width - 1`` inputs of its convolution, however long the
request grows.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from attention_tpu.ops.gated_delta import (
    RaggedStateStep,
    gated_delta_scan,
    ragged_causal_conv,
    ragged_gated_delta,
)


def _a_log_init(key, shape, dtype=jnp.float32):
    """log A with A uniform in [1, 16) (the family's initialisation)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step log-uniform in [1e-3, 1e-1)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedDeltaNet(nn.Module):
    """(B, S, D) -> (B, S, D); with a `RaggedStateStep` cache, one
    packed serving step (B = 1) that returns ``(out, cache)``."""

    num_heads: int
    key_dim: int
    value_dim: int
    conv_width: int = 4
    neg_eigval: bool = True   # beta in (0, 2): eigenvalues down to -1
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, cache: RaggedStateStep | None = None):
        if cache is not None and not isinstance(cache, RaggedStateStep):
            raise ValueError(
                "GatedDeltaNet serves through the packed step only "
                f"(RaggedStateStep); got {type(cache).__name__}")
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        f32 = jnp.float32

        def dense(name, features, dtype=self.dtype):
            return nn.Dense(features, use_bias=False, dtype=dtype,
                            name=name)(x)

        qkv = jnp.concatenate(
            [dense("q_proj", h * dk), dense("k_proj", h * dk),
             dense("v_proj", h * dv)], axis=-1)
        # depthwise, no bias; one kernel over the concatenated channels
        # is the three separate convolutions side by side
        conv = jnp.concatenate(
            [self.param(name, nn.initializers.lecun_normal(),
                        (self.conv_width, width), f32)
             for name, width in (("q_conv", h * dk), ("k_conv", h * dk),
                                 ("v_conv", h * dv))], axis=-1)
        a_log = self.param("A_log", _a_log_init, (h,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), f32)
        beta = jax.nn.sigmoid(dense("b_proj", h, f32))
        if self.neg_eigval:
            beta = 2.0 * beta
        log_a = -jnp.exp(a_log) * jax.nn.softplus(
            dense("a_proj", h, f32) + dt_bias)
        gate = dense("g_proj", h * dv)

        batch, seq, _ = x.shape
        if cache is None:
            pad = jnp.pad(qkv, ((0, 0), (self.conv_width - 1, 0), (0, 0)))
            mixed = sum(
                pad[:, i:i + seq].astype(f32) * conv[i]
                for i in range(self.conv_width)).astype(self.dtype)
        else:
            if batch != 1:
                raise ValueError("a packed step has batch 1")
            mixed, conv_pool = ragged_causal_conv(qkv[0], conv, cache)
            mixed = mixed[None]
        mixed = jax.nn.silu(mixed)
        q, k, v = jnp.split(mixed, [h * dk, 2 * h * dk], axis=-1)
        q = q.reshape(batch, seq, h, dk).astype(f32)
        k = k.reshape(batch, seq, h, dk).astype(f32)
        v = v.reshape(batch, seq, h, dv)

        def unit(t):
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

        q, k = unit(q) * dk ** -0.5, unit(k)
        if cache is None:
            o = jax.vmap(lambda *a: gated_delta_scan(*a)[0])(
                q, k, v, log_a, beta)
        else:
            o, state_pool = ragged_gated_delta(
                q[0], k[0], v[0], log_a[0], beta[0], cache)
            o = o[None]
            cache = cache._replace(state_pool=state_pool,
                                   conv_pool=conv_pool)
        o = nn.RMSNorm(dtype=self.dtype, name="o_norm")(o)
        o = o.reshape(batch, seq, h * dv) * jax.nn.silu(gate)
        out = nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                       name="o_proj")(o)
        return out if cache is None else (out, cache)
