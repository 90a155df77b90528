"""Multi-head latent attention (MLA): (B, S, D) -> (B, S, D).

Queries and keys / values are projected through low-rank latents:

    c_q           = N(x W_qa) * s_q                  D -> q_lora_rank
    [q_n | q_r]_h = c_q W_qb                         per head: nope + rope
    [c | k_r]     = x W_kva ;  c = N(c) * s_kv       D -> kv_lora_rank + rope
    [k_n | v]_h   = c W_kvb                          per head: nope + v
    q_r, k_r      = rope(q_r), rope(k_r)             k_r shared by every head
    p_h           = causal softmax((q_n,h . k_n,h + q_r,h . k_r) / sqrt(nope + rope))
    out           = concat_h(p_h v_h) W_o

``s_q`` = sqrt(D / q_lora_rank) and ``s_kv`` = sqrt(D / kv_lora_rank)
(the ``mla_scale_*`` keys); ``k_r`` is not scaled.

What a token leaves behind is ``[c | k_r]``, whatever the head count.
A packed engine step (`RaggedPagedStep` of ONE pool) serves the
ABSORBED form from it: ``q_c,h = q_n,h W_kvb,h^K`` meets the cached
latent itself, so the heads are the group of one KV head whose keys
are ``[c | k_r]`` and whose values are the keys' first
``kv_lora_rank`` lanes; ``W_kvb,h^V`` takes the result to the head's
values.  Every row of a step takes this form, a prefill chunk and a
decode row alike.  Without a cache (training, a whole sequence at
once) the layer computes the expanded form above.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    ragged_paged_append,
    ragged_paged_attention,
)
from attention_tpu.ops.rope import apply_rope

#: the lanes a cached row is padded to: a row is copied and sliced in
#: whole vector registers
_LANES = 128


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of a token's row in the latent pool: ``[c | k_r]``, padded
    with zeros to whole registers (576 -> 640)."""
    return -(-(kv_lora_rank + rope_dim) // _LANES) * _LANES


class LatentAttention(nn.Module):
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, cache: RaggedPagedStep | None = None):
        batch, seq, dim = x.shape
        heads, rank = self.num_heads, self.kv_lora_rank
        nope, rot = self.nope_dim, self.rope_dim

        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        def norm(name):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              name=name)

        c_q = norm("q_a_norm")(dense("q_a_proj", self.q_lora_rank)(x))
        c_q = c_q * jnp.asarray((dim / self.q_lora_rank) ** 0.5, self.dtype)
        q = dense("q_b_proj", heads * (nope + rot))(c_q)
        q = q.reshape(batch, seq, heads, nope + rot).transpose(0, 2, 1, 3)
        ckv = dense("kv_a_proj", rank + rot)(x)
        c = norm("kv_a_norm")(ckv[..., :rank])
        c = c * jnp.asarray((dim / rank) ** 0.5, self.dtype)
        # (rank, heads, nope + v): a head's key part and value part
        w_kvb = self.param(
            "kv_b_proj", nn.initializers.lecun_normal(in_axis=0,
                                                      out_axis=(1, 2)),
            (rank, heads, nope + self.v_dim), jnp.float32
        ).astype(self.dtype)
        if cache is None:
            pos = jnp.arange(seq, dtype=jnp.int32)
        elif isinstance(cache, RaggedPagedStep):
            pos = cache.token_pos[None, None, :]
        else:
            raise ValueError(
                f"latent attention is served from a packed step's one "
                f"pool; it has no {type(cache).__name__} path")
        q_n = q[..., :nope]
        q_r = apply_rope(q[..., nope:], pos, self.rope_theta)
        k_r = apply_rope(ckv[:, None, :, rank:], pos, self.rope_theta)
        scale = (nope + rot) ** -0.5
        if cache is None:
            out = self._expanded(q_n, q_r, c, k_r[:, 0], w_kvb, scale)
        else:
            out, cache = self._absorbed(q_n, q_r, c, k_r, w_kvb, scale,
                                        cache)
        out = out.transpose(0, 2, 1, 3).reshape(batch, seq, -1)
        out = dense("o_proj", dim)(out.astype(self.dtype))
        return out if cache is None else (out, cache)

    def _expanded(self, q_n, q_r, c, k_r, w_kvb, scale):
        """The published form: per-head keys and values from the
        latent, dense causal softmax in float32."""
        kv = jnp.einsum("bsc,chn->bhsn", c, w_kvb)
        k_n, v = kv[..., :self.nope_dim], kv[..., self.nope_dim:]
        s = (jnp.einsum("bhqn,bhkn->bhqk", q_n, k_n,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhqr,bkr->bhqk", q_r, k_r,
                          preferred_element_type=jnp.float32)) * scale
        seq = s.shape[-1]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkv->bhqv", p.astype(v.dtype), v)

    def _absorbed(self, q_n, q_r, c, k_r, w_kvb, scale, cache):
        """One packed step through the latent pool: append ``[c | k_r]``
        and attend with the absorbed queries."""
        rank = self.kv_lora_rank
        width = cache.k_pool.shape[-1]
        if width != latent_row_width(rank, self.rope_dim):
            raise ValueError(
                f"latent pool rows of {width} lanes; this layer keeps "
                f"{latent_row_width(rank, self.rope_dim)}")
        pad = width - rank - self.rope_dim
        q_c = jnp.einsum("bhsn,chn->bhsc", q_n,
                         w_kvb[..., :self.nope_dim]).astype(self.dtype)

        def row(*parts):
            lead = parts[0].shape[:-1]
            return jnp.concatenate(
                [*parts, jnp.zeros((*lead, pad), self.dtype)], axis=-1)

        cache = ragged_paged_append(cache, row(c[:, None], k_r))
        o_c = ragged_paged_attention(row(q_c, q_r), cache, scale=scale,
                                     value_dim=rank)
        out = jnp.einsum("bhsc,chv->bhsv", o_c.astype(self.dtype),
                         w_kvb[..., self.nope_dim:])
        return out.astype(self.dtype), cache
