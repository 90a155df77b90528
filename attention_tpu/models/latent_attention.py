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
where the configuration scales its normalised latents
(``scale_latents``: LongCat's ``mla_scale_*`` keys), and 1 where it
does not (DeepSeek's: the normalised latents go on as they are);
``k_r`` is never scaled.  ``rope_scaling`` stretches the rotary
frequencies by YaRN (`ops.rope`), and ``softmax_mscale`` = ``m`` of
`ops.rope.yarn_mscale` multiplies the softmax scale by ``m^2``.

A SELECTOR (``index_topk`` > 0: DeepSeek's lightning indexer) chooses
the keys a query attends.  It shares the query latent and keeps ONE
key of its own a token:

    q^I_j = (c_q W^I_q)_j                 index_heads x index_dim
    k^I   = LayerNorm(x W^I_k)            ONE key, scale and bias
    rope on the first rope_dim lanes of both (MLA's frequencies)
    w     = x W^I_w * index_heads^-0.5 * index_dim^-0.5
    I[t, s] = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)        s <= t
    S_t   = the min(index_topk, t + 1) keys of largest I[t, .]

and the softmax of query ``t`` runs over ``S_t`` alone (ties to the
lower position; `ops.sparse_index`).  What a token leaves behind is
then ``[c | k_r]`` AND ``k^I``: a latent pool and an index pool under
one page table (`RaggedPagedStep.index_pool`).  The layer sows the
(query token, key) pairs its mask let through into
``attention_stats`` / ``keys_attended``.

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
from attention_tpu.ops.rope import YarnScaling, apply_rope
from attention_tpu.ops.sparse_index import select_keys

#: the lanes a cached row is padded to: a row is copied and sliced in
#: whole vector registers
_LANES = 128


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of a token's row in the latent pool: ``[c | k_r]``, padded
    with zeros to whole registers (576 -> 640)."""
    return -(-(kv_lora_rank + rope_dim) // _LANES) * _LANES


def index_row_width(index_dim: int) -> int:
    """Lanes of a token's row in the index pool: the selector's key,
    padded with zeros to whole registers."""
    return -(-index_dim // _LANES) * _LANES


def chosen_keys(scores: jax.Array, top_k: int) -> jax.Array:
    """The selector's rule on a whole sequence: ``scores`` (..., S, S)
    -> bool, True where query ``t`` attends key ``s``: the ``min(top_k,
    t + 1)`` causal keys of largest score, ties to the lower
    position."""
    seq = scores.shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    _, best = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                            min(top_k, seq))
    kept = jnp.any(best[..., None] == jnp.arange(seq), axis=-2)
    return kept & causal


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
    scale_latents: bool = True
    rope_scaling: YarnScaling | None = None
    softmax_mscale: float = 1.0
    index_heads: int = 0      # the selector: heads, key width, keys kept
    index_dim: int = 0
    index_topk: int = 0

    def _rope(self, x, pos):
        return apply_rope(x, pos, self.rope_theta, self.rope_scaling)

    def _selector(self, x, c_q, pos):
        """``(q^I (B, S, H_i, d_i), k^I (B, S, d_i), w (B, S, H_i))``."""
        heads, width, rot = self.index_heads, self.index_dim, self.rope_dim
        q = nn.Dense(heads * width, use_bias=False, dtype=self.dtype,
                     name="index_q_proj")(c_q)
        q = q.reshape(*c_q.shape[:2], heads, width)
        k = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype,
                         name="index_k_norm")(
            nn.Dense(width, use_bias=False, dtype=self.dtype,
                     name="index_k_proj")(x))
        q = jnp.concatenate(
            [self._rope(q[..., :rot].swapaxes(1, 2), pos).swapaxes(1, 2),
             q[..., rot:]], axis=-1)
        k = jnp.concatenate(
            [self._rope(k[:, None, :, :rot], pos)[:, 0], k[..., rot:]],
            axis=-1)
        w = nn.Dense(heads, use_bias=False, dtype=self.dtype,
                     name="index_w_proj")(x)
        return q, k, w.astype(jnp.float32) * (heads * width) ** -0.5

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
        if self.scale_latents:
            c_q = c_q * jnp.asarray((dim / self.q_lora_rank) ** 0.5,
                                    self.dtype)
        q = dense("q_b_proj", heads * (nope + rot))(c_q)
        q = q.reshape(batch, seq, heads, nope + rot).transpose(0, 2, 1, 3)
        ckv = dense("kv_a_proj", rank + rot)(x)
        c = norm("kv_a_norm")(ckv[..., :rank])
        if self.scale_latents:
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
        q_r = self._rope(q[..., nope:], pos)
        k_r = self._rope(ckv[:, None, :, rank:], pos)
        scale = (nope + rot) ** -0.5 * self.softmax_mscale ** 2
        index = self._selector(x, c_q, pos) if self.index_topk else None
        if cache is None:
            out = self._expanded(q_n, q_r, c, k_r[:, 0], w_kvb, scale,
                                 index)
        else:
            out, cache = self._absorbed(q_n, q_r, c, k_r, w_kvb, scale,
                                        cache, index)
        out = out.transpose(0, 2, 1, 3).reshape(batch, seq, -1)
        out = dense("o_proj", dim)(out.astype(self.dtype))
        return out if cache is None else (out, cache)

    def _expanded(self, q_n, q_r, c, k_r, w_kvb, scale, index):
        """The published form: per-head keys and values from the
        latent, causal softmax in float32 over the keys the selector
        chose (every key without one)."""
        kv = jnp.einsum("bsc,chn->bhsn", c, w_kvb)
        k_n, v = kv[..., :self.nope_dim], kv[..., self.nope_dim:]
        s = (jnp.einsum("bhqn,bhkn->bhqk", q_n, k_n,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhqr,bkr->bhqk", q_r, k_r,
                          preferred_element_type=jnp.float32)) * scale
        seq = s.shape[-1]
        attends = jnp.tril(jnp.ones((seq, seq), bool))
        if index is not None:
            q_i, k_i, w_i = index
            each = jax.nn.relu(jnp.einsum(
                "bqhd,bkd->bhqk", q_i, k_i,
                preferred_element_type=jnp.float32))
            attends = chosen_keys(jnp.einsum("bhqk,bqh->bqk", each, w_i),
                                  self.index_topk)[:, None]
            self.sow("attention_stats", "keys_attended",
                     jnp.sum(attends).astype(jnp.int32))
        p = jax.nn.softmax(jnp.where(attends, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkv->bhqv", p.astype(v.dtype), v)

    def _absorbed(self, q_n, q_r, c, k_r, w_kvb, scale, cache, index):
        """One packed step through the latent pool: append ``[c | k_r]``
        (and the selector's key beside it) and attend, with the
        absorbed queries, the keys the selector chose."""
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

        if index is None:
            cache = ragged_paged_append(cache, row(c[:, None], k_r))
            o_c = ragged_paged_attention(row(q_c, q_r), cache, scale=scale,
                                         value_dim=rank)
        else:
            q_i, k_i, w_i = index
            lanes = cache.index_pool.shape[-1] - self.index_dim
            if lanes != index_row_width(self.index_dim) - self.index_dim:
                raise ValueError(
                    f"index pool rows of {cache.index_pool.shape[-1]} "
                    f"lanes; this layer keeps "
                    f"{index_row_width(self.index_dim)}")
            q_i, k_i = (jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, lanes)])
                        for a in (q_i, k_i))
            cache = ragged_paged_append(cache, row(c[:, None], k_r),
                                        index_new=k_i[:, None])
            select = select_keys(q_i[0], w_i[0], cache,
                                 top_k=self.index_topk,
                                 group=self.num_heads)
            o_c, attended = ragged_paged_attention(
                row(q_c, q_r), cache, scale=scale, value_dim=rank,
                select=select)
            self.sow("attention_stats", "keys_attended", attended)
        out = jnp.einsum("bhsc,chv->bhsv", o_c.astype(self.dtype),
                         w_kvb[..., self.nope_dim:])
        return out.astype(self.dtype), cache
