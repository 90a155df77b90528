"""Transformer block and tiny decoder LM around the attention kernels.

The flagship end-to-end model: pre-norm decoder blocks whose attention is
this framework's GQA layer.  Exists so the framework has a real model
family to (a) run the fused kernel inside, (b) train under dp/sp/tp mesh
shardings, and (c) serve as the `__graft_entry__` forward step.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from attention_tpu.models.attention_layer import (
    GQASelfAttention,
    KVCache,
    RollingKVCache,
)
from attention_tpu.models.moe import MoEMLP


class MLP(nn.Module):
    hidden_mult: int = 4
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        h = nn.Dense(d * self.hidden_mult, use_bias=False, dtype=self.dtype)(x)
        h = nn.gelu(h)
        return nn.Dense(d, use_bias=False, dtype=self.dtype)(h)


class TransformerBlock(nn.Module):
    num_q_heads: int
    num_kv_heads: int
    head_dim: int
    impl: str = "flash"
    causal: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    window: int | None = None
    attn_sinks: int = 0
    rope: bool = False
    rope_theta: float = 10000.0
    softcap: float | None = None
    moe_experts: int | None = None  # None = dense MLP
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    ep_axis: str | None = None
    cp_axis: str | None = None  # context-parallel attention (needs mesh)
    cp_impl: str = "allgather"  # "ring"/"zigzag" (O(n/R) KV) or "ulysses"
    tp_axis: str | None = None  # head-sharded serving on cached paths
    mesh: "jax.sharding.Mesh | None" = None

    @nn.compact
    def __call__(self, x, cache=None):
        y = nn.RMSNorm(dtype=self.dtype)(x)
        attn_out = GQASelfAttention(
            num_q_heads=self.num_q_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            impl=self.impl,
            causal=self.causal,
            dtype=self.dtype,
            window=self.window,
            attn_sinks=self.attn_sinks,
            rope=self.rope,
            rope_theta=self.rope_theta,
            softcap=self.softcap,
            cp_axis=self.cp_axis,
            cp_impl=self.cp_impl,
            tp_axis=self.tp_axis,
            mesh=self.mesh,
        )(y, cache)
        if cache is not None:
            attn_out, cache = attn_out
        x = x + attn_out
        y = nn.RMSNorm(dtype=self.dtype)(x)
        if self.moe_experts:
            mlp_out = MoEMLP(
                num_experts=self.moe_experts,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                ep_axis=self.ep_axis,
                dtype=self.dtype,
            )(y)
        else:
            mlp_out = MLP(dtype=self.dtype)(y)
        x = x + mlp_out
        return x if cache is None else (x, cache)


class TinyDecoder(nn.Module):
    """Decoder-only LM: embed -> N blocks -> norm -> logits.

    ``remat=True`` rematerializes each block's activations in the
    backward pass (`jax.checkpoint` via `nn.remat`) — the HBM-for-FLOPs
    trade that lets long-sequence training fit; ignored on the cached
    decode path (no backward there).

    ``logit_rows`` (int32 indices into the token axis) keeps only those
    positions ahead of the final norm and the head, which act row by
    row: the result is ``(B, len(logit_rows), vocab)``, each row the
    same numbers as in the whole projection.  ``None`` projects every
    position (training, ``generate*``); the parameters are the same.
    """

    vocab: int = 256
    dim: int = 256
    depth: int = 2
    num_q_heads: int = 8
    num_kv_heads: int = 2
    impl: str = "flash"
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    window: int | None = None  # sliding-window attention in every block
    attn_sinks: int = 0  # StreamingLLM sinks (requires window)
    rope: bool = False  # rotary position embeddings in every block
    rope_theta: float = 10000.0
    softcap: float | None = None  # attention logit soft-capping
    moe_experts: int | None = None  # MoE MLP in every block (None = dense)
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    ep_axis: str | None = None  # mesh axis experts shard over
    # Context-parallel training: run batch attention as the flash custom
    # VJP composed under shard_map over ``cp_axis`` of ``mesh`` (see
    # `parallel.cp`).  This is what makes the SHARDED train step execute
    # the framework's own kernels rather than XLA's auto-SPMD einsums.
    cp_axis: str | None = None
    cp_impl: str = "allgather"  # or "ring"/"zigzag"/"ulysses"
    # Tensor-parallel serving: every cached-path kernel call (decode on
    # any cache type, chunked prefill) runs head-sharded over
    # ``tp_axis`` via `parallel.serving`, with the projections left to
    # XLA auto-SPMD — generate()/generate_ragged()/... then serve
    # tensor-parallel with the framework's own kernels.
    tp_axis: str | None = None
    mesh: "jax.sharding.Mesh | None" = None

    @nn.compact
    def __call__(self, tokens: jax.Array, caches=None,
                 return_hidden: bool = False,
                 logit_rows: jax.Array | None = None):  # (B, S) int32
        head_dim = self.dim // self.num_q_heads
        x = nn.Embed(self.vocab, self.dim, dtype=self.dtype)(tokens)
        new_caches = []
        block_cls = (
            nn.remat(TransformerBlock)
            if self.remat and caches is None
            else TransformerBlock
        )
        for i in range(self.depth):
            # explicit name: keeps the param tree identical whether or
            # not the block class is wrapped in nn.remat
            block = block_cls(
                num_q_heads=self.num_q_heads,
                num_kv_heads=self.num_kv_heads,
                head_dim=head_dim,
                impl=self.impl,
                dtype=self.dtype,
                window=self.window,
                attn_sinks=self.attn_sinks,
                rope=self.rope,
                rope_theta=self.rope_theta,
                softcap=self.softcap,
                moe_experts=self.moe_experts,
                moe_top_k=self.moe_top_k,
                moe_capacity_factor=self.moe_capacity_factor,
                ep_axis=self.ep_axis,
                cp_axis=self.cp_axis,
                cp_impl=self.cp_impl,
                tp_axis=self.tp_axis,
                mesh=self.mesh,
                name=f"TransformerBlock_{i}",
            )
            if caches is None:
                x = block(x)
            else:
                x, c = block(x, caches[i])
                new_caches.append(c)
        if logit_rows is not None:
            x = jnp.take(x, logit_rows, axis=1)
        x = nn.RMSNorm(dtype=self.dtype)(x)
        if return_hidden:
            # pre-head activations for memory-bounded losses (chunked
            # cross-entropy re-projects per chunk instead of
            # materializing the (B, S, vocab) logits); the head params
            # still initialize below so the tree is call-invariant
            hidden = x
        logits = nn.Dense(self.vocab, use_bias=False, dtype=jnp.float32)(x)
        if return_hidden:
            return hidden if caches is None else (hidden, tuple(new_caches))
        return logits if caches is None else (logits, tuple(new_caches))

    def init_caches(self, batch: int, capacity: int,
                    cache_dtype=None, rolling: bool = False) -> tuple:
        """Fresh per-layer KV caches for autoregressive decoding.

        ``rolling=True`` (windowed models only) returns ring-buffer
        caches whose memory is bounded by the window, not by
        ``capacity``/sequence length."""
        head_dim = self.dim // self.num_q_heads
        if rolling:
            if self.window is None:
                raise ValueError("rolling caches require a windowed model")
            return tuple(
                RollingKVCache.create(batch, self.num_kv_heads,
                                      self.window, head_dim,
                                      cache_dtype or self.dtype,
                                      sinks=self.attn_sinks)
                for _ in range(self.depth)
            )
        return tuple(
            KVCache.create(batch, self.num_kv_heads, capacity, head_dim,
                           cache_dtype or self.dtype)
            for _ in range(self.depth)
        )
