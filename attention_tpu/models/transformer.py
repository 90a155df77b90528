"""Transformer block and tiny decoder LM around the attention kernels.

The flagship end-to-end model: pre-norm decoder blocks whose attention is
this framework's GQA layer.  Exists so the framework has a real model
family to (a) run the fused kernel inside, (b) train under dp/sp/tp mesh
shardings, and (c) serve as the `__graft_entry__` forward step.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from attention_tpu.models.attention_layer import (
    GQASelfAttention,
    KVCache,
    RollingKVCache,
)
from attention_tpu.models.latent_attention import (
    LatentAttention,
    index_row_width,
    latent_row_width,
)
from attention_tpu.models.linear_attention import GatedDeltaNet
from attention_tpu.models.mamba import Mamba2Mixer
from attention_tpu.models.moe import (
    GatedExperts,
    LatentExperts,
    MoEMLP,
    PackedTokens,
)

#: the kinds of layer a decoder can have (``layer_types``).  The first
#: two are blocks of TWO sublayers, a mixer (attention or Gated
#: DeltaNet) and an MLP, each under its own residual
FULL_ATTENTION = "full_attention"
LINEAR_ATTENTION = "linear_attention"
#: attention behind the model's sliding ``window``.  In a model that
#: has such layers the others (``full_attention``) attend every key,
#: and the two kinds keep their pages in page spaces of their own
#: (`TinyDecoder.window_layers`)
SLIDING_ATTENTION = "sliding_attention"
#: the rest are blocks of ONE sublayer, ``x + f(norm(x))``: attention
#: alone, a Mamba-2 state-space mixer, or latent sparse experts
#: (`LatentExperts`)
ATTENTION = "attention"
STATE_SPACE = "state_space"
SPARSE_EXPERTS = "sparse_experts"
SUBLAYER_KINDS = (ATTENTION, STATE_SPACE, SPARSE_EXPERTS)
#: a DOUBLE layer (`ShortcutExpertsBlock`): two latent-attention and
#: two dense feed-forward sublayers, and one expert branch that reads
#: the first half and lands after the second
SHORTCUT_EXPERTS = "shortcut_experts"
#: blocks of TWO sublayers whose mixer is latent attention behind a
#: selector of keys (`LatentBlock`): the feed-forward is dense, or
#: gated experts beside a shared expert
LATENT_DENSE = "latent_dense"
LATENT_EXPERTS = "latent_experts"
LATENT_KINDS = (LATENT_DENSE, LATENT_EXPERTS)
LAYER_KINDS = ((FULL_ATTENTION, LINEAR_ATTENTION) + SUBLAYER_KINDS
               + (SHORTCUT_EXPERTS,) + LATENT_KINDS + (SLIDING_ATTENTION,))

_ACTIVATIONS = {"gelu": nn.gelu, "silu": nn.silu}


class MLP(nn.Module):
    hidden_mult: int = 4
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        h = nn.Dense(d * self.hidden_mult, use_bias=False, dtype=self.dtype)(x)
        h = nn.gelu(h)
        return nn.Dense(d, use_bias=False, dtype=self.dtype)(h)


class GatedMLP(nn.Module):
    """``down(act(gate x) * up x)`` at a free width (SwiGLU with
    ``act="silu"``)."""

    hidden: int
    act: str = "silu"
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        h = (_ACTIVATIONS[self.act](dense("gate_proj", self.hidden)(x))
             * dense("up_proj", self.hidden)(x))
        return dense("down_proj", x.shape[-1])(h)


def expert_feed_forward(y, packed, *, dtype, experts: int, experts_held: int,
                        experts_share: int = 0, experts_top_k: int = 1,
                        experts_hidden: int = 0, experts_scale: float = 1.0,
                        experts_groups: int = 1,
                        experts_top_groups: int = 1):
    """`GatedExperts` behind the sigmoid router (limited to
    ``experts_top_groups`` of ``experts_groups`` groups; one group: no
    limit) plus ONE shared expert of the experts' width, which every
    token takes and every chip computes whole.  Called inside a
    block's ``__call__``: the two submodules are the block's own,
    ``experts`` and ``shared_expert``.  ``packed`` names the pads of a
    packed engine step (None: no cache)."""
    out = GatedExperts(
        num_experts=experts, held=experts_held, share=experts_share,
        top_k=experts_top_k, hidden=experts_hidden, scale=experts_scale,
        router="sigmoid", groups=experts_groups,
        top_groups=experts_top_groups, dtype=dtype, name="experts")(
            y, packed)
    return out + GatedMLP(hidden=experts_hidden, dtype=dtype,
                          name="shared_expert")(y)


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
    # what follows is off in the plain block; a decoder sets it per layer
    kind: str = FULL_ATTENTION  # the mixer: attention or GatedDeltaNet
    qk_norm: bool = False     # RMSNorm over the whole q / k projections
    post_norm: bool = False   # x + norm(f(x)) in place of x + f(norm(x))
    mlp_hidden: int | None = None  # gated MLP of this width (None: MLP)
    mlp_act: str = "silu"
    linear_heads: int = 0     # GatedDeltaNet's heads and head sizes
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    linear_neg_eigval: bool = True
    head_norm: bool = False   # RMSNorm over each head of q and k
    attn_gate: bool = False   # sigmoid output gate on the attention
    # a norm before AND after each sublayer, x + norm(f(norm(x))):
    # four norms a block
    sandwich_norm: bool = False
    norm_eps: float = 1e-6
    # `expert_feed_forward`'s fields by name, as a tuple of pairs so
    # that the module hashes: the feed-forward is gated experts beside
    # a shared expert (empty: the dense MLP above)
    experts: tuple[tuple[str, Any], ...] = ()

    def _mixer(self):
        if self.kind == LINEAR_ATTENTION:
            return GatedDeltaNet(
                num_heads=self.linear_heads, key_dim=self.linear_key_dim,
                value_dim=self.linear_value_dim,
                conv_width=self.linear_conv,
                neg_eigval=self.linear_neg_eigval, dtype=self.dtype)
        if self.kind not in (FULL_ATTENTION, SLIDING_ATTENTION):
            raise ValueError(
                f"unknown layer kind {self.kind!r}; one of {LAYER_KINDS}")
        return GQASelfAttention(
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
            qk_norm=self.qk_norm,
            head_norm=self.head_norm,
            gate=self.attn_gate,
            norm_eps=self.norm_eps,
            cp_axis=self.cp_axis,
            cp_impl=self.cp_impl,
            tp_axis=self.tp_axis,
            mesh=self.mesh,
        )

    @nn.compact
    def __call__(self, x, cache=None):
        def norm(t):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype)(t)

        before = self.sandwich_norm or not self.post_norm
        after = self.sandwich_norm or self.post_norm
        attn_out = self._mixer()(norm(x) if before else x, cache)
        if cache is not None:
            attn_out, cache = attn_out
        x = x + (norm(attn_out) if after else attn_out)
        y = norm(x) if before else x
        if self.experts:
            # a packed engine step names its pads; a dense cache has none
            slot = getattr(cache, "token_slot", None)
            mlp_out = expert_feed_forward(
                y, None if slot is None else PackedTokens(slot),
                dtype=self.dtype, **dict(self.experts))
        elif self.mlp_hidden is not None:
            mlp_out = GatedMLP(hidden=self.mlp_hidden, act=self.mlp_act,
                               dtype=self.dtype)(y)
        elif self.moe_experts:
            mlp_out = MoEMLP(
                num_experts=self.moe_experts,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                ep_axis=self.ep_axis,
                dtype=self.dtype,
            )(y)
        else:
            mlp_out = MLP(dtype=self.dtype)(y)
        x = x + (norm(mlp_out) if after else mlp_out)
        return x if cache is None else (x, cache)


class SublayerBlock(nn.Module):
    """A block of one sublayer under one residual, ``x + f(norm(x))``,
    ``f`` by ``kind`` (`SUBLAYER_KINDS`).  With a cache (a packed
    engine step) it returns ``(x, cache)``: K / V pools for attention,
    state pools for the state-space mixer, and for the experts, which
    keep nothing, the cache as it came."""

    kind: str
    num_q_heads: int
    num_kv_heads: int
    head_dim: int
    impl: str = "flash"
    dtype: jnp.dtype = jnp.bfloat16
    rope: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    ssm_heads: int = 0        # the state-space mixer's sizes
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    experts: int = 0          # the sparse layer: routed over, held, share
    experts_held: int = 0
    experts_share: int = 0
    experts_top_k: int = 1
    experts_latent: int = 0
    experts_hidden: int = 0
    experts_shared_hidden: int = 0
    experts_scale: float = 1.0

    @nn.compact
    def __call__(self, x, cache=None):
        y = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype)(x)
        if self.kind == ATTENTION:
            out = GQASelfAttention(
                num_q_heads=self.num_q_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, impl=self.impl, dtype=self.dtype,
                rope=self.rope, rope_theta=self.rope_theta)(y, cache)
        elif self.kind == STATE_SPACE:
            out = Mamba2Mixer(
                num_heads=self.ssm_heads, head_dim=self.ssm_head_dim,
                state_dim=self.ssm_state, num_groups=self.ssm_groups,
                conv_width=self.ssm_conv,
                norm_eps=self.norm_eps, dtype=self.dtype)(y, cache)
        elif self.kind == SPARSE_EXPERTS:
            out = LatentExperts(
                num_experts=self.experts, held=self.experts_held,
                share=self.experts_share, top_k=self.experts_top_k,
                latent=self.experts_latent, hidden=self.experts_hidden,
                shared_hidden=self.experts_shared_hidden,
                scale=self.experts_scale, dtype=self.dtype)(y, cache)
        else:
            raise ValueError(f"unknown sublayer kind {self.kind!r}; one of "
                             f"{SUBLAYER_KINDS}")
        if cache is not None and self.kind in (ATTENTION, STATE_SPACE):
            out, cache = out
        return x + out if cache is None else (x + out, cache)


class ShortcutExpertsBlock(nn.Module):
    """A double layer with a shortcut-connected expert branch:

        for i in (0, 1):
            x = x + LatentAttention_i(norm(x))
            y = norm(x)
            if i == 0: m = GatedExperts(y)       # reads the FIRST half
            x = x + GatedMLP_i(y)
        x = x + m                                # lands after the SECOND

    Four norms, separate parameters.  In a deployment the experts'
    exchange runs beside the second attention and feed-forward; here
    the branch is computed where it is read.  With a cache (a packed
    engine step) ``cache`` is the two attention sublayers' latent
    pools' steps, and the block returns ``(x, (step_0, step_1))``."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    mlp_hidden: int
    experts: int              # real experts routed over, held, share
    experts_held: int
    experts_share: int = 0
    experts_zero: int = 0
    experts_top_k: int = 1
    experts_hidden: int = 0
    experts_scale: float = 1.0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, cache=None):
        def norm(name):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              name=name)

        steps = []
        for i in (0, 1):
            attn = LatentAttention(
                num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank, nope_dim=self.nope_dim,
                rope_dim=self.rope_dim, v_dim=self.v_dim,
                rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                dtype=self.dtype, name=f"attn_{i}")(
                    norm(f"attn_norm_{i}")(x),
                    None if cache is None else cache[i])
            if cache is not None:
                attn, step = attn
                steps.append(step)
            x = x + attn
            y = norm(f"mlp_norm_{i}")(x)
            if i == 0:
                branch = GatedExperts(
                    num_experts=self.experts, held=self.experts_held,
                    share=self.experts_share,
                    zero_experts=self.experts_zero,
                    top_k=self.experts_top_k, hidden=self.experts_hidden,
                    scale=self.experts_scale, dtype=self.dtype,
                    name="experts")(
                        y, None if cache is None
                        else PackedTokens(cache[0].token_slot))
            x = x + GatedMLP(hidden=self.mlp_hidden, dtype=self.dtype,
                             name=f"mlp_{i}")(y)
        x = x + branch
        return x if cache is None else (x, tuple(steps))


class LatentBlock(nn.Module):
    """A pre-norm block of two sublayers around latent attention:

        x = x + LatentAttention(norm(x))     behind its selector of keys
        x = x + F(norm(x))

    ``F`` is a dense SwiGLU of ``mlp_hidden`` (``sparse`` false: a
    leading dense layer) or `expert_feed_forward`: `GatedExperts`
    behind the group-limited sigmoid router plus ONE shared expert.
    With a cache (a
    packed engine step of a latent pool and an index pool) it returns
    ``(x, step)``."""

    sparse: bool
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    mlp_hidden: int
    experts: int = 0          # routed over, held, share
    experts_held: int = 0
    experts_share: int = 0
    experts_top_k: int = 1
    experts_hidden: int = 0
    experts_scale: float = 1.0
    experts_groups: int = 1
    experts_top_groups: int = 1
    rope_theta: float = 10000.0
    rope_scaling: Any = None
    softmax_mscale: float = 1.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, cache=None):
        def norm(name):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              name=name)

        attn = LatentAttention(
            num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, nope_dim=self.nope_dim,
            rope_dim=self.rope_dim, v_dim=self.v_dim,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps,
            dtype=self.dtype, scale_latents=False,
            rope_scaling=self.rope_scaling,
            softmax_mscale=self.softmax_mscale,
            index_heads=self.index_heads, index_dim=self.index_dim,
            index_topk=self.index_topk, name="attn")(
                norm("attn_norm")(x), cache)
        if cache is not None:
            attn, cache = attn
        x = x + attn
        y = norm("mlp_norm")(x)
        if self.sparse:
            out = expert_feed_forward(
                y, None if cache is None else PackedTokens(cache.token_slot),
                dtype=self.dtype, experts=self.experts,
                experts_held=self.experts_held,
                experts_share=self.experts_share,
                experts_top_k=self.experts_top_k,
                experts_hidden=self.experts_hidden,
                experts_scale=self.experts_scale,
                experts_groups=self.experts_groups,
                experts_top_groups=self.experts_top_groups)
        else:
            out = GatedMLP(hidden=self.mlp_hidden, dtype=self.dtype,
                           name="mlp")(y)
        x = x + out
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
    # One kind per layer (`LAYER_KINDS`); None: attention in every
    # block.  A linear-attention layer is a `GatedDeltaNet` of
    # ``linear_heads`` heads, keys of ``linear_key_dim`` and values of
    # ``linear_value_dim``; it keeps a recurrent state per request in
    # place of K and V rows (`recurrent_state_shapes`), and so does a
    # state-space layer (`SublayerBlock`, which reads ``sublayer``: its
    # fields by name, as a tuple of pairs so that the module hashes;
    # a double layer, `ShortcutExpertsBlock`, reads its own the same
    # way).
    layer_types: tuple[str, ...] | None = None
    sublayer: tuple[tuple[str, Any], ...] = ()
    norm_eps: float = 1e-6    # the final norm's, and a SublayerBlock's
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    linear_neg_eigval: bool = True
    qk_norm: bool = False     # RMSNorm on the whole q / k projections
    post_norm: bool = False   # x + norm(f(x)): the OLMo 2 block
    mlp_hidden: int | None = None  # gated MLP of this width (None: 4x gelu)
    mlp_act: str = "silu"
    # Attention layers that DIFFER inside one model: a
    # ``sliding_attention`` layer attends behind ``window`` and rotates
    # by ``rope``; beside such layers a ``full_attention`` layer has no
    # window, and rotates only where ``global_rope`` says so (false:
    # NoPE).  A model of ``full_attention`` layers alone gives every one
    # ``window`` and ``rope``, as before.
    global_rope: bool = True
    head_dim: int | None = None    # a head's size (None: dim / heads)
    head_norm: bool = False   # RMSNorm over each head of q and k
    attn_gate: bool = False   # sigmoid output gate on the attention
    sandwich_norm: bool = False    # x + norm(f(norm(x))): four norms
    embed_scale: float = 1.0  # the embedding's rows times this
    # With ``num_dense_layers`` set, the two-sublayer blocks after
    # that many leading ones take `expert_feed_forward` (its fields in
    # ``sublayer``) in place of the dense MLP.
    num_dense_layers: int | None = None

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.num_q_heads

    @property
    def kinds(self) -> tuple[str, ...]:
        """The mixer kind of each layer."""
        if self.layer_types is None:
            return (FULL_ATTENTION,) * self.depth
        if len(self.layer_types) != self.depth:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"depth is {self.depth}")
        return tuple(self.layer_types)

    def _layers_of(self, *kinds: str) -> tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.kinds) if kind in kinds)

    @property
    def attention_layers(self) -> tuple[int, ...]:
        """Layers that keep K and V rows (paged KV pools)."""
        return self._layers_of(FULL_ATTENTION, ATTENTION, SLIDING_ATTENTION)

    @property
    def window_layers(self) -> tuple[int, ...]:
        """The sliding-window layers of a model that ALSO has attention
        layers without a window: the two kinds keep their pages in page
        spaces of their own, and a request holds of a window layer its
        trailing band and no more.  Empty for a model of one kind,
        sliding or not: one page space, as ever."""
        sliding = self._layers_of(SLIDING_ATTENTION)
        return sliding if len(sliding) < len(self.attention_layers) else ()

    def _beside_sliding(self, layer: int) -> bool:
        """Whether ``layer`` is a layer WITHOUT the window in a model
        that has sliding layers."""
        return (SLIDING_ATTENTION in self.kinds
                and self.kinds[layer] != SLIDING_ATTENTION)

    def layer_window(self, layer: int) -> int | None:
        """The window attention layer ``layer`` attends behind."""
        return None if self._beside_sliding(layer) else self.window

    def layer_rope(self, layer: int) -> bool:
        """Whether attention layer ``layer`` rotates q and k."""
        return self.rope and (self.global_rope
                              or not self._beside_sliding(layer))

    @property
    def recurrent_layers(self) -> tuple[int, ...]:
        """Layers that keep one state per request instead."""
        return self._layers_of(LINEAR_ATTENTION, STATE_SPACE)

    @property
    def latent_layers(self) -> tuple[int, ...]:
        """Double layers (`ShortcutExpertsBlock`): each keeps ONE
        latent pool for each of its two attention sublayers."""
        return self._layers_of(SHORTCUT_EXPERTS)

    @property
    def indexed_layers(self) -> tuple[int, ...]:
        """Layers whose latent attention chooses its keys
        (`LatentBlock`): each keeps a latent pool AND its selector's
        index pool, under one page table."""
        return self._layers_of(*LATENT_KINDS)

    @property
    def attention_sublayers(self) -> tuple[int, ...]:
        """The layer of every sublayer that keeps pages, in order: a
        double layer is named twice."""
        return tuple(sorted(self.attention_layers + 2 * self.latent_layers
                            + self.indexed_layers))

    def kv_pool_widths(self, layer: int | None = None
                       ) -> tuple[int, tuple[int, ...]]:
        """What attention sublayer ``layer`` (None: the model's first)
        keeps a token: the KV heads, and the row width of each pool: K
        and V of the head size; or the ONE latent pool's ``[c | k_r]``
        of ONE head (`latent_attention.latent_row_width`); or that
        latent pool and a selector's index pool beside it
        (`latent_attention.index_row_width`).  The step's counts take
        the first's for every sublayer; the pools are laid out a layer
        at a time (`cache_layout`)."""
        if layer is None:
            layer = self.attention_sublayers[0]
        kind = self.kinds[layer]
        f = dict(self.sublayer)
        if kind == SHORTCUT_EXPERTS:
            return 1, (latent_row_width(f["kv_lora_rank"], f["rope_dim"]),)
        if kind in LATENT_KINDS:
            return 1, (latent_row_width(f["kv_lora_rank"], f["rope_dim"]),
                       index_row_width(f["index_dim"]))
        # K and V of every KV head
        return self.num_kv_heads, (self.head_size,) * 2

    @property
    def expert_layers(self) -> tuple[int, ...]:
        """Layers with sparse experts: the experts keep nothing per
        request, and report their pairs (`LatentExperts`,
        `GatedExperts`)."""
        layers = self._layers_of(SPARSE_EXPERTS, SHORTCUT_EXPERTS,
                                 LATENT_EXPERTS)
        if self.num_dense_layers is not None:
            layers += tuple(i for i in self._layers_of(
                FULL_ATTENTION, SLIDING_ATTENTION, LINEAR_ATTENTION)
                if i >= self.num_dense_layers)
        return tuple(sorted(layers))

    @property
    def zero_experts(self) -> int:
        """Zero-compute experts each expert layer routes over beside
        the real ones; 0 for a model without them."""
        return dict(self.sublayer).get("experts_zero", 0)

    @property
    def held_experts(self) -> int:
        """Experts each expert layer holds here (its share of those it
        routes over); 0 for a model without expert layers."""
        return dict(self.sublayer).get("experts_held", 0)

    def recurrent_state_shapes(self, layer: int | None = None
                               ) -> tuple[tuple, tuple]:
        """Per request, of recurrent layer ``layer`` (None: the first):
        the float32 state (Gated DeltaNet: ``(heads, key_dim,
        value_dim)``; state-space: ``(heads, head_dim, state)``) and
        the convolution's tail ``(conv - 1, channels)`` in the model's
        dtype."""
        if layer is None:
            layer = self.recurrent_layers[0]
        if self.kinds[layer] == STATE_SPACE:
            f = dict(self.sublayer)
            h, p, n = f["ssm_heads"], f["ssm_head_dim"], f["ssm_state"]
            return (h, p, n), (f.get("ssm_conv", 4) - 1,
                               h * p + 2 * f.get("ssm_groups", 1) * n)
        h, dk, dv = (self.linear_heads, self.linear_key_dim,
                     self.linear_value_dim)
        return (h, dk, dv), (self.linear_conv - 1, h * (2 * dk + dv))

    @nn.compact
    def __call__(self, tokens: jax.Array, caches=None,
                 return_hidden: bool = False,
                 logit_rows: jax.Array | None = None):  # (B, S) int32
        head_dim = self.head_size
        # rows first, then the cast: `Embed(dtype=...)` would cast the
        # whole float32 table in every call and only then take the rows
        x = nn.Embed(self.vocab, self.dim)(tokens).astype(self.dtype)
        if self.embed_scale != 1.0:
            x = x * jnp.asarray(self.embed_scale, self.dtype)
        new_caches = []
        block_cls = (
            nn.remat(TransformerBlock)
            if self.remat and caches is None
            else TransformerBlock
        )
        for i, kind in enumerate(self.kinds):
            if kind == SHORTCUT_EXPERTS:
                block = ShortcutExpertsBlock(
                    num_heads=self.num_q_heads, dtype=self.dtype,
                    rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                    **dict(self.sublayer),
                    name=f"ShortcutExpertsBlock_{i}")
            elif kind in SUBLAYER_KINDS:
                block = SublayerBlock(
                    kind=kind, num_q_heads=self.num_q_heads,
                    num_kv_heads=self.num_kv_heads, head_dim=head_dim,
                    impl=self.impl, dtype=self.dtype, rope=self.rope,
                    rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                    **dict(self.sublayer), name=f"SublayerBlock_{i}")
            elif kind in LATENT_KINDS:
                block = LatentBlock(
                    sparse=kind == LATENT_EXPERTS,
                    num_heads=self.num_q_heads, dtype=self.dtype,
                    rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                    **dict(self.sublayer), name=f"LatentBlock_{i}")
            if kind in SUBLAYER_KINDS + (SHORTCUT_EXPERTS,) + LATENT_KINDS:
                if caches is None:
                    x = block(x)
                else:
                    x, c = block(x, caches[i])
                    new_caches.append(c)
                continue
            # explicit name: keeps the param tree identical whether or
            # not the block class is wrapped in nn.remat
            block = block_cls(
                num_q_heads=self.num_q_heads,
                num_kv_heads=self.num_kv_heads,
                head_dim=head_dim,
                impl=self.impl,
                dtype=self.dtype,
                window=self.layer_window(i),
                attn_sinks=self.attn_sinks,
                rope=self.layer_rope(i),
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
                kind=kind,
                qk_norm=self.qk_norm,
                post_norm=self.post_norm,
                mlp_hidden=self.mlp_hidden,
                mlp_act=self.mlp_act,
                linear_heads=self.linear_heads,
                linear_key_dim=self.linear_key_dim,
                linear_value_dim=self.linear_value_dim,
                linear_conv=self.linear_conv,
                linear_neg_eigval=self.linear_neg_eigval,
                head_norm=self.head_norm,
                attn_gate=self.attn_gate,
                sandwich_norm=self.sandwich_norm,
                norm_eps=self.norm_eps,
                experts=(self.sublayer if i in self.expert_layers else ()),
                name=f"TransformerBlock_{i}",
            )
            if caches is None:
                x = block(x)
            else:
                x, c = block(x, caches[i])
                new_caches.append(c)
        if logit_rows is not None:
            x = jnp.take(x, logit_rows, axis=1)
        x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype)(x)
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
        if (self.recurrent_layers or self.latent_layers
                or self.indexed_layers):
            raise ValueError(
                "a model with recurrent or latent-attention layers serves "
                "through the engine's packed step; it has no dense "
                "per-layer caches")
        head_dim = self.head_size
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

    def cache_layout(self):
        """What each layer keeps between the engine's steps: the one
        place that turns a layer's kind into its arrays, and the kinds
        into the typed refusal of features that carry K / V pages alone."""
        # here, not at the top: the engine imports this package
        from attention_tpu.engine import errors
        from attention_tpu.models import cache_layout as kept

        def layer_cache(layer, kind):
            if kind in (LINEAR_ATTENTION, STATE_SPACE):
                state, conv = self.recurrent_state_shapes(layer)
                return kept.LayerCache(kept.STATE_ROWS, (
                    (state, jnp.float32), (conv, self.dtype)), kept.state_step)
            if kind == SPARSE_EXPERTS:
                return None
            heads, widths = self.kv_pool_widths(layer)
            pools = tuple(((heads, kept.PAGE, w), None) for w in widths)
            if kind == SHORTCUT_EXPERTS:    # a pool a sublayer, two of them
                return kept.LayerCache(kept.PAGES, pools * 2,
                                       kept.latent_steps, kept.latents_kept)
            if kind in LATENT_KINDS:
                return kept.LayerCache(kept.PAGES, pools, kept.indexed_step,
                                       kept.indexed_kept)
            return kept.LayerCache(
                kept.WINDOW_PAGES if layer in self.window_layers
                else kept.PAGES, pools, kept.kv_step)

        name = type(self).__name__
        pair = f"carries a K and a V pool a layer, and {name} keeps "
        refusals = (
            (self.window_layers, errors.PageSpacesUnsupportedError,
             f"carries ONE list of page ids a request, and {name} keeps the "
             "pages of its sliding-window layers {} in a page space of "
             "their own"),
            (self.recurrent_layers, errors.RecurrentStateUnsupportedError,
             f"knows only KV pages, and {name} keeps a recurrent state per "
             "request in layers {}"),
            (self.latent_layers, errors.LatentCacheUnsupportedError, pair
             + "ONE latent pool for each attention sublayer of layers {}"),
            (self.indexed_layers, errors.LatentCacheUnsupportedError, pair
             + "a latent pool and a selector's index pool in layers {}"))
        return kept.CacheLayout(
            tuple(layer_cache(i, kind) for i, kind in enumerate(self.kinds)),
            shard_kv_heads=not (self.latent_layers or self.indexed_layers),
            pages_only_refusal=next(
                ((error, text.format(list(layers)))
                 for layers, error, text in refusals if layers), None))
