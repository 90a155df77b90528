"""A decoder from a published configuration's keys.

`decoder_from_config` reads a Hugging Face style ``config.json`` (the
keys as published, as `benchmark/configs/*.json` keeps them) and
returns the `TinyDecoder` that serves it.  Where the norms sit is not
a key of any published configuration, so the file states it beside its
``assumed`` list: ``post_norm`` (norm on each sublayer's output, OLMo
2 / 3) and ``qk_norm`` (RMSNorm on the q and k projections), both
false where absent, which is the pre-norm block of StarCoder2.
Nothing is decided from a model's name.

A configuration with ``hybrid_override_pattern`` is a stack of blocks
of ONE sublayer each, a letter a layer (`PATTERN_KINDS`): ``M`` a
Mamba-2 state-space mixer (``mamba_*``, ``ssm_state_size``,
``n_groups``, ``conv_kernel``, ``use_conv_bias``), ``E`` latent sparse
experts (``n_routed_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``moe_latent_size``,
``moe_shared_expert_intermediate_size``, ``routed_scaling_factor``,
``norm_topk_prob``), ``*`` attention.  What no configuration has asked
for yet is refused by the key's name: the letter ``-`` (a dense
feed-forward layer), a convolution without bias, router weights that
are not normalised, a group-limited router, a sliding window.  THE SHARE such a file states:
``n_routed_experts`` is the number of experts HELD HERE and
``expert_share`` = ``{"index": k, "of": n}`` says that they are share
``k`` of ``n`` equal shares, so the router is ``n_routed_experts * n``
wide; ``vocab_size`` is the slice of the vocabulary held here.  Absent,
the layer holds every expert.  ``attention_rotary`` (true where
absent) says whether the attention layers rotate q and k.

A configuration with ``attention_method`` is a stack of ``num_layers``
DOUBLE layers (`transformer.ShortcutExpertsBlock`): two latent
attention sublayers (``"MLA"``: ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``mla_scale_q_lora`` / ``mla_scale_kv_lora``, ``rope_theta``), two
dense gated feed-forwards (``ffn_hidden_size``) and ONE branch of
gated experts (``n_routed_experts`` and ``expert_share`` as above,
``expert_ffn_hidden_size``, ``moe_topk``, ``routed_scaling_factor``)
behind a softmax router that also routes over ``zero_expert_num``
zero-compute experts (``zero_expert_type``); the norms read
``rms_norm_eps``.  The decoder has ONE KV head, the latent, and every
query head is its group.  Refused by the key's name: an
``attention_method`` other than ``"MLA"``, ``mla_scale_*`` false or
absent, a ``zero_expert_type`` other than ``"identity"``,
``rope_scaling``, ``sliding_window``, ``norm_topk_prob`` true (the
softmax router's weights are not normalised), a group-limited router,
``n_shared_experts``.

A configuration with ``kv_lora_rank`` and no ``attention_method`` is a
stack of pre-norm blocks of TWO sublayers (`transformer.LatentBlock`):
latent attention (``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``; the
normalised latents NOT scaled) whose keys a selector chooses
(``index_n_heads``, ``index_head_dim``, ``index_topk``), rotary
frequencies stretched by ``rope_scaling`` of type ``yarn`` (``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``;
``mscale_all_dim`` into the softmax scale), then a feed-forward: dense
SwiGLU at ``intermediate_size`` in the first ``first_k_dense_replace``
layers, after them gated experts at ``moe_intermediate_size``
(``n_routed_experts`` and ``expert_share`` as above,
``num_experts_per_tok``, ``routed_scaling_factor``) behind a sigmoid
router limited to ``topk_group`` of ``n_group`` groups, plus ONE
shared expert (``n_shared_experts``); norms at ``rms_norm_eps``.  The
decoder has ONE KV head, the latent.  Refused by the key's name: a
``topk_method`` other than ``"noaux_tc"``, a ``scoring_func`` other
than ``"sigmoid"``, ``norm_topk_prob`` false, a ``rope_scaling.type``
other than ``"yarn"`` or one whose ``mscale`` and ``mscale_all_dim``
differ, ``moe_layer_freq`` other than 1, ``n_shared_experts`` other
than 1, a ``hidden_act`` other than ``"silu"``, ``attention_bias``,
``sliding_window``, and a configuration without ``index_topk`` (the
block is served with its selector).

A configuration whose ``layer_types`` hold ``sliding_attention`` and
which has ``num_experts`` is a stack of two-sublayer blocks whose
ATTENTION LAYERS DIFFER (`transformer.TransformerBlock`, kinds
``sliding_attention`` and ``full_attention``): a sliding layer attends
behind ``sliding_window`` and rotates q and k by ``rope_theta``, a full
layer attends every key and rotates only where the file states
``full_attention_rotary`` (false where absent: NoPE); heads of
``head_dim``, a key of its own (``hidden_size / num_attention_heads``
where absent); the feed-forward is dense SwiGLU at
``intermediate_size`` in the first ``num_dense_layers`` layers, after
them gated experts at ``moe_intermediate_size`` (``num_experts`` HELD
HERE and ``expert_share`` as above, ``num_experts_per_tok``,
``route_scale``) behind a sigmoid router whose chosen weights are
normalised (``route_norm``), plus ONE shared expert
(``num_shared_experts``); ``mup_enabled`` scales the embedding by
``sqrt(hidden_size)``; norms at ``rms_norm_eps``; an untied head.  What
no published key states the file states, each false where absent:
``qk_head_norm`` (RMSNorm over each head of q and k), ``attention_gate``
(a sigmoid output gate), ``sandwich_norm`` (a norm before AND after
each sublayer).  A file cut in depth may name the published layers it
serves, ``served_layers`` (indices into ``layer_types``, which stays
whole; absent: the first ``num_hidden_layers``).  The two kinds of
layer keep their pages in two page spaces (`engine.allocator`).
Refused by the key's name: ``n_group`` / ``topk_group`` /
``num_expert_groups`` / ``num_limited_groups`` other than 1, a
``score_func`` other than ``"sigmoid"``, ``route_norm`` false,
``rope_scaling``, ``tie_word_embeddings`` true, a ``hidden_act`` other
than ``"silu"``, ``num_shared_experts`` other than 1, and a
``layer_types`` of ``sliding_attention`` without ``sliding_window``.
"""

from __future__ import annotations

import jax.numpy as jnp

from attention_tpu.models.transformer import (
    ATTENTION,
    FULL_ATTENTION,
    LATENT_DENSE,
    LATENT_EXPERTS,
    LINEAR_ATTENTION,
    SHORTCUT_EXPERTS,
    SLIDING_ATTENTION,
    SPARSE_EXPERTS,
    STATE_SPACE,
    TinyDecoder,
)
from attention_tpu.ops.rope import YarnScaling, yarn_mscale

_GELU = ("gelu", "gelu_new", "gelu_pytorch_tanh")

#: the layer a letter of ``hybrid_override_pattern`` names
PATTERN_KINDS = {"M": STATE_SPACE, "E": SPARSE_EXPERTS, "*": ATTENTION}


def _common(config: dict, *, impl: str) -> dict:
    """The fields every decoder has, and the check of the head size."""
    dim = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    head_dim = config.get("head_dim")
    if head_dim is not None and dim // heads != int(head_dim):
        raise ValueError("head_dim: hidden_size / num_attention_heads in "
                         "every family but the one whose attention layers "
                         "differ (layer_types with sliding_attention)")
    theta = config.get("rope_theta")
    if theta is None:
        theta = (config.get("rope_parameters") or {}).get("rope_theta")
    rope = theta is not None and bool(config.get("attention_rotary", True))
    return dict(
        vocab=int(config["vocab_size"]), dim=dim,
        depth=int(config["num_hidden_layers"]), num_q_heads=heads,
        num_kv_heads=int(config.get("num_key_value_heads", heads)),
        impl=impl, dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        rope=rope, rope_theta=float(theta) if rope else 10000.0)


def _no_group_limit(config: dict) -> None:
    if (int(config.get("n_group", 1)), int(config.get("topk_group", 1))
            ) != (1, 1):
        raise ValueError("n_group / topk_group: the router has no "
                         "group limit")


def _sublayer_decoder(config: dict, *, impl: str) -> TinyDecoder:
    """The decoder of a ``hybrid_override_pattern``: see the module's
    docstring for the keys."""
    common = _common(config, impl=impl)
    pattern = config["hybrid_override_pattern"][:common["depth"]]
    unknown = sorted(set(pattern) - set(PATTERN_KINDS))
    if unknown or len(pattern) != common["depth"]:
        raise ValueError(
            f"hybrid_override_pattern must name {common['depth']} layers "
            f"by the letters {sorted(PATTERN_KINDS)}; it has "
            f"{len(pattern)} and cannot build {unknown}")
    kinds = tuple(PATTERN_KINDS[letter] for letter in pattern)
    if config.get("sliding_window") is not None:
        raise ValueError("sliding_window: the single-sublayer attention "
                         "layer is full attention")
    fields = {}
    if STATE_SPACE in kinds:
        if config.get("mamba_hidden_act", "silu") != "silu":
            raise ValueError("mamba_hidden_act: the state-space mixer "
                             "gates with silu")
        if not config["use_conv_bias"]:
            raise ValueError("use_conv_bias: the state-space mixer's "
                             "convolution carries a bias")
        fields.update(
            ssm_heads=int(config["mamba_num_heads"]),
            ssm_head_dim=int(config["mamba_head_dim"]),
            ssm_state=int(config["ssm_state_size"]),
            ssm_groups=int(config["n_groups"]),
            ssm_conv=int(config["conv_kernel"]))
    if SPARSE_EXPERTS in kinds:
        if config.get("mlp_hidden_act") != "relu2":
            raise ValueError(
                f"mlp_hidden_act {config.get('mlp_hidden_act')!r}: the "
                "experts are relu2")
        if not config["norm_topk_prob"]:
            raise ValueError("norm_topk_prob: the router normalises the "
                             "chosen experts' weights")
        _no_group_limit(config)
        share = config.get("expert_share") or {"index": 0, "of": 1}
        held = int(config["n_routed_experts"])
        fields.update(
            experts=held * int(share["of"]), experts_held=held,
            experts_share=int(share["index"]),
            experts_top_k=int(config["num_experts_per_tok"]),
            experts_latent=int(config["moe_latent_size"]),
            experts_hidden=int(config["moe_intermediate_size"]),
            experts_shared_hidden=int(
                config.get("n_shared_experts", 0)
                and config["moe_shared_expert_intermediate_size"]),
            experts_scale=float(config["routed_scaling_factor"]))
    return TinyDecoder(
        **common, layer_types=kinds, sublayer=tuple(sorted(fields.items())),
        norm_eps=float(config.get("norm_eps", 1e-6)))


def _latent_decoder(config: dict, *, impl: str) -> TinyDecoder:
    """The decoder of an ``attention_method``: double layers of latent
    attention with a shortcut-connected expert branch; see the
    module's docstring for the keys."""
    method = config["attention_method"]
    if method != "MLA":
        raise ValueError(f"attention_method {method!r}: the builder knows "
                         "\"MLA\"")
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora"):
        if not config.get(key, False):
            raise ValueError(f"{key}: the latent attention layer scales "
                             "both normalised latents")
    zero = int(config.get("zero_expert_num", 0))
    if zero and config.get("zero_expert_type") != "identity":
        raise ValueError(
            f"zero_expert_type {config.get('zero_expert_type')!r}: a "
            "zero-compute expert returns its input (\"identity\")")
    for key in ("rope_scaling", "sliding_window"):
        if config.get(key) is not None:
            raise ValueError(f"{key}: the latent attention layer has none")
    if config.get("norm_topk_prob"):
        raise ValueError("norm_topk_prob: the softmax router's weights "
                         "are not normalised over the chosen experts")
    _no_group_limit(config)
    if config.get("n_shared_experts"):
        raise ValueError("n_shared_experts: the double layer's dense "
                         "feed-forwards are its shared path")
    share = config.get("expert_share") or {"index": 0, "of": 1}
    held = int(config["n_routed_experts"])
    depth = int(config["num_layers"])
    fields = dict(
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        nope_dim=int(config["qk_nope_head_dim"]),
        rope_dim=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        mlp_hidden=int(config["ffn_hidden_size"]),
        experts=held * int(share["of"]), experts_held=held,
        experts_share=int(share["index"]), experts_zero=zero,
        experts_top_k=int(config["moe_topk"]),
        experts_hidden=int(config["expert_ffn_hidden_size"]),
        experts_scale=float(config.get("routed_scaling_factor", 1.0)))
    # one latent KV head a sublayer: every query head is its group
    return TinyDecoder(
        vocab=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        depth=depth, num_q_heads=int(config["num_attention_heads"]),
        num_kv_heads=1, impl=impl,
        dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        rope=True, rope_theta=float(config["rope_theta"]),
        layer_types=(SHORTCUT_EXPERTS,) * depth,
        sublayer=tuple(sorted(fields.items())),
        norm_eps=float(config.get("rms_norm_eps", 1e-6)))


def _yarn(config: dict) -> tuple[YarnScaling | None, float]:
    """``rope_scaling`` as the rotary frequencies' stretch and the
    factor ``m`` whose square multiplies the softmax scale."""
    scaling = config.get("rope_scaling")
    if scaling is None:
        return None, 1.0
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling.type {kind!r}: the builder knows "
                         "\"yarn\"")
    factor = float(scaling["factor"])
    all_dim = float(scaling.get("mscale_all_dim", 0))
    if float(scaling.get("mscale", 1)) != all_dim:
        raise ValueError("rope_scaling.mscale: cos and sin are not "
                         "scaled, so it has to equal mscale_all_dim")
    return YarnScaling(
        factor, int(scaling["original_max_position_embeddings"]),
        float(scaling.get("beta_fast", 32)),
        float(scaling.get("beta_slow", 1))), yarn_mscale(factor, all_dim)


def _indexed_latent_decoder(config: dict, *, impl: str) -> TinyDecoder:
    """The decoder of a ``kv_lora_rank`` without ``attention_method``:
    blocks of latent attention behind a selector of keys and a dense
    or expert feed-forward; see the module's docstring for the keys."""
    if "index_topk" not in config:
        raise ValueError("index_topk: the two-sublayer latent block is "
                         "served with its selector of keys")
    refusals = (
        ("topk_method", "noaux_tc", "the router's choice-only bias"),
        ("scoring_func", "sigmoid", "the group-limited router scores "
         "by sigmoid"),
        ("norm_topk_prob", True, "the sigmoid router normalises the "
         "chosen experts' weights"),
        ("moe_layer_freq", 1, "every layer after the leading dense ones "
         "is an expert layer"),
        ("n_shared_experts", 1, "an expert layer has ONE shared expert"),
        ("hidden_act", "silu", "the feed-forwards are SwiGLU"),
        ("attention_bias", False, "the projections carry no bias"),
        ("sliding_window", None, "the latent attention layer has none"),
    )
    for key, want, why in refusals:
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: {why}")
    scaling, mscale = _yarn(config)
    share = config.get("expert_share") or {"index": 0, "of": 1}
    held = int(config["n_routed_experts"])
    depth = int(config["num_hidden_layers"])
    dense = min(int(config.get("first_k_dense_replace", 0)), depth)
    fields = dict(
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        nope_dim=int(config["qk_nope_head_dim"]),
        rope_dim=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        index_heads=int(config["index_n_heads"]),
        index_dim=int(config["index_head_dim"]),
        index_topk=int(config["index_topk"]),
        rope_scaling=scaling, softmax_mscale=mscale,
        mlp_hidden=int(config["intermediate_size"]),
        experts=held * int(share["of"]), experts_held=held,
        experts_share=int(share["index"]),
        experts_top_k=int(config["num_experts_per_tok"]),
        experts_hidden=int(config["moe_intermediate_size"]),
        experts_scale=float(config.get("routed_scaling_factor", 1.0)),
        experts_groups=int(config.get("n_group", 1)),
        experts_top_groups=int(config.get("topk_group", 1)))
    # one latent KV head a layer: every query head is its group
    return TinyDecoder(
        vocab=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        depth=depth, num_q_heads=int(config["num_attention_heads"]),
        num_kv_heads=1, impl=impl,
        dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        rope=True, rope_theta=float(config["rope_theta"]),
        layer_types=((LATENT_DENSE,) * dense
                     + (LATENT_EXPERTS,) * (depth - dense)),
        sublayer=tuple(sorted(fields.items())),
        norm_eps=float(config.get("rms_norm_eps", 1e-6)))


def _mixed_window_decoder(config: dict, *, impl: str) -> TinyDecoder:
    """The decoder of a ``layer_types`` with ``sliding_attention`` and
    ``num_experts``: window and full attention layers in one model, a
    dense or expert feed-forward; see the module's docstring for the
    keys."""
    refusals = (
        ("n_group", 1, "the router has no group limit"),
        ("topk_group", 1, "the router has no group limit"),
        ("num_expert_groups", 1, "the router has no group limit"),
        ("num_limited_groups", 1, "the router has no group limit"),
        ("score_func", "sigmoid", "the router scores by sigmoid"),
        ("route_norm", True, "the sigmoid router normalises the chosen "
         "experts' weights"),
        ("rope_scaling", None, "the sliding layers rotate by rope_theta "
         "as it stands"),
        ("tie_word_embeddings", False, "the head is a matrix of its own"),
        ("hidden_act", "silu", "the feed-forwards are SwiGLU"),
        ("num_shared_experts", 1, "an expert layer has ONE shared expert"),
    )
    for key, want, why in refusals:
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: {why}")
    if config.get("sliding_window") is None:
        raise ValueError("sliding_window: a sliding_attention layer "
                         "attends behind one")
    dim, depth = int(config["hidden_size"]), int(config["num_hidden_layers"])
    heads = int(config["num_attention_heads"])
    published = config["layer_types"]
    served = config.get("served_layers", range(depth))
    kinds = tuple(published[i] for i in served)
    if len(kinds) != depth or set(kinds) - {FULL_ATTENTION,
                                            SLIDING_ATTENTION}:
        raise ValueError(
            f"layer_types / served_layers must name {depth} layers of "
            f"{(SLIDING_ATTENTION, FULL_ATTENTION)}; they name {kinds}")
    share = config.get("expert_share") or {"index": 0, "of": 1}
    held = int(config["num_experts"])
    fields = dict(
        experts=held * int(share["of"]), experts_held=held,
        experts_share=int(share["index"]),
        experts_top_k=int(config["num_experts_per_tok"]),
        experts_hidden=int(config["moe_intermediate_size"]),
        experts_scale=float(config.get("route_scale", 1.0)))
    head_dim = int(config.get("head_dim", dim // heads))
    return TinyDecoder(
        vocab=int(config["vocab_size"]), dim=dim, depth=depth,
        num_q_heads=heads,
        num_kv_heads=int(config.get("num_key_value_heads", heads)),
        impl=impl, dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        head_dim=None if head_dim == dim // heads else head_dim,
        window=int(config["sliding_window"]), rope=True,
        rope_theta=float(config["rope_theta"]),
        global_rope=bool(config.get("full_attention_rotary", False)),
        layer_types=kinds, sublayer=tuple(sorted(fields.items())),
        num_dense_layers=min(int(config.get("num_dense_layers", 0)), depth),
        mlp_hidden=int(config["intermediate_size"]),
        head_norm=bool(config.get("qk_head_norm", False)),
        attn_gate=bool(config.get("attention_gate", False)),
        sandwich_norm=bool(config.get("sandwich_norm", False)),
        embed_scale=(float(dim) ** 0.5 if config.get("mup_enabled")
                     else 1.0),
        norm_eps=float(config.get("rms_norm_eps", 1e-6)))


def decoder_from_config(config: dict, *, impl: str = "flash") -> TinyDecoder:
    """The program's decoder at the configuration's sizes."""
    if (SLIDING_ATTENTION in (config.get("layer_types") or ())
            and "num_experts" in config):
        return _mixed_window_decoder(config, impl=impl)
    if "attention_method" in config:
        return _latent_decoder(config, impl=impl)
    if "kv_lora_rank" in config:
        return _indexed_latent_decoder(config, impl=impl)
    if "hybrid_override_pattern" in config:
        return _sublayer_decoder(config, impl=impl)
    common = _common(config, impl=impl)
    dim, depth = common["dim"], common["depth"]
    two_sublayers = (FULL_ATTENTION, LINEAR_ATTENTION)
    kinds = config.get("layer_types")
    if kinds is not None:
        # a configuration cut in depth keeps the published list whole
        # and serves its first layers (whole periods of the pattern)
        kinds = tuple(kinds[:depth])
        if len(kinds) != depth or set(kinds) - set(two_sublayers):
            raise ValueError(
                f"layer_types must name {depth} layers of {two_sublayers}")
    act = config.get("hidden_act", "gelu")
    hidden = int(config["intermediate_size"])
    if act in _GELU:
        if hidden != 4 * dim:
            raise ValueError("the ungated gelu MLP is 4x wide")
        mlp = {}
    elif act == "silu":
        mlp = {"mlp_hidden": hidden, "mlp_act": "silu"}
    else:
        raise ValueError(f"no MLP for hidden_act {act!r}")
    linear = {}
    if kinds is not None and LINEAR_ATTENTION in kinds:
        if (config["linear_num_key_heads"]
                != config["linear_num_value_heads"]):
            raise ValueError("key and value heads of the linear layers "
                             "must be as many")
        linear = {
            "linear_heads": int(config["linear_num_value_heads"]),
            "linear_key_dim": int(config["linear_key_head_dim"]),
            "linear_value_dim": int(config["linear_value_head_dim"]),
            "linear_conv": int(config["linear_conv_kernel_dim"]),
            "linear_neg_eigval": bool(config["linear_allow_neg_eigval"]),
        }
    window = config.get("sliding_window")
    return TinyDecoder(
        **common, window=None if window is None else int(window),
        layer_types=kinds, **linear, **mlp,
        post_norm=bool(config.get("post_norm", False)),
        qk_norm=bool(config.get("qk_norm", False)))
