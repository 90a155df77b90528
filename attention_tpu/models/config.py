"""A decoder from a published configuration's keys.

`decoder_from_config` reads a Hugging Face style ``config.json`` (the
keys as published, as `benchmark/configs/*.json` keeps them) and
returns the `TinyDecoder` that serves it.  Where the norms sit is not
a key of any published configuration, so the file states it beside its
``assumed`` list: ``post_norm`` (norm on each sublayer's output, OLMo
2 / 3) and ``qk_norm`` (RMSNorm on the q and k projections), both
false where absent, which is the pre-norm block of StarCoder2.
Nothing is decided from a model's name.
"""

from __future__ import annotations

import jax.numpy as jnp

from attention_tpu.models.transformer import (
    LAYER_KINDS,
    LINEAR_ATTENTION,
    TinyDecoder,
)

_GELU = ("gelu", "gelu_new", "gelu_pytorch_tanh")


def decoder_from_config(config: dict, *, impl: str = "flash") -> TinyDecoder:
    """The program's decoder at the configuration's sizes."""
    dim = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    head_dim = config.get("head_dim")
    if head_dim is not None and dim // heads != int(head_dim):
        raise ValueError("hidden_size / num_attention_heads != head_dim")
    depth = int(config["num_hidden_layers"])
    kinds = config.get("layer_types")
    if kinds is not None:
        # a configuration cut in depth keeps the published list whole
        # and serves its first layers (whole periods of the pattern)
        kinds = tuple(kinds[:depth])
        if len(kinds) != depth or set(kinds) - set(LAYER_KINDS):
            raise ValueError(
                f"layer_types must name {depth} layers of {LAYER_KINDS}")
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
    theta = config.get("rope_theta")
    if theta is None:
        theta = (config.get("rope_parameters") or {}).get("rope_theta")
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
        vocab=int(config["vocab_size"]), dim=dim, depth=depth,
        num_q_heads=heads,
        num_kv_heads=int(config.get("num_key_value_heads", heads)),
        impl=impl, dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        window=None if window is None else int(window),
        rope=theta is not None,
        rope_theta=10000.0 if theta is None else float(theta),
        layer_types=kinds, **linear, **mlp,
        post_norm=bool(config.get("post_norm", False)),
        qk_norm=bool(config.get("qk_norm", False)))
