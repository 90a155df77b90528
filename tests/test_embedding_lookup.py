"""The decoder's embedding lookup takes the rows a call holds from the
float32 table and casts THOSE to the model's dtype: the table itself is
only ever indexed.  The order `flax.linen.Embed(dtype=...)` has (cast
the whole table, then take the rows) is written out here and fed to
the same blocks: the cast is element-wise, so both orders give the
same bits."""

import contextlib
import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.engine import EngineConfig, SamplingParams, ServingEngine
from attention_tpu.engine import engine as engine_module
from attention_tpu.models import TinyDecoder, decoder_from_config
from attention_tpu.models.train import loss_fn

VOCAB, DIM = 97, 64
TOKENS = np.asarray([[5, 9, 5, 3, 9, 1, 2, 7]], np.int32)  # 5 and 9 twice

# Toy cuts of the three served configurations, by their published keys.
DENSE = {  # starcoder2-7b: GQA, ungated gelu MLP, rope, a window
    "vocab_size": VOCAB, "hidden_size": DIM, "intermediate_size": 4 * DIM,
    "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 32,
    "hidden_act": "gelu_pytorch_tanh", "rope_theta": 1000000,
    "sliding_window": 4096, "torch_dtype": "bfloat16",
}
HYBRID = {  # olmo-hybrid-7b: three linear-attention layers to one full
    "post_norm": True, "qk_norm": True, "vocab_size": VOCAB,
    "hidden_size": DIM, "intermediate_size": 96, "num_hidden_layers": 4,
    "num_attention_heads": 2, "num_key_value_heads": 2, "hidden_act": "silu",
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "torch_dtype": "bfloat16",
}
SUBLAYER = {  # nemotron-3-super-120b: the cell's eleven letters
    "vocab_size": VOCAB, "hidden_size": DIM, "num_hidden_layers": 11,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "hybrid_override_pattern": "MEMEMEM*EME", "intermediate_size": 48,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "use_conv_bias": True,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "n_routed_experts": 4, "expert_share": {"index": 1, "of": 4},
    "num_experts_per_tok": 4, "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
    "n_shared_experts": 1, "routed_scaling_factor": 5.0,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "norm_eps": 1e-5, "rope_theta": 10000, "attention_rotary": False,
    "sliding_window": None, "torch_dtype": "bfloat16",
}
MODELS = {
    "tiny": lambda: TinyDecoder(vocab=VOCAB, dim=DIM, depth=2, num_q_heads=4,
                                num_kv_heads=2, dtype=jnp.bfloat16),
    "dense": lambda: decoder_from_config(DENSE),
    "hybrid": lambda: decoder_from_config(HYBRID),
    "sublayer": lambda: decoder_from_config(SUBLAYER),
}
# sha256 over the sorted "path shape dtype" lines of each model's
# parameter tree and the number of its leaves, written from the tree at
# commit b2f6f13 (`Embed(dtype=...)`, the table cast whole).
PARENT_TREES = {
    "tiny": (19, "ef08e491a34cfbe04899bfe713498ac6"
                 "1bb3edf51e68679be9243eb1f88c2f40"),
    "dense": (19, "addec5742b6f16d9088b4b5c692c4318"
                  "43e5bb7cf7f9463a8def8166002d2506"),
    "hybrid": (68, "ae96c9aae0b3da759f97bd7947d14b49"
                   "3347cd88f8783708d1432b6b069aac3d"),
    "sublayer": (98, "7e39ac91bd72b3de9401f66cfc48e146"
                     "341a7d2d39a15b781286f802659b311d"),
}


def _init(model, seed=0):
    return model.init(jax.random.PRNGKey(seed), TOKENS)["params"]


def _is_lookup(context):
    return (type(context.module) is nn.Embed
            and context.method_name == "__call__")


@contextlib.contextmanager
def _old_order(dtype):
    """Trace `Embed.__call__` as it was: the whole table cast to the
    model's dtype, then the rows.  Fails if no lookup ran under it."""
    calls = []

    def cast_table_then_take(next_fun, args, kwargs, context):
        if _is_lookup(context):
            calls.append(args[0].shape)
            return jnp.take(context.module.embedding.astype(dtype), args[0],
                            axis=0)
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(cast_table_then_take):
        yield
    assert calls


def _equations(jaxpr):
    """Every equation of ``jaxpr`` that is not itself a call of an inner
    jaxpr, those of the inner jaxprs included."""
    for eqn in jaxpr.eqns:
        inner = [sub for value in eqn.params.values()
                 for sub in (value if isinstance(value, (tuple, list))
                             else (value,))
                 if hasattr(sub, "eqns") or hasattr(sub, "jaxpr")]
        if not inner:
            yield eqn
        for sub in inner:
            yield from _equations(getattr(sub, "jaxpr", sub))


def _table_readers(model):
    """The primitives of a forward pass that take an array of the
    table's shape."""
    params = jax.eval_shape(lambda: _init(model))
    jaxpr = jax.make_jaxpr(
        lambda p, t: model.apply({"params": p}, t))(params, TOKENS)
    return [eqn.primitive.name for eqn in _equations(jaxpr.jaxpr)
            if any(getattr(v.aval, "shape", None) == (VOCAB, DIM)
                   for v in eqn.invars)]


@pytest.mark.parametrize("name", MODELS)
def test_the_table_is_only_ever_indexed(name):
    """No equation of a forward pass reads an array of the table's shape
    but the gather: no cast of 49,152 x 4,608 floats beside 8 rows."""
    model = MODELS[name]()
    assert _table_readers(model) == ["gather"]
    with _old_order(model.dtype):  # the walker does see the old order's cast
        assert "convert_element_type" in _table_readers(model)


@pytest.mark.parametrize("name", MODELS)
def test_logits_are_the_old_orders_bit_for_bit(name):
    model = MODELS[name]()
    params = _init(model)
    new = model.apply({"params": params}, TOKENS)
    with _old_order(model.dtype):
        old = model.apply({"params": params}, TOKENS)
    assert new.dtype == old.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    assert np.isfinite(np.asarray(new)).all() and np.asarray(new).any()


@pytest.mark.parametrize("name", ["tiny", "dense"])
def test_logits_with_caches_are_the_old_orders_bit_for_bit(name):
    """A prompt of five tokens into dense caches, then three single
    tokens on top of them."""
    model = MODELS[name]()
    params = _init(model)

    def run():
        caches = model.init_caches(1, 128)
        out, caches = model.apply({"params": params}, TOKENS[:, :5], caches)
        outs = [out]
        for i in range(5, 8):
            out, caches = model.apply({"params": params}, TOKENS[:, i:i + 1],
                                      caches)
            outs.append(out)
        return np.concatenate([np.asarray(o) for o in outs], axis=1)

    new = run()
    with _old_order(model.dtype):
        old = run()
    np.testing.assert_array_equal(new, old)
    assert np.isfinite(new).all()


@pytest.mark.parametrize("name", ["dense", "hybrid", "sublayer"])
def test_one_ragged_engine_step_is_the_old_orders_bit_for_bit(name):
    """Two prompts through `ServingEngine.step`, chunk then decode: the
    rows the host is handed are the same bits under either order.
    `_ragged_apply` is jitted on the model, so its cache is dropped
    around the old order's trace."""
    model = MODELS[name]()
    params = _init(model)
    prompts = [TOKENS[0].tolist(), TOKENS[0, ::-1].tolist() + [11, 13]]

    def two_steps():
        eng = ServingEngine(model, params, EngineConfig(
            num_pages=16, page_size=128, max_seq_len=256, max_decode_batch=2,
            max_prefill_rows=2, prefill_chunk=32, token_budget=64))
        fetch, fetched = eng._fetch_logits, []

        def recording(*args):
            fetched.append(fetch(*args))
            return fetched[-1]

        eng._fetch_logits = recording
        for i, prompt in enumerate(prompts):
            eng.add_request(prompt, SamplingParams(max_tokens=2),
                            request_id=f"r{i}", arrival=i)
        eng.step()
        eng.step()  # a decode row each, on the state the first step left
        return np.stack([np.asarray(rows) for rows in fetched])

    new = two_steps()
    engine_module._ragged_apply.clear_cache()
    try:
        with _old_order(model.dtype):
            old = two_steps()
    finally:
        engine_module._ragged_apply.clear_cache()
    np.testing.assert_array_equal(new, old)
    assert np.isfinite(new).all()


@pytest.mark.parametrize("name", MODELS)
def test_the_parameter_tree_is_the_parents(name):
    model = MODELS[name]()
    shapes = jax.eval_shape(lambda: _init(model))
    table = shapes["Embed_0"]["embedding"]
    assert (table.shape, table.dtype) == ((VOCAB, DIM), jnp.float32)
    lines = sorted(
        f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0])
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()
    assert (len(lines), digest) == PARENT_TREES[name]


def test_float32_gradient_of_the_table_is_unchanged():
    model = MODELS["tiny"]().clone(dtype=jnp.float32)
    params = _init(model)
    batch = jnp.concatenate([TOKENS, TOKENS[:, ::-1]], axis=0)
    new = jax.grad(loss_fn)(params, model, batch)
    with _old_order(jnp.float32):
        old = jax.grad(loss_fn)(params, model, batch)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(new["Embed_0"]["embedding"]).any()


def test_bf16_gradient_of_a_repeated_token_sums_in_float32():
    """The rows' cotangents reach the table in float32 and are summed
    there: a token that appears twice gets the float32 sum of its two
    rows, which bfloat16 cannot hold."""
    model = MODELS["tiny"]()
    params = _init(model)

    def loss(p, delta):
        def add_to_rows(next_fun, args, kwargs, context):
            rows = next_fun(*args, **kwargs)
            return rows + delta if _is_lookup(context) else rows

        with nn.intercept_methods(add_to_rows):
            return loss_fn(p, model, TOKENS)

    # the derivative by a float32 zero added to the rows before their
    # cast is each row's cotangent, in float32
    rows = jax.grad(loss, argnums=1)(
        params, jnp.zeros((1, TOKENS.shape[1] - 1, DIM), jnp.float32))
    assert rows.dtype == jnp.float32
    want = np.zeros((VOCAB, DIM), np.float32)
    np.add.at(want, np.asarray(TOKENS[0, :-1]), np.asarray(rows[0]))
    got = jax.grad(loss_fn)(params, model, TOKENS)["Embed_0"]["embedding"]
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), want)
    twice = want[[5, 9]]  # both appear twice among the seven inputs
    assert (twice != np.asarray(
        jnp.asarray(twice).astype(jnp.bfloat16).astype(jnp.float32))).any()
