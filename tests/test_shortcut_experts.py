"""A decoder of double layers (two latent-attention and two dense
feed-forward sublayers, one shortcut-connected branch of gated and
zero-compute experts) through `ServingEngine`, at a toy cut of
`benchmark/configs/longcat-flash-omni.json` (8 heads where the issue's
toy cut has 4: the kernel's row-blocked form wants a group that is a
multiple of 8): logits against the plain reference beside that file,
the pools the engine builds, the prefix cache, the leaves' dtype, the
share, and what is refused by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu import obs
from attention_tpu.engine import EngineConfig, SamplingParams, ServingEngine
from attention_tpu.engine.errors import LatentCacheUnsupportedError
from attention_tpu.models import decoder_from_config
from attention_tpu.models.moe import GatedExperts, PackedTokens
from benchmark import harness

VOCAB = 97
CONFIG = {
    "attention_method": "MLA", "hidden_size": 64, "num_attention_heads": 8,
    "num_layers": 2, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "rope_theta": 1e4,
    "ffn_hidden_size": 96, "expert_ffn_hidden_size": 48,
    "n_routed_experts": 4, "expert_share": {"index": 1, "of": 2},
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "routed_scaling_factor": 6.0, "vocab_size": VOCAB, "rms_norm_eps": 1e-5,
    "torch_dtype": "float32",
}
ENGINE = dict(num_pages=24, page_size=128, max_seq_len=512,
              max_decode_batch=3, max_prefill_rows=1, prefill_chunk=32,
              token_budget=40)
# Both compute in float32, the reference expanded, a head and an expert
# at a time at the highest precision, the program absorbed, in chunks,
# pages and one grouped product: they differ by rounding (read: 8e-7).
# A top-3 choice that flipped would move a logit by an expert's part.
TOL = 1e-4
# bfloat16 activations against the float32 reference, logits of size 2:
# read 0.015-0.126 over these requests (the weights are the same
# bfloat16 values in both; a top-3 choice of 12 that flips at the margin
# moves a logit by a tenth); a sublayer or a scale left out reads 0.5+
BF16_BAND = 0.3


@pytest.fixture(scope="module")
def served():
    reference = harness.load_module("configs", "longcat-flash-omni_reference")
    model = decoder_from_config(CONFIG)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.jit(lambda k: reference.init_params(shapes, k))(
        jax.random.PRNGKey(3))
    return model, params, reference


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=n).tolist() for n in lengths]


def _serve(model, params, prompts, max_tokens, eng=None, **engine):
    """Serve ``prompts`` together; per request its tokens and the
    logits row each was sampled from."""
    eng = eng or ServingEngine(model, params,
                               EngineConfig(**dict(ENGINE, **engine)))
    rows, sample = {}, eng._sample

    def recording(req, logits_row):
        rows.setdefault(req.request_id, []).append(logits_row.copy())
        return sample(req, logits_row)

    eng._sample = recording
    reqs = [eng.add_request(p, SamplingParams(max_tokens=max_tokens))
            for p in prompts]
    eng.run(max_steps=400)
    eng._sample = sample
    return eng, reqs, [np.stack(rows[r.request_id]) for r in reqs]


@pytest.fixture(scope="module")
def float32_run(served):
    model, params, _ = served
    prompts = _prompts(0, 75, 140, 9)
    return prompts, *_serve(model, params, prompts, 6)


def test_chunked_prefill_then_decode_is_the_references_forward_pass(
        served, float32_run):
    """(a): prompts of 75, 140 and 9 tokens in chunks of 32 beside each
    other's decode rows, six tokens each through the latent pools."""
    _, params, reference = served
    prompts, _, reqs, logits = float32_run
    for prompt, req, got in zip(prompts, reqs, logits):
        want = reference.served_logits(
            params, CONFIG, prompt, req.output_tokens, pad_to=256, rows=8)
        np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("left_out, moved", [
    ("no_experts", 0.2), ("no_zero", 0.2), ("no_s_kv", 0.2),
    ("branch_first", 0.02)])
def test_a_piece_left_out_is_far_outside_the_tolerance(served, left_out,
                                                        moved):
    """What the float32 comparison above holds: the expert branch, the
    zero experts' part, the ``s_kv`` scale and WHERE the branch lands
    each move the reference's own logits by 100 tolerances or more."""
    _, params, reference = served
    prompt = _prompts(0, 75)[0]
    exact, less = (reference.served_logits(
        params, CONFIG, prompt, [1, 2, 3, 4], pad_to=128, rows=4,
        low_precision=which) for which in (False, left_out))
    assert np.abs(less - exact).max() > moved >= 200 * TOL


def test_bfloat16_activations_stay_in_a_band(served):
    _, params, reference = served
    model = decoder_from_config(dict(CONFIG, torch_dtype="bfloat16"))
    prompts = _prompts(1, 75, 140)
    _, reqs, logits = _serve(model, params, prompts, 4)
    for prompt, req, got in zip(prompts, reqs, logits):
        want = reference.served_logits(
            params, CONFIG, prompt, req.output_tokens, pad_to=256, rows=4)
        assert 1e-4 < np.abs(got - want).max() < BF16_BAND


def test_the_engine_builds_one_latent_pool_a_sublayer(float32_run):
    """(e): 2 x depth pools of (pages, 1, page, [c | k_r] padded to
    whole registers), none for V; a token costs one row a sublayer."""
    _, eng, _, _ = float32_run
    latents = [p for pools in eng._pools for p in pools]
    assert [len(pools) for pools in eng._pools] == [2, 2]   # and no V
    assert {p.shape for p in latents} == {(24, 1, 128, 128)}
    assert eng.model.kv_pool_widths() == (1, (128,))
    assert eng.model.attention_sublayers == (0, 0, 1, 1)
    per_token = sum(p.shape[1] * p.shape[3] * p.dtype.itemsize
                    for p in latents)
    assert per_token == 4 * 128 * 4
    # at the published widths: 576 values in 640 lanes, 2 bytes each
    full = decoder_from_config(harness.load_json(
        "configs", "longcat-flash-omni.json"))
    assert full.kv_pool_widths() == (1, (640,))
    assert len(full.attention_sublayers) == 8
    assert full.num_kv_heads == 1 and full.num_q_heads == 64
    assert not eng._layout.state_rows
    assert eng.allocator.state_slots_in_use == 0


def test_the_steps_counters_add_up(float32_run):
    """Every real token routes top-3 in each of the 2 expert layers:
    local + absent + zero pairs; the attention's pairs by the lengths."""
    _, eng, _, _ = float32_run
    busy = [m for m in eng.metrics.steps
            if m.decode_tokens or m.prefill_tokens]
    for m in busy:
        tokens = m.decode_tokens + m.prefill_tokens
        assert (m.expert_pairs_local + m.expert_pairs_absent
                + m.expert_pairs_zero) == 2 * 3 * tokens
        assert m.expert_load_max <= m.expert_pairs_local
        assert m.attn_qk_pairs >= tokens and m.kv_pages >= 1
    first = busy[0]          # the first chunk of the first prompt alone
    assert first.prefill_tokens == 32
    assert first.attn_qk_pairs == 32 * 33 // 2
    # the row-blocked kernel's grid, a sublayer: the chunk's one block
    # of 32 tokens x 8 heads on its one page; a decode row's pages
    assert first.ragged_grid_steps == 4 * 1
    assert all(m.ragged_grid_steps == 4 * m.kv_pages for m in busy
               if not m.prefill_tokens)
    summary = eng.metrics.summary()
    # 4 held of 8 real + 4 zero columns: a third each at an even router
    assert 0.2 < summary["local_pair_share"] < 0.45
    assert 0.2 < summary["zero_pair_share"] < 0.45
    assert summary["mean_attn_qk_pairs"] > 0


def test_a_second_request_matches_the_first_ones_pages(served):
    """(f): 300 shared tokens are two whole pages in the prefix cache;
    the second request computes only what follows them and is served
    the logits it gets without the cache."""
    model, params, reference = served
    shared = _prompts(5, 300)[0]
    first, second = shared + _prompts(6, 20)[0], shared + _prompts(7, 33)[0]
    eng, _, _ = _serve(model, params, [first], 2)
    _, (req,), (cached,) = _serve(model, params, [second], 3, eng=eng)
    assert req.prefix_cached_tokens == 256
    _, _, (cold,) = _serve(model, params, [second], 3)
    np.testing.assert_allclose(cached, cold, atol=2e-5)
    want = reference.served_logits(params, CONFIG, second, req.output_tokens,
                                   pad_to=384, rows=4)
    np.testing.assert_allclose(cached, want, atol=TOL)


def test_bfloat16_and_float32_leaves_serve_the_same_bits(served):
    """(g): the program casts what it reads, so float32 leaves that
    hold bfloat16 values are the same model."""
    _, params, _ = served
    assert {str(a.dtype) for a in jax.tree.leaves(params)} == {
        "bfloat16", "float32"}
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    model = decoder_from_config(dict(CONFIG, torch_dtype="bfloat16"))
    prompts = _prompts(8, 40, 5)
    _, a_reqs, a = _serve(model, params, prompts, 3)
    _, b_reqs, b = _serve(model, wide, prompts, 3)
    for x, y in zip(a, b):
        assert x.tobytes() == y.tobytes()
    assert [r.output_tokens for r in a_reqs] == [r.output_tokens
                                                 for r in b_reqs]


def test_the_dispatch_span_names_the_latent_sublayers(served):
    model, params, _ = served
    eng = ServingEngine(model, params, EngineConfig(**ENGINE))
    eng.add_request(_prompts(9, 12)[0], SamplingParams(max_tokens=2))
    seen = []
    real = obs.span

    def spy(name, **fields):
        if name == "engine.step.dispatch":
            seen.append(fields)
        return real(name, **fields)

    import attention_tpu.engine.engine as engine_module

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_module.obs, "span", spy)
        eng.run(max_steps=10)
    assert seen and all(f["latent_layers"] == 4 and f["expert_layers"] == 2
                        and f["zero_experts"] == 4 for f in seen)


def test_features_that_carry_k_and_v_pools_refuse_by_name(served):
    from attention_tpu.engine import snapshot
    from attention_tpu.fleet.handoff import export_handoff
    from attention_tpu.parallel.serving import MeshConfigError
    from attention_tpu.prefixstore.adapter import export_chain

    model, params, _ = served
    eng = ServingEngine(model, params, EngineConfig(**ENGINE))
    for refused in (lambda: snapshot.save(eng, "/nonexistent/x"),
                    lambda: export_chain(eng, (1,) * 128, [0], now=0),
                    lambda: export_handoff(eng, None, {})):
        with pytest.raises(LatentCacheUnsupportedError, match="ONE latent"):
            refused()
    with pytest.raises(MeshConfigError, match="shards pages, not heads"):
        ServingEngine(model, params, EngineConfig(mesh_shards=2, **ENGINE))
    with pytest.raises(ValueError, match="packed step"):
        model.init_caches(1, 64)


@pytest.mark.parametrize("change, match", [
    ({"attention_method": "GQA"}, "attention_method"),
    ({"mla_scale_q_lora": False}, "mla_scale_q_lora"),
    ({"mla_scale_kv_lora": False}, "mla_scale_kv_lora"),
    ({"zero_expert_type": "copy"}, "zero_expert_type"),
    ({"rope_scaling": {"factor": 4}}, "rope_scaling"),
    ({"norm_topk_prob": True}, "norm_topk_prob"),
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
])
def test_what_no_configuration_runs_is_refused_by_its_key(change, match):
    """(i)"""
    with pytest.raises(ValueError, match=match):
        decoder_from_config(dict(CONFIG, **change))


def test_the_other_configurations_trees_are_what_they_were():
    """(h): the names and shapes of every leaf the other three served
    configurations declare, by a digest taken on the parent commit."""
    import hashlib

    want = {"starcoder2-7b": "b78eae25", "olmo-hybrid-7b": "7672c2f0",
            "nemotron-3-super-120b": "7f9b9aa1"}
    serve = harness.load_module("runners", "serve")
    got = {}
    for name in want:
        config = harness.load_json("configs", name + ".json")
        model = (serve.build_model(config) if config["runner"] == "serve"
                 else decoder_from_config(config))
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))["params"]
        leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
        text = ";".join(f"{jax.tree_util.keystr(p)}{a.shape}{a.dtype}"
                        for p, a in leaves)
        got[name] = hashlib.sha256(text.encode()).hexdigest()[:8]
    assert got == want


# -- the share ---------------------------------------------------------------

E, HELD, ZERO, TOP_K, DIM, HIDDEN = 8, 4, 4, 3, 64, 48


@pytest.fixture(scope="module")
def whole_branch():
    reference = harness.load_module("configs", "longcat-flash-omni_reference")
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 24, DIM)), jnp.float32)
    layer = GatedExperts(num_experts=E, held=E, zero_experts=ZERO,
                         top_k=TOP_K, hidden=HIDDEN, scale=6.0,
                         dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    return reference, x, params


def _share_of(params, share):
    cut = slice(share * HELD, (share + 1) * HELD)
    return dict(params, **{k: params[k][cut] for k in (
        "experts_gate", "experts_up", "experts_down")})


def _layer(share, held=HELD):
    return GatedExperts(num_experts=E, held=held, share=share,
                        zero_experts=ZERO, top_k=TOP_K, hidden=HIDDEN,
                        scale=6.0, dtype=jnp.float32)


def _dense(reference, params, x, share=0, shares=1, left_out=None):
    sizes = {"share": share, "shares": shares, "top_k": TOP_K, "scale": 6.0,
             "capacity": 24}
    out, _, over = reference._experts(params, x[0], sizes=sizes,
                                      quant=lambda t: t, left_out=left_out)
    assert int(over) <= 0
    return out


def test_the_shares_add_up_to_the_uncut_branch(whole_branch):
    """(c) THE SHARE TEST: the real experts' parts of both shares of 4
    experts, with what every chip computes alike (the zero experts'
    part) counted once, are the uncut reference's branch."""
    reference, x, params = whole_branch
    with jax.default_matmul_precision("highest"):
        whole = _dense(reference, params, x)
        zero = whole - _dense(reference, params, x, left_out="no_zero")
        assert np.abs(zero).max() > 0.1
        total, counted = 0.0, 0
        for share in range(E // HELD):
            out, sown = _layer(share).apply(
                {"params": _share_of(params, share)}, x,
                mutable=["expert_stats"])
            np.testing.assert_allclose(
                out[0], _dense(reference, _share_of(params, share), x,
                               share, E // HELD), atol=2e-5)
            total = total + (out[0] - zero)
            (pairs,) = sown["expert_stats"]["pairs"]
            assert pairs.shape == (HELD + 3,)
            local, (absent, reached, zeros) = pairs[:HELD], pairs[HELD:]
            assert int(local.sum() + absent + zeros) == 24 * TOP_K
            assert int(reached) == int((local > 0).sum())
            counted += int(local.sum())
        np.testing.assert_allclose(total + zero, whole, atol=5e-5)
        out, sown = _layer(0, held=E).apply({"params": params}, x,
                                            mutable=["expert_stats"])
        np.testing.assert_allclose(out[0], whole, atol=2e-5)
        (pairs,) = sown["expert_stats"]["pairs"]
        assert counted == int(pairs[:E].sum()) and int(pairs[E]) == 0


def test_a_token_of_zero_experts_alone_gets_its_input_back(whole_branch):
    """(d): with the selection bias lifting three zero experts over
    everything, every pick of every token is one of them: the branch
    is (sum g) y exactly, no real expert takes a row, and the pad
    tokens of a packed step take none and are not counted."""
    _, x, params = whole_branch
    bias = params["router_bias"].at[E:E + 3].set(9.0)
    slot = jnp.where(jnp.arange(24) < 20, 0, -1)
    out, sown = _layer(1).apply(
        {"params": _share_of(dict(params, router_bias=bias), 1)}, x,
        PackedTokens(slot), mutable=["expert_stats"])
    scores = jax.nn.softmax(jnp.dot(
        x[0], params["router"], precision=jax.lax.Precision.HIGHEST))
    weight = 6.0 * scores[:, E:E + 3].sum(axis=1, keepdims=True)
    np.testing.assert_allclose(out[0], weight * x[0], rtol=1e-6, atol=1e-7)
    (pairs,) = sown["expert_stats"]["pairs"]
    assert pairs.tolist() == [0, 0, 0, 0, 0, 0, 20 * TOP_K]
