"""A decoder of two-sublayer blocks whose latent attention CHOOSES its
keys (a lightning indexer with a cache of its own beside the latent
pool) through `ServingEngine`, at a toy cut of
`benchmark/configs/deepseek-v3.2-exp.json`: logits against the plain
reference beside that file with ``index_topk`` BELOW the context (keys
are really dropped) and above it (every key attended), the two pools a
sublayer under one page table, the prefix cache restoring both, the
device's count of attended keys, the group-limited sigmoid router, the
share, YaRN's frequencies, and what is refused by name."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu import obs
from attention_tpu.engine import EngineConfig, SamplingParams, ServingEngine
from attention_tpu.engine.errors import LatentCacheUnsupportedError
from attention_tpu.models import decoder_from_config
from attention_tpu.models.latent_attention import LatentAttention
from attention_tpu.models.moe import GatedExperts, sigmoid_top_k
from attention_tpu.ops.rope import YarnScaling, yarn_inv_freq, yarn_mscale
from benchmark import harness

VOCAB = 97
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 8, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 16,
    "intermediate_size": 96, "moe_intermediate_size": 48,
    "n_routed_experts": 4, "expert_share": {"index": 1, "of": 4},
    "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "moe_layer_freq": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 1e4, "rope_scaling": YARN,
    "hidden_act": "silu", "attention_bias": False, "vocab_size": VOCAB,
    "rms_norm_eps": 1e-6, "torch_dtype": "float32",
}
ENGINE = dict(num_pages=24, page_size=128, max_seq_len=512,
              max_decode_batch=3, max_prefill_rows=1, prefill_chunk=32,
              token_budget=40)
# Both compute in float32, the reference expanded and a head at a time
# at the highest precision, the program absorbed, in chunks and pages:
# they differ by rounding (read: 1e-6).  A key that entered or left a
# row's 16 would move its logits by far more.
TOL = 1e-4


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("configs", "deepseek-v3.2-exp_reference")


def _params(reference, config, seed=3):
    model = decoder_from_config(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.jit(lambda k: reference.init_params(shapes, k))(
        jax.random.PRNGKey(seed))
    # a selection bias away from zero, so that it shows in the choice
    # and must not show in the weights
    rng = np.random.default_rng(seed)
    for block in params.values():
        if "experts" in block:
            bias = block["experts"]["router_bias"]
            block["experts"]["router_bias"] = jnp.asarray(
                rng.standard_normal(bias.shape) * 0.3, jnp.float32)
    return model, params


@pytest.fixture(scope="module")
def served(reference):
    return (*_params(reference, CONFIG), reference)


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


def test_chunked_prefill_then_decode_attends_the_references_sixteen_keys(
        served, float32_run):
    """Prompts of 75, 140 and 9 tokens in chunks of 32 beside each
    other's decode rows, six tokens each: from position 16 on a row
    attends 16 of its keys, chosen through the index pool."""
    _, params, reference = served
    prompts, _, reqs, logits = float32_run
    for prompt, req, got in zip(prompts, reqs, logits):
        want = reference.served_logits(
            params, CONFIG, prompt, req.output_tokens, pad_to=256, rows=8)
        np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("left_out", [
    "newest", "all_keys", "no_shared", "no_experts", "no_yarn"])
def test_a_piece_changed_is_far_outside_the_tolerance(served, left_out):
    """What the comparison above holds: the selection (against the
    newest 16 keys and against every key), the shared expert, the
    routed experts, YaRN with its softmax scale: each moves the
    reference's own logits by 100 tolerances or more."""
    _, params, reference = served
    prompt = _prompts(0, 75)[0]
    exact, less = (reference.served_logits(
        params, CONFIG, prompt, [1, 2, 3, 4], pad_to=128, rows=4,
        low_precision=which) for which in (False, left_out))
    assert np.abs(less - exact).max() > 100 * TOL


@pytest.mark.parametrize("which", [False, "newest", "all_keys", "fp8"])
def test_the_references_two_writings_of_the_attention_agree(served, which):
    """The GATHERED form the benchmark runs (a row's chosen latents
    gathered, ``W_kvb`` taken to the query's side) against the
    EXPANDED one (per-head keys and
    values, every causal pair scored, the pairs not chosen masked): the
    same logits to rounding, where one key more or less in one row's
    16 moves them by 100 tolerances.  The fp8 control rounds under
    other scales in the two (a block of rows, a sequence): both are
    far from the exact logits, and near each other."""
    _, params, reference = served
    prompt = _prompts(4, 150)[0]
    tokens = [5, 6, 7, 8, 9]
    gathered, expanded = (reference.served_logits(
        params, CONFIG, prompt, tokens, pad_to=256, rows=8,
        low_precision=which, form=form) for form in ("gathered", "expanded"))
    if which != "fp8":
        np.testing.assert_allclose(gathered, expanded, atol=1e-5)
        return
    exact = reference.served_logits(params, CONFIG, prompt, tokens,
                                    pad_to=256, rows=8)
    assert np.abs(gathered - exact).max() > 100 * TOL
    assert np.abs(expanded - exact).max() > 100 * TOL
    with pytest.raises(ValueError, match="form"):
        reference.served_logits(params, CONFIG, prompt, tokens, pad_to=256,
                                rows=8, form="absorbed")


def test_a_group_of_128_heads_has_no_tile_under_256(reference):
    """128 query heads on the one latent head, the published group: 8
    tokens of it are a whole block of the row-blocked form, so the
    tile only bounds a span's blocks and is never under 256 tokens
    (`recommended_q_tile`; other groups keep their tiers).  Chunks of
    40, 7 and 3 tokens beside decode rows at the tile of 256 (32
    blocks to a span, one to five of them live), then decode, against
    the reference."""
    from attention_tpu.ops.ragged_paged import (
        packed_bucket, recommended_q_tile, tile_tokens)

    tiles = {n: recommended_q_tile(n, 128) for n in range(1, 600)}
    assert tiles[1] == 1
    assert {tiles[n] for n in range(2, 257)} == {256}
    assert sorted(set(tiles.values())) == [1, 256, 384, 512, 768]
    for group in (8, 64):
        assert all(recommended_q_tile(n, group) == tile_tokens(
            max(packed_bucket(n, minimum=1), 8), group)
            for n in range(2, 600))
    config = dict(CONFIG, num_attention_heads=128, qk_nope_head_dim=8,
                  v_head_dim=8, num_hidden_layers=2)
    model, params = _params(reference, config)
    prompts = _prompts(7, 47, 83)
    eng, reqs, logits = _serve(model, params, prompts, 3, prefill_chunk=40,
                               token_budget=48)
    assert eng.metrics.compiled_shapes == {(256, 256), (8, 1)}
    for prompt, req, got in zip(prompts, reqs, logits):
        want = reference.served_logits(
            params, config, prompt, req.output_tokens, pad_to=128, rows=4)
        np.testing.assert_allclose(got, want, atol=TOL)


def test_a_choice_wider_than_the_context_is_dense_latent_attention(
        reference):
    """``index_topk`` 2,048 over contexts of at most 146: every key is
    attended, and the logits are those of the same weights with every
    key attended (the reference's ``all_keys``), the attended count
    every causal pair."""
    config = dict(CONFIG, index_topk=2048)
    model, params = _params(reference, config)
    prompts = _prompts(0, 75, 140)
    eng, reqs, logits = _serve(model, params, prompts, 6)
    for prompt, req, got in zip(prompts, reqs, logits):
        want = reference.served_logits(
            params, config, prompt, req.output_tokens, pad_to=256, rows=8,
            low_precision="all_keys")
        np.testing.assert_allclose(got, want, atol=TOL)
    busy = [m for m in eng.metrics.steps
            if m.decode_tokens or m.prefill_tokens]
    assert all(m.attn_keys_attended == 3 * m.attn_qk_pairs for m in busy)
    assert eng.metrics.summary()["selected_key_share"] == 1.0


def test_the_engine_builds_a_latent_and_an_index_pool_a_sublayer(
        float32_run):
    _, eng, _, _ = float32_run
    # a latent pool and an index pool a layer, no V
    assert [len(pools) for pools in eng._pools] == [2, 2, 2]
    assert {p.shape for p, _ in eng._pools} == {(24, 1, 128, 128)}
    assert {p.shape for _, p in eng._pools} == {(24, 1, 128, 128)}
    assert eng.model.kv_pool_widths() == (1, (128, 128))
    assert eng.model.attention_sublayers == (0, 1, 2)
    assert eng.model.indexed_layers == (0, 1, 2)
    assert eng.model.expert_layers == (1, 2)
    # at the published widths: 576 values in 640 lanes and 128 index
    # lanes, 2 bytes each: 1,536 B a token and layer
    full = decoder_from_config(harness.load_json(
        "configs", "deepseek-v3.2-exp.json"))
    assert full.kv_pool_widths() == (1, (640, 128))
    assert full.kinds == ("latent_dense",) + ("latent_experts",) * 4
    assert full.num_kv_heads == 1 and full.num_q_heads == 128


def test_the_device_counts_the_keys_the_rule_selects(float32_run):
    """`StepMetrics.attn_keys_attended`, from the device, is the sum
    over query rows of min(16, visible keys) times the three sublayers,
    on decode-only, chunk-only and MIXED steps alike; the expert pairs
    add up beside it."""
    _, eng, _, _ = float32_run
    steps = eng.metrics.steps
    busy = [m for m in steps if m.decode_tokens or m.prefill_tokens]
    assert any(m.decode_tokens and m.prefill_tokens for m in busy)
    first = busy[0]                    # the first chunk, 32 tokens at 0
    assert first.attn_qk_pairs == 32 * 33 // 2
    assert first.attn_keys_attended == 3 * (16 * 17 // 2 + 16 * 16)
    for m in busy:
        tokens = m.decode_tokens + m.prefill_tokens
        assert (m.expert_pairs_local + m.expert_pairs_absent) == 2 * 3 * tokens
        assert m.expert_pairs_zero == 0
        assert 0 < m.attn_keys_attended <= 3 * min(m.attn_qk_pairs,
                                                   16 * tokens)
        assert m.ragged_grid_steps == 0     # a list is attended: no page walked
    summary = eng.metrics.summary()
    assert 0.1 < summary["selected_key_share"] < 0.6
    assert 0.1 < summary["local_pair_share"] < 0.45


def test_the_count_on_every_step_is_the_hosts_own(served):
    """A mixed run, step by step: the device's count against the sum
    over query rows of min(index_topk, keys seen), from the lengths the
    requests had."""
    model, params, _ = served
    eng = ServingEngine(model, params, EngineConfig(**ENGINE))
    reqs = [eng.add_request(p, SamplingParams(max_tokens=5))
            for p in _prompts(4, 50, 21)]
    while eng.scheduler.has_work():
        before = {r.request_id: r.computed_tokens for r in reqs}
        m = eng.step()
        want = 0
        for r in reqs:
            a, b = before[r.request_id], r.computed_tokens
            want += sum(min(16, t + 1) for t in range(a, b))
        assert m.attn_keys_attended == 3 * want, m.step
        assert m.attn_keys_selected == want
        # the list form reads the chosen rows and no other
        assert m.attn_rows_read == want
    assert eng.metrics.summary()["rows_read_per_key_attended"] == 1.0


def test_the_dispatch_span_carries_the_rows_the_attention_reads(served):
    """`engine.step.dispatch` has ``attn_rows``, the rule's count of a
    step with a selector (what `StepMetrics.attn_rows_read` holds), and
    the counter beside the attended pairs adds up to the same ratio."""
    from attention_tpu import obs

    model, params, _ = served
    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        eng = ServingEngine(model, params, EngineConfig(**ENGINE))
        for p in _prompts(3, 40, 21):
            eng.add_request(p, SamplingParams(max_tokens=3))
        while eng.scheduler.has_work():
            eng.step()
        spans = [e["fields"] for e in obs.events()
                 if e["name"] == "engine.step.dispatch"]
        keys = {s["labels"]["which"]: s["value"]
                for s in obs.REGISTRY.snapshot()["counters"]
                if s["name"] == "engine.attention.keys"}
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    busy = [m for m in eng.metrics.steps
            if m.decode_tokens or m.prefill_tokens]
    assert len(spans) == len(busy) > 0
    for span, m in zip(spans, busy):
        assert span["attn_rows"] == m.attn_rows_read == m.attn_keys_selected
    assert keys["rows_read"] == keys["attended"] == 3 * sum(
        m.attn_rows_read for m in busy)


def test_a_prefix_hit_brings_back_latents_and_index_keys(served):
    """300 shared tokens are two whole pages in the prefix cache, of
    BOTH pools: the second request computes what follows them and is
    served the logits it gets without the cache (a row that chose its
    16 keys among the cached ones needs their index keys back)."""
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


def test_the_dispatch_span_names_the_sparse_sublayers(served):
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
    assert seen and all(
        f["sparse_layers"] == 3 and f["index_topk"] == 16
        and f["expert_layers"] == 2 and "latent_layers" not in f
        for f in seen)


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
        with pytest.raises(LatentCacheUnsupportedError, match="index pool"):
            refused()
    with pytest.raises(MeshConfigError, match="shards pages, not heads"):
        ServingEngine(model, params, EngineConfig(mesh_shards=2, **ENGINE))
    with pytest.raises(ValueError, match="packed step"):
        model.init_caches(1, 64)


@pytest.mark.parametrize("change, match", [
    ({"topk_method": "greedy"}, "topk_method"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_scaling": dict(YARN, type="linear")}, "rope_scaling.type"),
    ({"rope_scaling": dict(YARN, mscale=0.5)}, "rope_scaling.mscale"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"sliding_window": 128}, "sliding_window"),
])
def test_what_no_configuration_runs_is_refused_by_its_key(change, match):
    with pytest.raises(ValueError, match=match):
        decoder_from_config(dict(CONFIG, **change))


def test_a_latent_block_without_its_selector_is_refused_by_the_key():
    config = {k: v for k, v in CONFIG.items() if k != "index_topk"}
    with pytest.raises(ValueError, match="index_topk"):
        decoder_from_config(config)


# -- the indexer's scores against the equation -------------------------------

def test_the_layers_choice_is_the_equations(reference):
    """`LatentAttention` without a cache (the expanded form): the keys
    each position attends are those of I[t, s] = sum_j w_tj relu(q_tj .
    k_s), written out here from the layer's own parameters."""
    layer = LatentAttention(
        num_heads=8, q_lora_rank=32, kv_lora_rank=16, nope_dim=16,
        rope_dim=8, v_dim=16, dtype=jnp.float32, scale_latents=False,
        index_heads=4, index_dim=16, index_topk=5)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 40, 64)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    params["index_k_norm"]["bias"] = jnp.asarray(
        rng.standard_normal(16) * 0.2, jnp.float32)
    _, sown = layer.apply({"params": params}, x, mutable=["attention_stats"])
    (attended,) = sown["attention_stats"]["keys_attended"]
    assert int(attended) == sum(min(5, t + 1) for t in range(40))
    # the equation, in NumPy
    f = lambda a: np.asarray(a, np.float64)          # noqa: E731
    xs = f(x[0])
    c_q = xs @ f(params["q_a_proj"]["kernel"])
    c_q = c_q / np.sqrt((c_q ** 2).mean(-1, keepdims=True) + 1e-6)
    q = (c_q @ f(params["index_q_proj"]["kernel"])).reshape(40, 4, 16)
    k = xs @ f(params["index_k_proj"]["kernel"])
    k = (k - k.mean(-1, keepdims=True))
    k = (k / np.sqrt((k ** 2).mean(-1, keepdims=True) + 1e-6)
         * f(params["index_k_norm"]["scale"])
         + f(params["index_k_norm"]["bias"]))
    freq = 1e4 ** (-np.arange(4) / 4)
    ang = np.arange(40)[:, None] * freq

    def rope(a):
        lo, hi = a[..., :4], a[..., 4:8]
        c, s = np.cos(ang), np.sin(ang)
        if a.ndim == 3:
            c, s = c[:, None], s[:, None]
        return np.concatenate([lo * c - hi * s, lo * s + hi * c, a[..., 8:]],
                              axis=-1)

    q, k = rope(q), rope(k)
    w = xs @ f(params["index_w_proj"]["kernel"]) * (4 * 16) ** -0.5
    scores = np.einsum("tj,tjs->ts", w,
                       np.maximum(np.einsum("tjd,sd->tjs", q, k), 0.0))
    from attention_tpu.models.latent_attention import chosen_keys

    got = np.asarray(chosen_keys(jnp.asarray(scores, jnp.float32)[None], 5))
    for t in range(40):
        order = np.argsort(-scores[t, :t + 1], kind="stable")
        want = np.zeros(40, bool)
        want[order[:5]] = True
        np.testing.assert_array_equal(got[0, t], want)


# -- the router --------------------------------------------------------------

def test_the_router_is_the_written_out_loop():
    """Groups, a choice-only bias away from zero, normalised weights
    and the scale, token by token in Python."""
    rng = np.random.default_rng(7)
    tokens, dim, experts, groups, top_groups, top_k = 50, 24, 16, 4, 2, 3
    x = rng.standard_normal((tokens, dim)).astype(np.float32)
    router = rng.standard_normal((dim, experts)).astype(np.float32) * 0.4
    bias = rng.standard_normal(experts).astype(np.float32) * 0.5
    chosen, weight = sigmoid_top_k(
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(bias), top_k=top_k,
        scale=2.5, groups=groups, top_groups=top_groups)
    scores = 1 / (1 + np.exp(-(x.astype(np.float64) @ router)))
    size = experts // groups
    for t in range(tokens):
        choice = scores[t] + bias
        marks = [np.sort(choice[g * size:(g + 1) * size])[-2:].sum()
                 for g in range(groups)]
        keep = np.argsort(-np.asarray(marks), kind="stable")[:top_groups]
        allowed = [e for e in range(experts) if e // size in keep]
        best = sorted(allowed, key=lambda e: (-choice[e], e))[:top_k]
        assert sorted(np.asarray(chosen[t]).tolist()) == sorted(best)
        picked = scores[t][np.asarray(chosen[t])]
        np.testing.assert_allclose(
            np.asarray(weight[t]), 2.5 * picked / picked.sum(), rtol=1e-5)
    # the bias chose: without it the sets differ somewhere
    plain, _ = sigmoid_top_k(
        jnp.asarray(x), jnp.asarray(router), jnp.zeros(experts), top_k=top_k,
        scale=2.5, groups=groups, top_groups=top_groups)
    assert (np.sort(np.asarray(plain), 1)
            != np.sort(np.asarray(chosen), 1)).any()


# -- the share ---------------------------------------------------------------

E, HELD, TOP_K, DIM, HIDDEN, GROUPS = 16, 4, 3, 64, 48, 4


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """THE SHARE TEST: the parts that all four shares of 4 experts
    give, with the shared expert (which every chip computes whole)
    counted ONCE, are the uncut reference's expert sublayer."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 24, DIM)), jnp.float32)

    def layer(share, held=HELD):
        return GatedExperts(
            num_experts=E, held=held, share=share, top_k=TOP_K,
            hidden=HIDDEN, scale=2.5, router="sigmoid", groups=GROUPS,
            top_groups=2, dtype=jnp.float32)

    params = layer(0, held=E).init(jax.random.PRNGKey(1), x)["params"]
    params["router_bias"] = jnp.asarray(rng.standard_normal(E) * 0.3,
                                        jnp.float32)
    shared = {n: {"kernel": jnp.asarray(
        rng.standard_normal(s) * s[0] ** -0.5, jnp.float32)}
        for n, s in (("gate_proj", (DIM, HIDDEN)), ("up_proj", (DIM, HIDDEN)),
                     ("down_proj", (HIDDEN, DIM)))}
    sizes = {"share": 0, "shares": 1, "top_k": TOP_K, "groups": GROUPS,
             "top_groups": 2, "scale": 2.5, "capacity": 24, "block": 64,
             "eps": 1e-6}
    with jax.default_matmul_precision("highest"):
        # the uncut sublayer: x + experts(N(x)) + shared(N(x)), less x
        whole, _, over = reference._expert_sublayer(
            params, shared, jnp.ones(DIM), x[0] + 0,
            sizes=tuple(sorted(sizes.items())), low_precision=False)
        assert int(over) <= 0
        whole = whole - x[0]
        y = reference._rms_norm(x[0], jnp.ones(DIM), 1e-6)
        once = reference._swiglu(shared, y, sizes=sizes,
                                 quant=lambda t: t)
        assert np.abs(once).max() > 0.1
        total, counted = once, 0
        for share in range(E // HELD):
            cut = slice(share * HELD, (share + 1) * HELD)
            mine = dict(params, **{k: params[k][cut] for k in (
                "experts_gate", "experts_up", "experts_down")})
            out, sown = layer(share).apply({"params": mine}, y[None],
                                           mutable=["expert_stats"])
            total = total + out[0]
            (pairs,) = sown["expert_stats"]["pairs"]
            local, (absent, reached, zeros) = pairs[:HELD], pairs[HELD:]
            assert int(local.sum() + absent) == 24 * TOP_K
            assert int(zeros) == 0
            assert int(reached) == int((local > 0).sum())
            counted += int(local.sum())
        assert counted == 24 * TOP_K
        np.testing.assert_allclose(total, whole, atol=5e-5)


# -- YaRN --------------------------------------------------------------------

def test_yarns_frequencies_are_the_closed_form():
    """Pair by pair at the published numbers (64 rope lanes, theta
    10,000, factor 40, 4,096 original positions, beta 32 / 1), and the
    rotation they give at three positions beyond 4,096."""
    scaling = YarnScaling(40.0, 4096, 32.0, 1.0)
    got = np.asarray(yarn_inv_freq(64, 10000.0, scaling), np.float64)

    def pair(turns):
        return 64 * math.log(4096 / (turns * 2 * math.pi)) / (
            2 * math.log(10000.0))

    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (10, 23)
    for i in range(32):
        f = 10000.0 ** (-2 * i / 64)
        keep = 1 - min(max((i - low) / (high - low), 0.0), 1.0)
        np.testing.assert_allclose(got[i], f / 40 * (1 - keep) + f * keep,
                                   rtol=1e-6)
    assert got[0] == 1.0 and abs(got[31] * 40 / 10000.0 ** (-62 / 64) - 1) < 1e-6
    assert abs(yarn_mscale(40.0, 1.0) - 1.3688879) < 1e-6
    assert yarn_mscale(40.0, 0.0) == 1.0 and yarn_mscale(1.0, 1.0) == 1.0
    from attention_tpu.ops.rope import apply_rope

    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 64)),
                    jnp.float32)
    positions = jnp.asarray([4097, 20000, 49999])
    out = np.asarray(apply_rope(x, positions, 10000.0, scaling), np.float64)
    ang = np.asarray(positions, np.float64)[:, None] * got
    a, b = np.asarray(x, np.float64)[:, :32], np.asarray(x, np.float64)[:, 32:]
    want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           a * np.sin(ang) + b * np.cos(ang)], axis=1)
    # float32 angles of up to 5e4 radians: 4e-3 of a turn's phase
    np.testing.assert_allclose(out, want, atol=2e-2)
    plain = np.asarray(apply_rope(x, positions, 10000.0), np.float64)
    assert np.abs(plain - out).max() > 0.5
