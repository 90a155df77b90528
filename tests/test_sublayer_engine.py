"""A decoder of single-sublayer blocks (Mamba-2 state-space mixers,
latent sparse experts as one share of four, one GQA attention layer)
through `ServingEngine`: logits against the plain reference of
`benchmark/configs/nemotron-3-super-120b_reference.py`, and what the
step reports of its expert layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu import obs
from attention_tpu.engine import EngineConfig, SamplingParams, ServingEngine
from attention_tpu.models import decoder_from_config
from attention_tpu.ops.ragged_paged import (
    packed_bucket,
    recommended_q_tile,
    tile_tokens,
)
from benchmark import harness

VOCAB = 97
CONFIG = {
    "vocab_size": VOCAB, "hidden_size": 64, "num_hidden_layers": 7,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "hybrid_override_pattern": "MEM*EMEMEM", "intermediate_size": 48,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "use_conv_bias": True,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "n_routed_experts": 4, "expert_share": {"index": 1, "of": 4},
    "num_experts_per_tok": 4, "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
    "n_shared_experts": 1, "routed_scaling_factor": 5.0,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "norm_eps": 1e-5, "rope_theta": 10000, "attention_rotary": False,
    "sliding_window": None, "torch_dtype": "float32",
}
ENGINE = dict(num_pages=32, page_size=128, max_seq_len=512,
              max_decode_batch=3, max_prefill_rows=1, prefill_chunk=32,
              token_budget=64)
# Model and reference both compute in float32, the reference at the
# highest matmul precision, token by token and expert by expert, the
# program in chunks, pages and one grouped product: they differ by
# rounding.  A top-4 choice that flips at the margin would move a
# logit by an expert's whole part (1e-1); none does at these seeds.
# Leaving out the conv's bias, D, the scaling factor or the shared
# expert moves the logits by 1e-2 to 1 (the last test).
TOL = 3e-4


@pytest.fixture(scope="module")
def served():
    reference = harness.load_module("configs",
                                    "nemotron-3-super-120b_reference")
    model = decoder_from_config(CONFIG)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.jit(lambda k: reference.init_params(shapes, k))(
        jax.random.PRNGKey(3))
    return model, params, reference


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=n).tolist() for n in lengths]


def _serve(model, params, prompts, max_tokens, poisoned=(), **engine):
    """Serve ``prompts`` together; per request its tokens and the
    logits row each was sampled from.  The steps in ``poisoned`` (by
    count of fetches) hand the host NaN logits."""
    eng = ServingEngine(model, params, EngineConfig(**dict(ENGINE, **engine)))
    fetch, fetches = eng._fetch_logits, iter(range(10**6))

    def poisoning(*args):
        out = fetch(*args)
        return np.full_like(out, np.nan) if next(fetches) in poisoned else out

    eng._fetch_logits = poisoning
    rows = {}
    sample = eng._sample

    def recording(req, logits_row):
        rows.setdefault(req.request_id, []).append(logits_row.copy())
        return sample(req, logits_row)

    eng._sample = recording
    reqs = [eng.add_request(p, SamplingParams(max_tokens=max_tokens),
                            request_id=f"r{i}", arrival=i)
            for i, p in enumerate(prompts)]
    eng.run(max_steps=400)
    return eng, reqs, [np.stack(rows[r.request_id]) for r in reqs]


def _check(reference, params, prompts, reqs, logits, config=CONFIG):
    for prompt, req, got in zip(prompts, reqs, logits):
        want = reference.served_logits(
            params, config, prompt, req.output_tokens, pad_to=384,
            rows=len(req.output_tokens))
        np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("group", [1, 9, 16])
def test_a_chunks_query_tile_is_never_under_8_tokens(group):
    """A GQA group that is a multiple of 8 let the tile of a short last
    chunk be 1, 2 or 4 tokens, each a compiled program at every packed
    width; the groups the other configurations have (1, 9) were held to
    8 by the sublane rule, and nothing moves for them."""
    tiles = {n: recommended_q_tile(n, group) for n in range(1, 600)}
    assert all(t >= max(n, 8) for n, t in tiles.items() if n > 1)
    before = {n: tile_tokens(packed_bucket(n, minimum=1), group)
              for n in tiles}
    if group % 8:
        assert tiles == before
    else:
        assert tiles[1] == 1               # a decode-only step's tile
        assert {n for n in tiles if tiles[n] != before[n]} == {2, 3, 4}
        assert sorted(set(tiles.values())) == [
            1, 8, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512, 768]


def test_the_builder_reads_the_pattern_and_the_share(served):
    model, params, _ = served
    assert model.kinds == ("state_space", "sparse_experts", "state_space",
                           "attention", "sparse_experts", "state_space",
                           "sparse_experts")
    assert model.attention_layers == (3,)
    assert model.recurrent_layers == (0, 2, 5)
    assert model.expert_layers == (1, 4, 6)
    assert model.held_experts == 4
    assert model.recurrent_state_shapes() == ((8, 16, 16), (3, 8 * 16 + 64))
    experts = params["SublayerBlock_1"]["LatentExperts_0"]
    assert experts["router"].shape == (64, 16)          # all 16, 4 held
    assert experts["experts_up"].shape == (4, 32, 48)
    assert model.rope is False and model.norm_eps == 1e-5


def test_prefill_over_chunks_then_decode_matches_the_reference(served):
    """75 tokens = two chunks of 32 and a tail of 11, then 6 decodes,
    through the state slots and the paged KV."""
    model, params, reference = served
    prompts = _prompts(0, 75)
    eng, reqs, logits = _serve(model, params, prompts, 6)
    assert len(reqs[0].output_tokens) == 6
    _check(reference, params, prompts, reqs, logits)
    assert eng.allocator.state_slots_in_use == 0 == eng.pool.used_pages
    # one K / V pair, three state pairs, nothing for the experts
    assert [p and len(p) for p in eng._pools] == [2, None, 2, 2, None, 2,
                                                  None]
    assert [p is q for p, q in zip(eng.page_pools(), eng._pools[3])] == [
        True, True]
    assert eng._pools[0][0].shape == (5, 8, 16, 16)


def test_requests_of_free_lengths_in_one_batch(served):
    model, params, reference = served
    prompts = _prompts(1, 70, 40, 33, 9)
    eng, reqs, logits = _serve(model, params, prompts, 5)
    mixed = [m for m in eng.metrics.steps
             if m.num_decode_reqs and m.num_prefill_reqs]
    assert mixed and max(m.num_decode_reqs for m in eng.metrics.steps) >= 2
    _check(reference, params, prompts, reqs, logits)


def test_preempt_and_resume_recomputes_from_token_zero(served):
    model, params, reference = served
    prompts = _prompts(3, 120, 120, 120)
    eng, reqs, logits = _serve(
        model, params, prompts, 12, num_pages=3, max_seq_len=256,
        max_decode_batch=4, max_prefill_rows=2, token_budget=80,
        watermark_pages=0)
    assert eng.scheduler.num_preemptions >= 1
    assert all(len(r.output_tokens) == 12 for r in reqs)
    _check(reference, params, prompts, reqs, logits)
    assert eng.allocator.state_slots_in_use == 0 == eng.pool.used_pages


def test_a_retry_after_nan_logits_recomputes_the_state(served):
    model, params, reference = served
    prompts = _prompts(6, 75, 40)
    eng, reqs, logits = _serve(model, params, prompts, 6,
                               poisoned={2, 9, 10})
    assert eng.nonfinite_events >= 3
    assert all(r.preemptions >= 1 for r in reqs)
    assert all(len(r.output_tokens) == 6 for r in reqs)
    _check(reference, params, prompts, reqs, logits)


def test_the_step_reports_its_expert_pairs(served):
    """Every real token of a step takes top-4 of 16 experts in each of
    the 3 expert layers; pads take none."""
    model, params, _ = served
    obs.enable()
    obs.reset()
    try:
        eng, _, _ = _serve(model, params, _prompts(8, 50, 21), 4)
        pairs = obs.counter("engine.experts.pairs")
        counted = {where: pairs.value(where=where)
                   for where in ("local", "absent")}
        dispatched = [e["fields"] for e in obs.events()
                      if e["name"] == "engine.step.dispatch"]
    finally:
        obs.reset()
        obs.disable()
    busy = [m for m in eng.metrics.steps
            if m.decode_tokens or m.prefill_tokens]
    for m in busy:
        tokens = m.decode_tokens + m.prefill_tokens
        assert m.expert_pairs_local + m.expert_pairs_absent == 3 * 4 * tokens
        assert 0 < m.expert_load_max <= 3 * tokens
        assert 0 < m.experts_reached <= 3 * 4
    assert any(m.pad_tokens for m in busy)
    summary = eng.metrics.summary()
    local = sum(m.expert_pairs_local for m in busy)
    absent = sum(m.expert_pairs_absent for m in busy)
    assert summary["local_pair_share"] == round(local / (local + absent), 4)
    assert 0.1 < summary["local_pair_share"] < 0.45      # 1/4 at an even router
    assert summary["expert_load_max_over_mean"] >= 1.0
    assert counted == {"local": local, "absent": absent}
    # the dispatch span says what kinds of layer the step ran
    assert len(dispatched) == len(busy)
    assert all(f["expert_layers"] == 3 and f["state_layers"] == 3
               and f["recurrent_tokens"] >= f["recurrent_slot_steps"] >= 1
               for f in dispatched)


@pytest.mark.parametrize("change", [
    {"conv_bias": 0.0},                       # the conv's bias
    {"routed_scaling_factor": 1.0},           # g without its 5
    {"n_shared_experts": 0},                  # no shared expert
    {"expert_share": {"index": 0, "of": 4}},  # another share's experts
    {"D": 0.0},                               # the scan's skip
    {"attention_rotary": True},               # rotary on the attention
], ids=["conv_bias", "scale", "shared", "share", "skip", "rotary"])
def test_the_tolerance_catches_a_part_left_out(served, change):
    model, params, reference = served
    (prompt,) = _prompts(6, 40)
    (key, value), = change.items()
    params = jax.tree_util.tree_map(lambda x: x, params)
    layers = [layer for block in params.values() if isinstance(block, dict)
              for layer in block.values() if isinstance(layer, dict)]
    if key in ("conv_bias", "D"):             # a parameter, zeroed
        broken = model
        for layer in layers:
            if key in layer:
                layer[key] = jnp.full_like(layer[key], value)
    else:                                     # a key of the configuration
        broken = decoder_from_config(dict(CONFIG, **change))
        for layer in layers:
            if key == "n_shared_experts":
                layer.pop("shared_expert", None)
    _, (req,), (logits,) = _serve(broken, params, [prompt], 4)
    want = reference.served_logits(
        served[1], CONFIG, prompt, req.output_tokens, pad_to=128, rows=4)
    assert np.abs(logits - want).max() > 30 * TOL, np.abs(logits - want).max()


@pytest.mark.parametrize("config, match", [
    (dict(CONFIG, hybrid_override_pattern="MEX*EME"), r"\['X'\]"),
    (dict(CONFIG, num_hidden_layers=11), "must name 11 layers"),
    (dict(CONFIG, mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(CONFIG, n_group=2), "n_group"),
    (dict(CONFIG, sliding_window=64), "sliding_window"),
    # what no configuration of the benchmark runs is not built: the
    # dense feed-forward letter, a conv without bias, raw router weights
    (dict(CONFIG, hybrid_override_pattern="M-*EMEM"), r"\['-'\]"),
    (dict(CONFIG, use_conv_bias=False), "use_conv_bias"),
    (dict(CONFIG, norm_topk_prob=False), "norm_topk_prob"),
])
def test_the_builder_names_the_key_it_cannot_build(config, match):
    with pytest.raises(ValueError, match=match):
        decoder_from_config(config)
