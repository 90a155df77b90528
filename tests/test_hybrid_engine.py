"""A decoder with recurrent (gated-delta-rule) layers beside attention
layers through `ServingEngine`: logits against the plain reference of
`benchmark/configs/olmo-hybrid-7b_reference.py`, the state slots' life
cycle, and the refusals of everything that knows only pages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.engine import (
    EngineConfig,
    RecurrentStateUnsupportedError,
    RequestState,
    SamplingParams,
    ServingEngine,
    SnapshotManager,
    snapshot,
    synthetic_trace,
)
from attention_tpu.engine.sim import replay
from attention_tpu.models import decoder_from_config
from benchmark import harness

VOCAB = 97
CONFIG = {
    "post_norm": True, "qk_norm": True, "vocab_size": VOCAB, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 4,
    "num_attention_heads": 2, "num_key_value_heads": 2, "hidden_act": "silu",
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "torch_dtype": "float32",
}
ENGINE = dict(num_pages=32, page_size=128, max_seq_len=512,
              max_decode_batch=3, max_prefill_rows=1, prefill_chunk=32,
              token_budget=64)
# Model and reference both compute in float32, the reference at the
# highest matmul precision and token by token, the program in chunks
# and pages: they differ by rounding, 1e-5 on logits of magnitude 3 and
# up to 1e-4 on a prompt's first token for some chunkings.  Leaving out
# the factor 2 of beta, a tap of the convolution or the norms moves the
# logits by 1e-2 to 1 (the last test); a bfloat16 state moves the
# kernel's output by 1e-3 (`tests/test_gated_delta.py`).
TOL = 3e-4


@pytest.fixture(scope="module")
def hybrid():
    reference = harness.load_module("configs", "olmo-hybrid-7b_reference")
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
    count of fetches) hand the host NaN logits, through the seam the
    chaos ``nan`` fault wraps."""
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


def _reference_logits(reference, params, prompt, served):
    return reference.served_logits(params, CONFIG, prompt, served,
                                   pad_to=384, rows=len(served))


def test_prefill_over_chunks_then_decode_matches_the_reference(hybrid):
    """75 tokens = two chunks of 32 and a tail of 11, then 6 decodes."""
    model, params, reference = hybrid
    (prompt,) = _prompts(0, 75)
    eng, (req,), (logits,) = _serve(model, params, [prompt], 6)
    assert len(req.output_tokens) == 6
    want = _reference_logits(reference, params, prompt, req.output_tokens)
    np.testing.assert_allclose(logits, want, atol=TOL)
    assert eng.allocator.state_slots_in_use == 0 == eng.pool.used_pages


def test_requests_interleaved_in_one_step_do_not_mix_states(hybrid):
    model, params, reference = hybrid
    prompts = _prompts(1, 70, 40, 33)
    eng, reqs, logits = _serve(model, params, prompts, 5)
    mixed = [m for m in eng.metrics.steps
             if m.num_decode_reqs and m.num_prefill_reqs]
    assert mixed and max(m.num_decode_reqs for m in eng.metrics.steps) >= 2
    for prompt, req, got in zip(prompts, reqs, logits):
        want = _reference_logits(reference, params, prompt,
                                 req.output_tokens)
        np.testing.assert_allclose(got, want, atol=TOL)


def test_preempt_and_resume_gives_the_logits_of_an_undisturbed_run(hybrid):
    """Three pages for three requests that each grow into a second
    page: the youngest are preempted, their state slots go back, and on
    readmission they start from a zero state and recompute."""
    model, params, reference = hybrid
    prompts = _prompts(3, 120, 120, 120)
    eng, reqs, logits = _serve(
        model, params, prompts, 12, num_pages=3, max_seq_len=256,
        max_decode_batch=4, max_prefill_rows=2, token_budget=80,
        watermark_pages=0)
    assert eng.scheduler.num_preemptions >= 1
    assert reqs[0].preemptions == 0 < sum(r.preemptions for r in reqs)
    for prompt, req, got in zip(prompts, reqs, logits):
        assert len(req.output_tokens) == 12
        want = _reference_logits(reference, params, prompt,
                                 req.output_tokens)
        np.testing.assert_allclose(got, want, atol=TOL)
    assert eng.allocator.state_slots_in_use == 0 == eng.pool.used_pages


def test_a_retry_after_nan_logits_recomputes_the_state(hybrid):
    """NaN logits at a prompt's last chunk (fetch 2: the state has
    taken the chunk) and at decode steps (the state has taken the
    token): the retry must not apply either a second time."""
    model, params, reference = hybrid
    prompts = _prompts(6, 75, 40)
    eng, reqs, logits = _serve(model, params, prompts, 6,
                               poisoned={2, 9, 10})
    assert eng.nonfinite_events >= 3
    assert all(r.preemptions >= 1 for r in reqs)
    for prompt, req, got in zip(prompts, reqs, logits):
        assert len(req.output_tokens) == 6 == len(got)
        want = _reference_logits(reference, params, prompt,
                                 req.output_tokens)
        np.testing.assert_allclose(got, want, atol=TOL)
    assert eng.allocator.state_slots_in_use == 0 == eng.pool.used_pages


#: what the parent of the in-place append (b9da22c) served for
#: `test_replay_gives_the_parents_recorded_tokens`
_PARENT_TOKENS = {
    "req-0": [87, 71, 69, 52, 12, 43], "req-1": [12, 90, 7, 77, 22, 5],
    "req-2": [2, 2, 49, 87, 60, 78], "req-3": [89, 60, 12, 71, 4, 28],
    "req-4": [43, 39, 7, 25, 94, 68],
}


def test_replay_gives_the_parents_recorded_tokens(hybrid):
    """The step donates the K / V, state and convolution-tail pools
    and writes them in place: the same numbers at the same addresses,
    so the same tokens as before, and no pool of the step before
    survives it."""
    model, params, _ = hybrid
    trace = synthetic_trace(5, vocab=VOCAB, seed=13, max_tokens=6,
                            prompt_len_min=4, prompt_len_max=80)
    eng = ServingEngine(model, params, EngineConfig(**ENGINE))
    pools = []
    step = eng.step

    def watched():
        held = jax.tree.leaves(eng._pools)
        assert len(held) == 2 * model.depth
        out = step()
        pools.append((held, out))
        return out

    eng.step = watched
    _, out = replay(eng, trace)
    assert out == _PARENT_TOKENS
    busy = [held for held, m in pools if m.decode_tokens or m.prefill_tokens]
    assert busy and all(a.is_deleted() for held in busy for a in held)


def test_a_repeated_prompt_reports_no_prefix_cached_tokens(hybrid):
    model, params, _ = hybrid
    (prompt,) = _prompts(4, 300)               # two whole pages
    eng = ServingEngine(model, params, EngineConfig(**ENGINE))
    outs = []
    for _ in range(2):
        req = eng.add_request(prompt, SamplingParams(max_tokens=3))
        eng.run(max_steps=100)
        assert req.prefix_cached_tokens == 0
        outs.append(req.output_tokens)
    assert outs[0] == outs[1]
    assert eng.allocator.cached_pages == 0 == eng.allocator.prefix_hits
    assert eng.allocator.peek_prefix(prompt) == 0


def test_state_slots_follow_the_request_life_cycle(hybrid):
    """As many slots as a step has rows; admission waits for one;
    cancel, time-out and finish each give theirs back."""
    model, params, _ = hybrid
    eng = ServingEngine(model, params, EngineConfig(**dict(
        ENGINE, max_decode_batch=2)))
    assert eng.allocator.state_slots == 3
    state, tail = eng._pools[0]
    assert state.shape == (4, 2, 16, 32)
    assert tail.shape == (4, 3, 2 * (16 + 16 + 32))
    # three layers keep a state a request, one keeps K and V pages
    assert [p[0].shape == state.shape for p in eng._pools] == [
        True, True, True, False]
    assert [p.shape for p in eng.page_pools()] == [(32, 2, 128, 32)] * 2
    prompts = _prompts(5, 20, 20, 20, 20)
    reqs = [eng.add_request(p, SamplingParams(max_tokens=40),
                            request_id=f"r{i}", arrival=0,
                            deadline_step=12 if i == 1 else None)
            for i, p in enumerate(prompts)]
    for _ in range(5):
        eng.step()
    assert sorted(r.state_slot for r in reqs[:3]) == [0, 1, 2]
    assert reqs[3].state is RequestState.WAITING       # no slot left
    assert reqs[3].state_slot == -1
    eng.cancel("r0")
    assert reqs[0].state_slot == -1
    eng.step()
    assert reqs[3].state_slot == 0                      # took the freed one
    while reqs[1].state is not RequestState.TIMED_OUT:
        eng.step()
    assert reqs[1].state_slot == -1
    eng.run(max_steps=300)
    assert all(r.state_slot == -1 for r in reqs)
    assert eng.allocator.state_slots_in_use == 0


def _snapshot_manager(eng, tmp_path):
    SnapshotManager(eng, str(tmp_path / "snaps"))


def _snapshot_save(eng, tmp_path):
    snapshot.save(eng, str(tmp_path / "one.snap"))


def _prefix_export(eng, tmp_path):
    from attention_tpu.prefixstore.adapter import export_chain, import_chain

    with pytest.raises(RecurrentStateUnsupportedError):
        import_chain(eng, list(range(130)), now=0)
    export_chain(eng, list(range(130)), [0], now=0)


def _handoff_export(eng, tmp_path):
    from attention_tpu.fleet.handoff import export_handoff

    req = eng.add_request(list(range(1, 60)), SamplingParams(max_tokens=2))
    export_handoff(eng, req, {})


@pytest.mark.parametrize("feature", [
    _snapshot_manager, _snapshot_save, _prefix_export, _handoff_export])
def test_pages_only_features_refuse_a_model_with_recurrent_state(
        hybrid, tmp_path, feature):
    model, params, _ = hybrid
    eng = ServingEngine(model, params, EngineConfig(**ENGINE))
    with pytest.raises(RecurrentStateUnsupportedError,
                       match="knows only KV pages"):
        feature(eng, tmp_path)


def test_mesh_engine_refuses_at_construction(hybrid):
    model, params, _ = hybrid
    with pytest.raises(RecurrentStateUnsupportedError):
        ServingEngine(model, params, EngineConfig(**ENGINE, mesh_shards=2))
    with pytest.raises(RecurrentStateUnsupportedError):
        snapshot.restore("nowhere", model, params)


@pytest.mark.parametrize("change", [
    {"linear_allow_neg_eigval": False},           # beta without its 2
    {"linear_conv_kernel_dim": 3},                # a tap of the conv
    {"post_norm": False},                         # a pre-norm block
    {"qk_norm": False},                           # q and k as projected
], ids=["beta", "conv", "post_norm", "qk_norm"])
def test_the_tolerance_catches_a_part_left_out(hybrid, change):
    """The same weights through a model that leaves one part of the
    layer out are further from the reference than the tolerance."""
    model, params, reference = hybrid
    (prompt,) = _prompts(6, 40)
    broken = decoder_from_config(dict(CONFIG, **change))
    if "linear_conv_kernel_dim" in change:
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x[1:] if "_conv" in jax.tree_util.keystr(path)
            else x, params)
    _, (req,), (logits,) = _serve(broken, params, [prompt], 4)
    want = reference.served_logits(
        hybrid[1], CONFIG, prompt, req.output_tokens, pad_to=128, rows=4)
    assert np.abs(logits - want).max() > 30 * TOL, np.abs(logits - want).max()


# Written from the tree at commit f018d1b (before layer kinds existed):
# `benchmark/runners/serve.py:build_model` on the starcoder2-7b
# configuration at the toy sizes below, every leaf float32.
_ATTN = {"k_proj']['kernel": (64, 1, 32), "o_proj']['kernel": (64, 64),
         "q_proj']['kernel": (64, 2, 32), "v_proj']['kernel": (64, 1, 32)}
PARENT_TREE = {
    "['Dense_0']['kernel']": (64, 64),
    "['Embed_0']['embedding']": (64, 64),
    "['RMSNorm_0']['scale']": (64,),
    **{f"['TransformerBlock_{i}']['GQASelfAttention_0']['{name}']": shape
       for i in range(2) for name, shape in _ATTN.items()},
    **{f"['TransformerBlock_{i}']{leaf}": shape for i in range(2)
       for leaf, shape in {
           "['MLP_0']['Dense_0']['kernel']": (64, 256),
           "['MLP_0']['Dense_1']['kernel']": (256, 64),
           "['RMSNorm_0']['scale']": (64,),
           "['RMSNorm_1']['scale']": (64,)}.items()},
}


def test_the_starcoder2_decoder_keeps_the_parents_parameter_tree():
    """Layer kinds, gated MLPs, QK-norm and post-norm are keys that are
    off for the model the benchmark already serves: its parameter tree
    (paths, shapes, dtypes) is the parent's, and the program's builder
    gives the very decoder the benchmark's runner builds."""
    from benchmark.runners import serve

    config = dict(harness.Cell("starcoder2-7b.chat-poisson").config,
                  hidden_size=64, intermediate_size=256,
                  num_attention_heads=2, num_key_value_heads=1, head_dim=32,
                  num_hidden_layers=2, vocab_size=64)
    model = serve.build_model(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    tree = {jax.tree_util.keystr(path): (leaf.shape, str(leaf.dtype))
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert tree == {k: (v, "float32") for k, v in PARENT_TREE.items()}
    assert decoder_from_config(config) == model
    assert model.kinds == ("full_attention",) * 2
    assert model.recurrent_layers == ()
