"""Window and full attention layers in ONE model, each kind with a page
space of its own, through `ServingEngine`, at a toy cut of
`benchmark/configs/trinity-mini.json` (window 160, pages of 128, chunks
of 64, contexts of 640-900 tokens: the window binds, pages are given
back, a prefix hit lands inside a band): logits against the plain
reference beside that file (a prefix hit, chunks, decode), what each
piece of the mathematics weighs, the shares adding up, the allocator's
invariants over the second page space, the builder and what it refuses
by name, and that a model with one kind of attention layer keeps the
parent's step buffer and pools."""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu import obs
from attention_tpu.engine import EngineConfig, SamplingParams, ServingEngine
from attention_tpu.engine.allocator import BlockAllocator
from attention_tpu.engine import engine as engine_mod
from attention_tpu.engine.engine import _band_pages, _qk_pairs
from attention_tpu.engine.errors import PageSpacesUnsupportedError
from attention_tpu.engine.request import Request
from attention_tpu.engine.scheduler import (
    ScheduledStep,
    split_step_buffer,
    step_buffer_len,
)
from attention_tpu.models import decoder_from_config
from attention_tpu.models.transformer import expert_feed_forward
from attention_tpu.ops.paged import OutOfPagesError, PagePool
from attention_tpu.ops.ragged_paged import (
    _ragged_paged_attention_jit,
    head_block,
    live_pages,
    span_tile_rows,
    work_items,
)
from benchmark import harness

VOCAB = 97
S, F = "sliding_attention", "full_attention"
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 5, "layer_types": [S, S, S, F] * 3,
    "served_layers": [0, 4, 5, 6, 7], "num_dense_layers": 1,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 4,
    "expert_share": {"index": 1, "of": 4}, "num_experts_per_tok": 3,
    "num_shared_experts": 1, "route_scale": 2.826, "route_norm": True,
    "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "num_expert_groups": 1, "num_limited_groups": 1, "sliding_window": 160,
    "rope_theta": 10000, "rope_scaling": None, "mup_enabled": True,
    "rms_norm_eps": 1e-5, "hidden_act": "silu", "tie_word_embeddings": False,
    "vocab_size": VOCAB, "qk_head_norm": True, "attention_gate": True,
    "sandwich_norm": True, "full_attention_rotary": False,
    "torch_dtype": "float32",
}
ENGINE = dict(num_pages=40, num_window_pages=16, page_size=128,
              max_seq_len=1024, max_decode_batch=3, max_prefill_rows=1,
              prefill_chunk=64, token_budget=72)
# Both compute in float32, the reference a block of rows at a time at
# the highest precision over every position, the program in chunks
# through two page tables: they differ by rounding (read: 1e-6).  A key
# more or less in one row's window moves its logits by far more.
TOL = 1e-4


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("configs", "trinity-mini_reference")


@pytest.fixture(scope="module")
def served(reference):
    model = decoder_from_config(CONFIG)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.jit(lambda k: reference.init_params(shapes, k))(
        jax.random.PRNGKey(3))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    # a selection bias away from zero, so that it shows in the choice
    # and must not show in the weights
    rng = np.random.default_rng(3)
    for block in params.values():
        if "experts" in block:
            bias = block["experts"]["router_bias"]
            block["experts"]["router_bias"] = jnp.asarray(
                rng.standard_normal(bias.shape) * 0.3, jnp.float32)
    return model, params


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=n).tolist() for n in lengths]


def _serve(model, params, prompts, max_tokens, eng=None, after_step=None,
           **engine):
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
    while eng.scheduler.has_work():
        eng.step()
        assert eng.current_step < 400
        if after_step is not None:
            after_step(eng)
    eng._sample = sample
    return eng, reqs, [np.stack(rows[r.request_id]) for r in reqs]


def _pad(n):
    return -(-(n + 8) // 128) * 128


def _held(req):
    return sum(1 for p in req.window_pages if p >= 0)


@pytest.fixture(scope="module")
def session(served):
    """A context of 640 tokens served once (its five pages cached, the
    window pages of the trailing two among them), then a turn on it
    beside a fresh prompt of 700 tokens, five tokens each; after every
    step the most window pages a running request holds."""
    model, params = served
    context, turn, fresh = _prompts(0, 640, 70, 700)
    most = [0]

    def watch(eng):
        for req in eng.scheduler.running:
            most[0] = max(most[0], _held(req))
            assert len(req.window_pages) == len(req.pages)

    eng, _, _ = _serve(model, params, [context], 1, after_step=watch)
    prompts = [context + turn, fresh]
    eng, reqs, logits = _serve(model, params, prompts, 5, eng=eng,
                               after_step=watch)
    return eng, prompts, reqs, logits, most[0]


def test_a_prefix_hit_chunks_and_decode_match_the_reference(
        served, reference, session):
    """The turn's request starts at position 640 on the cached pages:
    its first chunk's rows attend keys 481 and later in the window
    layers, from the two cached tail pages, and all 640 in the full
    layer; the fresh prompt walks ten chunks of 64 and then decodes,
    its band sliding past three pages."""
    _, params = served
    eng, prompts, reqs, logits, _ = session
    assert [r.prefix_cached_tokens for r in reqs] == [640, 0]
    for prompt, req, got in zip(prompts, reqs, logits):
        want = reference.served_logits(
            params, CONFIG, prompt, req.output_tokens,
            pad_to=_pad(len(prompt)), rows=8)
        np.testing.assert_allclose(got, want, atol=TOL)
    assert eng.allocator.window_pages_released >= 3
    steps = eng.metrics.steps
    assert sum(m.window_pages_released for m in steps) == (
        eng.allocator.window_pages_released)
    assert max(m.window_used_pages for m in steps) < max(
        m.used_pages for m in steps)


@pytest.mark.parametrize("left_out", [
    "fp8", "window_as_full", "rope_on_full", "no_gate", "no_shared",
    "no_experts"])
def test_a_piece_changed_is_far_outside_the_tolerance(served, reference,
                                                      left_out):
    """What the comparison above holds: the window (against every key
    attended), NoPE in the full layers, the output gate, the shared
    and the routed experts: each moves the reference's own logits by
    100 tolerances or more, and so does fp8."""
    _, params = served
    prompt = _prompts(0, 400)[0]
    exact, less = (reference.served_logits(
        params, CONFIG, prompt, [1, 2, 3, 4], pad_to=512, rows=4,
        low_precision=which) for which in (False, left_out))
    assert np.abs(less - exact).max() > 100 * TOL


@pytest.mark.parametrize("key", ["sandwich_norm", "qk_head_norm",
                                 "mup_enabled"])
def test_a_norm_or_a_scale_left_out_shows(served, reference, key):
    """The scales away from 1 (`init_params`): the four-norm block
    against two norms, the per-head q / k norm against none, the
    embedding's sqrt(hidden_size)."""
    _, params = served
    prompt = _prompts(1, 200)[0]
    exact, less = (reference.served_logits(
        params, config, prompt, [1, 2], pad_to=256, rows=2)
        for config in (CONFIG, dict(CONFIG, **{key: False})))
    assert np.abs(less - exact).max() > 100 * TOL


def test_a_request_holds_no_more_window_pages_than_its_bound(session):
    """Band = window + widest tile - 1 = 223 keys: decoding a request
    holds 3 window pages at most, in a whole chunk's step 4; every page
    comes back: after the run the window pool holds what the prefix
    cache holds, and an emptied cache leaves both pools empty."""
    eng, _, _, _, most = session
    alloc = eng.allocator
    assert (alloc.band, alloc.tail_blocks) == (160 + 64 - 1, 2)
    assert alloc.window_pages_bound() == 3
    assert alloc.window_pages_bound(64) == 4
    assert 3 <= most <= alloc.window_pages_bound(64)
    assert not eng.scheduler.running
    cached = [e.window_page for e in alloc._prefix.values()
              if e.window_page is not None]
    assert sorted(cached) == sorted(set(cached))
    assert eng.window_pool.used_pages == len(cached) > 0
    assert eng.pool.used_pages == alloc.cached_pages
    while alloc.evict_lru() is not None:
        pass
    assert eng.window_pool.free_pages == ENGINE["num_window_pages"]
    assert eng.pool.free_pages == ENGINE["num_pages"]


def test_a_freed_window_page_poisoned_with_nan_changes_no_logit(
        served, session):
    """Every window page a band slid past is taken out of circulation
    the moment it is given back and filled with NaN in every window
    layer's pools (a page handed out again would be read, rows of it
    not yet written among them, masked): a page below a band is never
    read, so the logits are the clean run's to the bit."""
    model, params = served
    _, prompts, _, clean, _ = session
    window = model.window_layers
    assert len(window) == 4
    eng = ServingEngine(model, params, EngineConfig(**dict(
        ENGINE, num_window_pages=40)))
    pool, release = eng.window_pool, eng.allocator.release_window
    hostages = []

    def hold(req):
        before = pool.free_pages
        released = release(req)
        # the pool hands out what came back last
        hostages.extend(pool.alloc(pool.free_pages - before))
        return released

    def poison(eng):
        if hostages:
            at = jnp.asarray(hostages)
            for i in window:
                eng._pools[i] = tuple(pool.at[at].set(jnp.nan)
                                      for pool in eng._pools[i])

    eng.allocator.release_window = hold
    _serve(model, params, [prompts[0][:640]], 1, eng=eng, after_step=poison)
    _, _, logits = _serve(model, params, prompts, 5, eng=eng,
                          after_step=poison)
    assert len(hostages) >= 3
    assert all(bool(jnp.isnan(eng._pools[window[0]][0][p]).all())
               for p in hostages)
    for got, want in zip(logits, clean):
        np.testing.assert_array_equal(got, want)
    assert eng.nonfinite_events == 0


def test_a_tail_gone_from_the_window_pool_is_no_hit_and_no_wrong_logit(
        served, reference):
    """The cached context loses ONE window page of its tail (the
    window pool's own eviction: the entry stays): the turn on it finds
    no prefix whose trailing window is there, recomputes from token 0
    and serves the reference's logits."""
    model, params = served
    context, turn = _prompts(5, 640, 40)
    eng, _, _ = _serve(model, params, [context], 1)
    entries = eng.allocator.cached_pages
    assert eng.allocator.peek_prefix(context + turn) == 5
    assert eng.allocator.evict_window_lru() is not None
    assert eng.allocator.cached_pages == entries
    assert eng.allocator.peek_prefix(context + turn) == 0
    _, (req,), (got,) = _serve(model, params, [context + turn], 3, eng=eng)
    assert req.prefix_cached_tokens == 0
    want = reference.served_logits(params, CONFIG, context + turn,
                                   req.output_tokens, pad_to=768, rows=8)
    np.testing.assert_allclose(got, want, atol=TOL)


# -- the allocator alone: pages of 4 tokens, a band of 6 keys ---------------

def _allocator(pages=32, window_pages=12, **kw):
    return BlockAllocator(PagePool(pages), 4, window_pool=PagePool(
        window_pages), band=6, **kw)


def _commit(alloc, tokens, now):
    """What a request that computed ``tokens`` leaves in the cache: its
    pages and, of the window space, what its band still held."""
    req = Request("r", tuple(tokens), SamplingParams())
    alloc.cover(req, len(tokens), for_decode=True)
    req.computed_tokens = len(tokens) - 1
    alloc.release_window(req)
    full = len(tokens) // 4
    alloc.commit_prefix(tokens, req.pages[:full], now=now,
                        window_pages=req.window_pages[:full])
    alloc.release(req)
    return full


def test_a_hit_is_as_long_as_the_window_pool_allows():
    """Prefixes of 3 and of 5 blocks committed, each keeping the window
    pages of its trailing two: a lookup matches 5 blocks, and as window
    pages go from the end 4, 3 and nothing; the hit's window pages are
    -1 below its tail."""
    alloc = _allocator()
    assert alloc.tail_blocks == 2 and alloc.window_pages_bound() == 3
    tokens = list(range(100, 121))
    _commit(alloc, tokens[:12], now=1)
    _commit(alloc, tokens[:20], now=2)
    chain = [alloc._prefix[tuple(tokens[:4 * i])] for i in range(1, 6)]
    assert [e.window_page is not None for e in chain] == [
        False, True, True, True, True]
    assert alloc.window_pool.used_pages == 4

    held = []
    pages = alloc.lookup_prefix(tokens, now=3, window_out=held)
    assert len(pages) == 5 and alloc.peek_prefix(tokens) == 5
    assert held[:3] == [-1, -1, -1] and held[3:] == [
        chain[3].window_page, chain[4].window_page]
    assert alloc.window_pool.refcount(held[4]) == 2
    alloc.free(pages)
    alloc.window_pool.free(held[3:])

    # the last block's window page gone: 4 blocks, whose trailing two
    # are there; the fourth's gone too: 3; the third's: nothing
    alloc.window_pool.free([chain[4].window_page])
    chain[4].window_page = None
    assert alloc.peek_prefix(tokens) == 4
    alloc.window_pool.free([chain[3].window_page])
    chain[3].window_page = None
    assert alloc.peek_prefix(tokens) == 3
    pages = alloc.lookup_prefix(tokens, now=4, window_out=held)
    assert len(pages) == 3 and alloc.prefix_hit_tokens == 20 + 12
    assert held == [-1, chain[1].window_page, chain[2].window_page]
    alloc.free(pages)
    alloc.window_pool.free([chain[1].window_page, chain[2].window_page])

    alloc.window_pool.free([chain[2].window_page])
    chain[2].window_page = None
    assert alloc.peek_prefix(tokens) == 0
    assert alloc.lookup_prefix(tokens, now=5) == []
    assert alloc.prefix_misses == 1


def test_one_lru_order_evicts_both_pools():
    """Prefix A (older) and prefix B: the window pool under pressure
    takes A's window pages first and leaves A's entries; the full pool
    under pressure drops A's leaf entries first, and what window page a
    dropped entry still held goes back with it."""
    alloc = _allocator(pages=12, window_pages=6)
    a, b = list(range(10, 22)), list(range(50, 62))
    _commit(alloc, a, now=1)
    _commit(alloc, b, now=2)
    assert alloc.cached_pages == 6 and alloc.pool.free_pages == 6
    assert alloc.window_pool.free_pages == 2

    def holds(tokens):
        return [alloc._prefix[tuple(tokens[:4 * i])].window_page is not None
                for i in range(1, 4) if tuple(tokens[:4 * i]) in alloc._prefix]

    got = alloc.allocate(3, window=True, for_decode=True)
    assert holds(a) == [False, False, True] and holds(b) == [
        False, True, True]
    assert alloc.cached_pages == 6          # the entries stayed
    alloc.window_pool.free(got)
    # the full pool: A's leaf first, with the window page it still held
    free_window = alloc.window_pool.free_pages
    got = alloc.allocate(7, for_decode=True)
    assert holds(a) == [False, False] and len(holds(b)) == 3
    assert alloc.window_pool.free_pages == free_window + 1
    alloc.free(got)
    # nothing evictable is an OutOfPagesError in either space
    with pytest.raises(OutOfPagesError, match="window pages"):
        alloc.allocate(7, window=True)
    with pytest.raises(ValueError, match="band"):
        BlockAllocator(PagePool(8), 4, window_pool=PagePool(4))


def test_the_band_slides_and_an_admission_refused_gives_everything_back():
    alloc = _allocator(pages=16, window_pages=4, watermark_pages=1)
    req = Request("r", tuple(range(40)), SamplingParams())
    for computed in range(0, 33, 4):
        req.computed_tokens = computed
        alloc.release_window(req)
        alloc.cover(req, computed + 4, for_decode=True)
        assert sum(p >= 0 for p in req.window_pages) <= (
            alloc.window_pages_bound(4))
        # the lowest page the next step can read is still there
        first = max(computed + 1 - alloc.band, 0) // 4
        assert all(p >= 0 for p in req.window_pages[first:])
        assert all(p < 0 for p in req.window_pages[:first])
    assert alloc.window_pages_released == 6
    # the full space gives, the window space refuses: the request keeps
    # what it was given, and `release` returns both
    other = Request("o", tuple(range(40)), SamplingParams())
    with pytest.raises(OutOfPagesError):
        alloc.cover(other, 12, for_decode=False)
    assert len(other.pages) == 3 and other.window_pages == []
    alloc.release(other)
    alloc.release(req)
    assert alloc.pool.free_pages == 16
    assert alloc.window_pool.free_pages == 4


# -- counters ---------------------------------------------------------------

def test_pairs_and_band_pages_by_kind_against_a_count_by_hand():
    kv = np.array([0, 5, 300, 639, 0])
    q = np.array([7, 1, 1, 64, 0])
    for window in (None, 160):
        pairs = pages = 0
        for c, n in zip(kv, q):
            reached = set()
            for t in range(c, c + n):
                lo = 0 if window is None else max(t - window + 1, 0)
                pairs += t - lo + 1
                reached |= {s // 128 for s in range(lo, t + 1)}
            pages += len(reached)
        assert _qk_pairs(kv, q, window) == pairs
        assert _band_pages(kv, q, window, 128) == pages


def test_the_steps_count_each_kind_of_layer(session):
    """`attn_qk_pairs` and `kv_pages` are the SUM of one sublayer of
    each kind; the window kind's part is beside them, and under it."""
    eng, _, _, _, _ = session
    busy = [m for m in eng.metrics.steps if m.decode_tokens
            or m.prefill_tokens]
    assert all(0 < m.attn_qk_pairs_window <= m.attn_qk_pairs / 2
               for m in busy)
    assert all(0 < m.kv_pages_window <= m.kv_pages / 2 for m in busy)
    assert all(m.attn_band_pages_window <= m.kv_pages_window for m in busy)
    assert all(m.attn_band_pages - m.attn_band_pages_window
               <= m.kv_pages - m.kv_pages_window for m in busy)
    late = [m for m in busy if m.step > 12 and m.prefill_tokens]
    assert any(m.attn_qk_pairs_window < 0.4 * m.attn_qk_pairs
               for m in late)       # the window binds
    assert all(m.attn_rows_read == m.kv_pages * 128 for m in busy)


def test_own_tile_spans_follow_the_kernels_rule_for_the_group(session):
    """`own_tile_spans` is the kernel's rule, not the step's shape: the
    turn's request decodes beside the fresh prompt's chunks of 64, but
    this model's group is 4, where the kernel keeps one tile for every
    span (`ops.ragged_paged.span_tile_rows`; 8 and 16 get two), so no
    step counts a span at a tile of its own."""
    eng, _, _, _, _ = session
    busy = [m for m in eng.metrics.steps if m.decode_tokens
            or m.prefill_tokens]
    mixed = [m for m in busy if m.decode_tokens and m.prefill_tokens > 1]
    assert mixed and all(m.num_decode_reqs == 1 for m in mixed)
    assert span_tile_rows(64, 72, 4) == (264, 264)
    assert span_tile_rows(64, 72, 8) == (512, 8)
    assert all(m.own_tile_spans == 0 for m in busy)


def test_the_grid_steps_are_each_layers_own_grid(served, monkeypatch):
    """`StepMetrics.ragged_grid_steps`, and the dispatch span's field,
    on steps that hold a decode row beside a chunk: for each attention
    layer the work items the DEVICE builds from the layer's own table
    and window, times the blocks its KV heads are carried in, which at
    this model's 2 KV heads is one (`ops.ragged.lowered`: "2/2")."""
    model, params = served
    grids, apply = [], engine_mod._ragged_apply

    def spy(model, params, buffer, pools, layout):
        _, index = engine_mod._step_inputs(model, buffer, layout)
        total = 0
        for layer, c in enumerate(model.cache_layout().steps(pools,
                                                             index)):
            hkv = c.k_pool.shape[1]
            group = model.num_q_heads // hkv
            width = c.token_slot.shape[0]
            _, n = work_items(live_pages(
                c.kv_lens + jnp.diff(c.cu_q_lens), c.cu_q_lens,
                c.distribution, max_pages=c.page_table.shape[1],
                page=c.page_size, q_tile=c.q_tile,
                window=model.layer_window(layer), sinks=None))
            total += int(n) * (hkv // head_block(
                hkv, c.q_tile, width, group, d=16, dv=16, page=c.page_size,
                q_itemsize=4, kv_itemsize=4))
        grids.append(total)
        return apply(model, params, buffer, pools, layout)

    monkeypatch.setattr(engine_mod, "_ragged_apply", spy)
    was = obs.is_enabled()
    obs.enable()
    obs.reset()
    apply.clear_cache()                     # the counter ticks at trace time
    _ragged_paged_attention_jit.clear_cache()
    try:
        eng, _, _ = _serve(model, params, _prompts(5, 70, 300), 6)
        spans = [e["fields"] for e in obs.events()
                 if e["name"] == "engine.step.dispatch"]
        heads = {s["labels"]["heads"]
                 for s in obs.counter("ops.ragged.lowered").series()}
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    busy = [m for m in eng.metrics.steps if m.decode_tokens
            or m.prefill_tokens]
    assert any(m.decode_tokens and m.prefill_tokens > 1 for m in busy)
    assert [m.ragged_grid_steps for m in busy] == grids
    assert [s["ragged_grid_steps"] for s in spans] == grids
    # one full layer and four window layers, every head in one block
    assert all(m.ragged_grid_steps == m.kv_pages + 3 * m.kv_pages_window
               for m in busy)
    assert heads == {"2/2"}


def test_the_slide_has_a_span_inside_the_schedule_phase(served):
    model, params = served
    obs.enable()
    obs.reset()
    try:
        _serve(model, params, _prompts(2, 300), 2)
        events = obs.events()
        names = [e["name"] for e in events]
        assert "allocator.release_window" in names
        gauges = {(s["name"], tuple(sorted(s["labels"].items()))): s["value"]
                  for s in obs.REGISTRY.snapshot()["gauges"]}
        assert ("engine.pages.used", (("pool", "window"),)) in gauges
        assert gauges[("engine.pages.free", (("pool", "window"),))] > 0
    finally:
        obs.reset()
        obs.disable()


# -- the builder ------------------------------------------------------------

def test_the_builder_reads_the_catalogs_keys():
    path = os.path.join(harness.HERE, "configs", "trinity-mini.json")
    config = json.load(open(path))
    model = decoder_from_config(config)
    assert (model.dim, model.num_q_heads, model.num_kv_heads,
            model.head_size, model.window, model.depth, model.vocab) == (
                2048, 32, 4, 128, 2048, 9, 25024)
    assert model.kinds == (S, S, S, S, F, S, S, S, F)
    assert model.window_layers == (0, 1, 2, 3, 5, 6, 7)
    assert model.expert_layers == tuple(range(1, 9))
    assert model.held_experts == 16 and model.num_dense_layers == 1
    assert model.kv_pool_widths() == (4, (128, 128))
    assert dict(model.sublayer) == dict(
        experts=128, experts_held=16, experts_share=0, experts_top_k=8,
        experts_hidden=1024, experts_scale=2.826)
    assert (model.head_norm, model.attn_gate, model.sandwich_norm,
            model.global_rope, model.rope, model.mlp_hidden) == (
                True, True, True, False, True, 6144)
    assert model.embed_scale == pytest.approx(2048 ** 0.5)
    assert model.norm_eps == 1e-5
    assert [model.layer_window(i) for i in (0, 4)] == [2048, None]
    assert [model.layer_rope(i) for i in (0, 4)] == [True, False]
    # the first published layers where the file names none
    first = dict(config, num_hidden_layers=8)
    del first["served_layers"]
    assert decoder_from_config(first).kinds == (S, S, S, F) * 2
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 1.243e9 < count < 1.244e9


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 2), ("num_expert_groups", 4),
    ("num_limited_groups", 2), ("score_func", "softmax"),
    ("route_norm", False), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("num_shared_experts", 2), ("sliding_window", None)])
def test_the_builder_refuses_by_the_keys_name(key, value):
    with pytest.raises(ValueError, match=key):
        decoder_from_config(dict(CONFIG, **{key: value}))


def test_a_free_head_size_belongs_to_this_family_alone():
    with pytest.raises(ValueError, match="head_dim"):
        decoder_from_config({
            "hidden_size": 64, "num_attention_heads": 8, "head_dim": 16,
            "num_hidden_layers": 2, "vocab_size": VOCAB,
            "intermediate_size": 256})
    with pytest.raises(ValueError, match="served_layers"):
        decoder_from_config(dict(CONFIG, served_layers=[0, 1]))


# -- the share --------------------------------------------------------------

class _FeedForward(nn.Module):
    fields: tuple

    @nn.compact
    def __call__(self, y):
        return expert_feed_forward(y, None, dtype=jnp.float32,
                                   **dict(self.fields))


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """THE SHARE TEST: the routed parts of all 4 shares of 4 experts,
    with what every chip computes alike (the shared expert) counted
    once, are the uncut reference's expert layer."""
    experts, held, top_k, dim, hidden = 16, 4, 3, 64, 32
    rng = jax.random.split(jax.random.PRNGKey(7), 8)
    p = {"router": jax.random.normal(rng[0], (dim, experts)) * dim ** -0.5,
         "router_bias": jax.random.normal(rng[1], (experts,)) * 0.3,
         "experts_gate": jax.random.normal(rng[2], (experts, dim, hidden))
         * dim ** -0.5,
         "experts_up": jax.random.normal(rng[3], (experts, dim, hidden))
         * dim ** -0.5,
         "experts_down": jax.random.normal(rng[4], (experts, hidden, dim))
         * hidden ** -0.5}
    shared = {name: {"kernel": jax.random.normal(k, shape) * shape[0] ** -0.5}
              for name, k, shape in (("gate_proj", rng[5], (dim, hidden)),
                                     ("up_proj", rng[6], (dim, hidden)),
                                     ("down_proj", rng[7], (hidden, dim)))}
    y = jax.random.normal(jax.random.PRNGKey(8), (24, dim))

    def sizes(share, shares):
        return {"share": share, "shares": shares, "top_k": top_k,
                "scale": 2.826, "capacity": 256, "block": 8}

    def cut(share):
        at = slice(held * share, held * (share + 1))
        return dict(p, **{n: p[n][at] for n in (
            "experts_gate", "experts_up", "experts_down")})

    with jax.default_matmul_precision("highest"):
        whole, _ = reference.expert_feed_forward(p, shared, y,
                                                 sizes=sizes(0, 1))
        alike = reference._swiglu(shared, y, sizes=sizes(0, 1),
                                  quant=lambda t: t)
        total = 0.0
        for share in range(experts // held):
            layer = _FeedForward(tuple(dict(
                experts=experts, experts_held=held, experts_share=share,
                experts_top_k=top_k, experts_hidden=hidden,
                experts_scale=2.826).items()))
            out = layer.apply({"params": {"experts": cut(share),
                                          "shared_expert": shared}}, y[None])
            mine, _ = reference.expert_feed_forward(
                cut(share), shared, y, sizes=sizes(share, 4))
            np.testing.assert_allclose(out[0], mine, atol=2e-5)
            total = total + (out[0] - alike)
        np.testing.assert_allclose(total + alike, whole, atol=5e-5)
        # the whole layer through the program, all 16 held
        layer = _FeedForward(tuple(dict(
            experts=experts, experts_held=experts, experts_top_k=top_k,
            experts_hidden=hidden, experts_scale=2.826).items()))
        out = layer.apply({"params": {"experts": p,
                                      "shared_expert": shared}}, y[None])
        np.testing.assert_allclose(out[0], whole, atol=2e-5)


# -- who refuses two page spaces, and who keeps one -------------------------

def test_features_that_carry_one_list_of_pages_refuse_by_name(served):
    from attention_tpu.engine import snapshot
    from attention_tpu.fleet.handoff import export_handoff
    from attention_tpu.prefixstore.adapter import export_chain

    model, params = served
    eng = ServingEngine(model, params, EngineConfig(**ENGINE))
    for call in (lambda: snapshot.save(eng, "/nonexistent"),
                 lambda: snapshot.SnapshotManager(eng, "/nonexistent"),
                 lambda: export_chain(eng, (1, 2, 3), [0], now=0),
                 lambda: export_handoff(eng, None, {})):
        with pytest.raises(PageSpacesUnsupportedError,
                           match="page space of their own"):
            call()
    with pytest.raises(PageSpacesUnsupportedError, match="mesh_shards"):
        ServingEngine(model, params, EngineConfig(**dict(ENGINE,
                                                         mesh_shards=2)))
    with pytest.raises(ValueError, match="num_window_pages"):
        ServingEngine(model, params, EngineConfig(**dict(
            ENGINE, num_window_pages=0)))
    plain = decoder_from_config({
        "hidden_size": 64, "num_attention_heads": 8, "num_hidden_layers": 2,
        "vocab_size": VOCAB, "intermediate_size": 256,
        "torch_dtype": "float32"})
    with pytest.raises(ValueError, match="num_window_pages"):
        ServingEngine(plain, None, EngineConfig(**ENGINE))


def _parents_buffer(items, *, width, slots, table_width, recurrent):
    """A packed step as the parent of PR 43 laid it out, written out
    literally: tokens, slots, positions, lengths, offsets, the split,
    the table, and last the state rows of a recurrent model."""
    tokens = np.zeros(width, np.int32)
    token_slot = np.full(width, -1, np.int32)
    token_pos = np.zeros(width, np.int32)
    kv_lens = np.zeros(slots, np.int32)
    cu = np.zeros(slots + 1, np.int32)
    tables = np.full((slots, table_width), -1, np.int32)
    state_rows = np.full(slots, -1, np.int32)
    off = 0
    for s, (toks, computed, pages, state) in enumerate(items):
        n = len(toks)
        tokens[off:off + n] = toks
        token_slot[off:off + n] = s
        token_pos[off:off + n] = np.arange(computed, computed + n)
        kv_lens[s] = computed
        tables[s, :len(pages)] = pages
        state_rows[s] = state
        off += n
        cu[s + 1] = off
    cu[len(items) + 1:] = off
    parts = [tokens, token_slot, token_pos, kv_lens, cu,
             np.array([1, len(items)], np.int32), tables.reshape(-1)]
    if recurrent:
        parts.append(state_rows)
    return np.concatenate(parts)


@pytest.mark.parametrize("name, pools", [
    ("starcoder2-7b", (4, (128, 128))), ("olmo-hybrid-7b", (30, (128, 128))),
    ("nemotron-3-super-120b", (2, (128, 128))),
    ("longcat-flash-omni", (1, (640,))),
    ("deepseek-v3.2-exp", (1, (640, 128)))])
def test_a_model_of_one_kind_keeps_the_parents_step_buffer_and_pools(
        name, pools):
    """The five configurations the nine serving cells run: one page
    space, the parent's pool list, and a packed step byte for byte the
    parent's at the cell's own slots and table width."""
    config = json.load(open(os.path.join(harness.HERE, "configs",
                                         name + ".json")))
    runner = harness.load_module("runners", config["runner"])
    model = getattr(runner, "serve", runner).build_model(config)
    assert model.window_layers == () and model.kv_pool_widths() == pools
    engine = EngineConfig(**config["engine"])
    assert engine.num_window_pages == 0 and engine.min_prefill_tile == 0
    recurrent = bool(model.recurrent_layers)
    slots = engine.max_decode_batch + engine.max_prefill_rows
    consts = dict(slots=slots, table_width=engine.table_width,
                  recurrent=recurrent)
    assert step_buffer_len(48, **consts) == (
        3 * 48 + 2 * slots + 3 + slots * engine.table_width
        + (slots if recurrent else 0))
    decode = Request("d", (5, 6, 7), SamplingParams())
    decode.tokens, decode.pending_token = [5, 6, 7], 9
    decode.computed_tokens, decode.pages, decode.state_slot = 3, [4], 2
    chunk = Request("p", tuple(range(10, 40)), SamplingParams())
    chunk.tokens, chunk.pages, chunk.state_slot = list(chunk.prompt), [7, 8], 0
    sched = ScheduledStep(step=0, decode=[decode], prefill=[(chunk, 30)])
    batch = sched.pack(width=48, **consts)
    assert batch.window_tables is None
    want = _parents_buffer(
        [([9], 3, [4], 2), (list(range(10, 40)), 0, [7, 8], 0)], width=48,
        **consts)
    assert batch.buffer.tobytes() == want.tobytes()
    assert split_step_buffer(batch.buffer, **consts).window_tables is None
