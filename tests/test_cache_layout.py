"""The seam between model and engine: `models.cache_layout`.

The model says what each layer keeps between steps; the engine
allocates it, hands it to the jitted step and takes it back, and its
pool code names no kind of layer.  Held here: for the small
configuration of every family the other engine test files build, the
layout's entries against what the engine allocates and against the
caches a TRACED step hands each layer; and a model whose layout holds
an entry no kind in `models/transformer.py` makes, served with
`engine/engine.py` as it is.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_hybrid_engine
import test_indexed_latent
import test_shortcut_experts
import test_sublayer_engine
import test_window_layers
from attention_tpu.engine import EngineConfig, SamplingParams, ServingEngine
from attention_tpu.engine.engine import (
    RaggedStepIndex,
    StepLayout,
    _ragged_apply,
)
from attention_tpu.engine.scheduler import step_buffer_len
from attention_tpu.models import TinyDecoder, decoder_from_config
from attention_tpu.models.cache_layout import (
    PAGE,
    PAGES,
    STATE_ROWS,
    WINDOW_PAGES,
    CacheLayout,
    LayerCache,
)
from attention_tpu.models.moe import PackedTokens
from attention_tpu.ops.gated_delta import RaggedStateStep
from attention_tpu.ops.ragged_paged import RaggedPagedStep

pytestmark = pytest.mark.engine

_DENSE = dict(vocab=43, dim=32, depth=1, num_q_heads=4, num_kv_heads=2,
              impl="flash", dtype=jnp.float32)
_DENSE_ENGINE = dict(num_pages=24, page_size=128, max_seq_len=256,
                     max_decode_batch=4, max_prefill_rows=2,
                     prefill_chunk=32, token_budget=80)
P, W, S = PAGES, WINDOW_PAGES, STATE_ROWS

#: family -> (model, engine fields, each layer's id space or None, the
#: error a pages-only feature raises for it)
FAMILIES = {
    "dense": (lambda: TinyDecoder(**_DENSE), _DENSE_ENGINE, [P], None),
    "dense_sliding_bf16": (
        lambda: TinyDecoder(**dict(_DENSE, depth=2, window=160, rope=True,
                                   dtype=jnp.bfloat16)),
        dict(_DENSE_ENGINE, cache_dtype=jnp.float32), [P, P], None),
    "hybrid": (lambda: decoder_from_config(test_hybrid_engine.CONFIG),
               test_hybrid_engine.ENGINE, [S, S, S, P],
               "RecurrentStateUnsupportedError"),
    "sublayer": (lambda: decoder_from_config(test_sublayer_engine.CONFIG),
                 test_sublayer_engine.ENGINE,
                 [S, None, S, P, None, S, None],
                 "RecurrentStateUnsupportedError"),
    "shortcut": (lambda: decoder_from_config(test_shortcut_experts.CONFIG),
                 test_shortcut_experts.ENGINE, [P, P],
                 "LatentCacheUnsupportedError"),
    "indexed": (lambda: decoder_from_config(test_indexed_latent.CONFIG),
                test_indexed_latent.ENGINE, [P, P, P],
                "LatentCacheUnsupportedError"),
    "window": (lambda: decoder_from_config(test_window_layers.CONFIG),
               test_window_layers.ENGINE, [W, W, W, W, P],
               "PageSpacesUnsupportedError"),
}


def _flat(step):
    """A layer's cache as a list of its steps (a double layer has two)."""
    return list(step) if type(step) is tuple else [step]


@pytest.mark.parametrize("family", FAMILIES)
def test_the_layout_is_what_the_engine_allocates_and_a_traced_step_hands_on(
        family, monkeypatch):
    make, engine, spaces, refusal = FAMILIES[family]
    model = make()
    layout = model.cache_layout()
    hash(layout)                                    # static under a jit
    assert layout == make().cache_layout()
    assert [kept and kept.space for kept in layout.layers] == spaces
    assert layout.state_rows == (S in spaces)
    assert layout.window_table == (W in spaces)
    assert (layout.pages_only_refusal
            and layout.pages_only_refusal[0].__name__) == refusal

    # what the engine allocates: the layout's arrays, a layer at a time
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = EngineConfig(**engine)
    eng = ServingEngine(model, params, cfg)
    slots = cfg.max_decode_batch + cfg.max_prefill_rows
    rows = {P: cfg.num_pages, W: cfg.num_window_pages, S: slots + 1}
    assert eng._layout == layout
    assert len(eng._pools) == model.depth
    for layer, (kept, pools) in enumerate(zip(layout.layers, eng._pools)):
        if kept is None:
            assert pools is None
            continue
        assert [p.shape for p in pools] == [
            (rows[kept.space], *(cfg.page_size if n == PAGE else n
                                 for n in row)) for row, _ in kept.arrays]
        assert [p.dtype for p in pools] == [
            jnp.dtype(cfg.cache_dtype or model.dtype) if dtype is None
            else jnp.dtype(dtype) for _, dtype in kept.arrays]
    # the accessor: what lies under the request's page ids, each such
    # layer's first array, then each one's second
    paged = [pools for kept, pools in zip(layout.layers, eng._pools)
             if kept is not None and kept.space == P]
    got = eng.page_pools()
    assert len(got) == sum(map(len, paged))
    assert all(a is b for a, b in zip(
        got, [pools[n] for n in range(len(paged[0])) for pools in paged]))
    assert eng.allocator.state_slots == (slots if S in spaces else 0)
    assert (eng.window_pool is not None) == (W in spaces)

    # what a TRACED step hands each layer, and what it takes back
    handed = []
    apply = TinyDecoder.apply

    def spy(self, variables, tokens, caches, **kw):
        handed.append(caches)
        return apply(self, variables, tokens, caches, **kw)

    monkeypatch.setattr(TinyDecoder, "apply", spy)
    width, q_tile = eng.step_shape(2, cfg.prefill_chunk)
    step = StepLayout(slots, cfg.table_width, q_tile)
    buffer = jax.ShapeDtypeStruct((step_buffer_len(
        width, slots=slots, table_width=cfg.table_width,
        recurrent=layout.state_rows, window_tables=layout.window_table),),
        jnp.int32)
    pools = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         tuple(eng._pools))
    _, back, _ = jax.eval_shape(
        functools.partial(_ragged_apply.__wrapped__, model, layout=step),
        params, buffer, pools)
    assert jax.tree.structure(back) == jax.tree.structure(pools)
    assert jax.tree.leaves(back) == jax.tree.leaves(pools)
    (caches,) = handed
    assert len(caches) == model.depth
    for kept, held, cache in zip(layout.layers, pools, caches):
        if kept is None:
            assert isinstance(cache, PackedTokens)
            assert cache.token_slot.shape == (width,)
            continue
        steps = _flat(cache)
        if kept.space == S:
            (step_,) = steps
            assert isinstance(step_, RaggedStateStep)
            assert step_.state_rows.shape == (slots,)
            got = step_[:2]
        else:
            assert all(isinstance(s, RaggedPagedStep) for s in steps)
            assert {s.page_table.shape for s in steps} == {
                (slots, cfg.table_width)}
            got = [p for s in steps
                   for p in (s.k_pool, s.v_pool, s.index_pool)
                   if p is not None]
        assert all(s.q_tile == q_tile for s in steps)
        assert [(a.shape, a.dtype) for a in got] == [
            (a.shape, a.dtype) for a in held]


# ------------------------------------------ an entry no kind here makes


def _counting_step(arrays, index):
    k, v, written = arrays
    return (RaggedPagedStep(k, v, *index[:7]), written, index)


def _counting_kept(cache):
    step, written, _ = cache
    return step.k_pool, step.v_pool, written


@dataclasses.dataclass(frozen=True)
class CountingDecoder:
    """A dense decoder whose one layer keeps, beside K and V, an int32
    array of how often each cache row was written: three arrays, one of
    another dtype and rank, behind a cache object of its own.  Nothing
    in `models/transformer.py` makes such an entry, and nothing in
    `engine/engine.py` knows of it."""

    inner: TinyDecoder

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def cache_layout(self) -> CacheLayout:
        pool = ((self.inner.num_kv_heads, PAGE, self.inner.head_size), None)
        return CacheLayout((LayerCache(
            PAGES, (pool, pool, ((PAGE,), jnp.int32)),
            _counting_step, _counting_kept),))

    def apply(self, variables, tokens, caches, **kw):
        (step, written, index), = caches
        assert isinstance(index, RaggedStepIndex)
        logits, (step,) = self.inner.apply(variables, tokens, (step,), **kw)
        page = written.shape[1]
        slot = jnp.maximum(index.token_slot, 0)
        pages = index.page_table[slot, index.token_pos // page]
        written = written.at[pages, index.token_pos % page].add(
            (index.token_slot >= 0).astype(jnp.int32))
        return logits, ((step, written, index),)


def test_an_entry_no_kind_makes_is_served_with_the_engine_as_it_is():
    inner = TinyDecoder(**_DENSE)
    params = inner.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 43, size=n).tolist() for n in (70, 9, 150)]

    def serve(model):
        eng = ServingEngine(model, params, EngineConfig(**_DENSE_ENGINE))
        reqs = [eng.add_request(p, SamplingParams(max_tokens=5))
                for p in prompts]
        eng.run(max_steps=60)
        return eng, [r.output_tokens for r in reqs]

    eng, got = serve(CountingDecoder(inner))
    _, want = serve(inner)
    assert got == want and all(len(t) == 5 for t in got)
    k, v, written = eng.page_pools()
    assert k.shape == v.shape == (24, 2, 128, 8)
    assert written.shape == (24, 128) and written.dtype == jnp.int32
    # every fed token wrote one cache row: the prompt, and every sampled
    # token but the last (a page given back and handed out again counts
    # both of its requests)
    assert int(written.sum()) == sum(len(p) + 5 - 1 for p in prompts)
    assert any(m.prefill_tokens for m in eng.metrics.steps)
    assert any(m.decode_tokens for m in eng.metrics.steps)
    # the features that carry K / V pages serve it: it names no refusal
    eng.require_pages_only("a snapshot")
