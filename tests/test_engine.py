"""Continuous-batching serving engine tests (attention_tpu/engine/).

Tiny CPU shapes throughout.  The flagship is the token-parity test:
a trace of 8 overlapping requests served by the engine — chunked
prefill interleaved with decode in the same scheduler steps, one
prefix-cache hit (pinned by page refcounts) — must produce, request
for request, EXACTLY the tokens sequential `generate_paged` produces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.engine import (
    BlockAllocator,
    EngineConfig,
    SamplingParams,
    Scheduler,
    ServingEngine,
    synthetic_trace,
)
from attention_tpu.engine.request import Request, RequestState
from attention_tpu.models import TinyDecoder
from attention_tpu.models.decode import generate_paged
from attention_tpu.ops.paged import (
    OutOfPagesError,
    PageAccountingError,
    PagePool,
)

pytestmark = pytest.mark.engine


@pytest.fixture(scope="module")
def tiny_model():
    model = TinyDecoder(vocab=43, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    probe = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), probe)["params"]
    return model, params


def _sequential_reference(model, params, prompt, max_tokens):
    toks, _caches, _pools = generate_paged(
        model, params, jnp.asarray([prompt], jnp.int32),
        jnp.asarray([len(prompt)], jnp.int32), steps=max_tokens,
    )
    return np.asarray(toks)[0].tolist()


# ---------------------------------------------------------------- request


def test_request_lifecycle_transitions():
    req = Request(request_id="r", prompt=(1, 2, 3),
                  sampling=SamplingParams(max_tokens=2))
    assert req.state is RequestState.WAITING
    with pytest.raises(ValueError, match="illegal lifecycle"):
        req.transition(RequestState.DECODING)  # must prefill first
    req.transition(RequestState.PREFILLING)
    req.transition(RequestState.PREEMPTED)
    req.transition(RequestState.PREFILLING)
    req.transition(RequestState.DECODING)
    req.transition(RequestState.FINISHED)
    with pytest.raises(ValueError, match="illegal lifecycle"):
        req.transition(RequestState.WAITING)


def test_request_emit_feed_contract():
    req = Request(request_id="r", prompt=(5,),
                  sampling=SamplingParams(max_tokens=2, stop_token=9))
    assert not req.emit(4)          # not done: pending awaits feeding
    assert req.pending_token == 4
    assert req.feed_pending() == 4
    assert req.tokens == [5, 4]
    with pytest.raises(ValueError, match="no pending"):
        req.feed_pending()
    assert req.emit(9)              # stop token ends the request
    assert req.pending_token is None
    with pytest.raises(ValueError, match="empty prompt"):
        Request(request_id="x", prompt=(), sampling=SamplingParams())


def test_sampling_params_validation():
    SamplingParams(max_tokens=1).validate(vocab=8)
    with pytest.raises(ValueError, match="max_tokens"):
        SamplingParams(max_tokens=0).validate(vocab=8)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1).validate(vocab=8)
    with pytest.raises(ValueError, match="greedy"):
        SamplingParams(top_k=3).validate(vocab=8)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=1.0, top_p=1.5).validate(vocab=8)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(temperature=1.0, top_k=9).validate(vocab=8)


# -------------------------------------------------------------- allocator


def test_allocator_watermark_and_fragmentation():
    """Watermark refusal on the admission path, reserve draining on the
    decode path — across a deliberately fragmented free list."""
    pool = PagePool(8)
    alloc = BlockAllocator(pool, 128, watermark_pages=2)
    # fragment: claim everything, free a scattered subset
    held = alloc.allocate(6, for_decode=True)
    for p in (held[0], held[3], held[5]):
        alloc.free([p])
        held.remove(p)
    assert pool.free_pages == 5
    got = alloc.allocate(3)              # leaves 2 = watermark: OK
    assert pool.free_pages == 2
    with pytest.raises(OutOfPagesError, match="watermark"):
        alloc.allocate(1)                # would dip into the reserve
    drained = alloc.allocate(2, for_decode=True)  # decode may drain it
    assert len(drained) == 2
    with pytest.raises(OutOfPagesError):
        alloc.allocate(1, for_decode=True)
    alloc.free(got + held + drained)
    assert pool.free_pages == 8
    # pool accounting stayed sane through the churn
    assert sorted(alloc.allocate(8, for_decode=True)) == list(range(8))


def test_allocator_exact_watermark_boundary():
    """Regression (ISSUE 4 satellite): one allocation landing EXACTLY
    on the watermark boundary must succeed — ``free - n == watermark``
    is legal, ``free - n == watermark - 1`` is not (the off-by-one
    class the chaos fuzzer's watermark-flap plans also cover), and the
    decode path may drain to exactly zero."""
    pool = PagePool(8)
    alloc = BlockAllocator(pool, 128, watermark_pages=2)
    got = alloc.allocate(6)              # 8 - 6 == 2 == watermark: OK
    assert pool.free_pages == 2
    with pytest.raises(OutOfPagesError, match="watermark"):
        alloc.allocate(1)                # 2 - 1 < watermark
    alloc.free([got.pop()])
    assert pool.free_pages == 3
    got += alloc.allocate(1)             # back ON the boundary: OK
    assert pool.free_pages == 2
    # decode may consume the entire reserve, to exactly zero free
    got += alloc.allocate(2, for_decode=True)
    assert pool.free_pages == 0
    with pytest.raises(OutOfPagesError):
        alloc.allocate(1, for_decode=True)
    # an evictable cached page exactly covering the shortfall counts:
    # eviction runs until the boundary holds, then allocation succeeds
    alloc.free([got.pop()])
    page = alloc.allocate(1, for_decode=True)
    alloc.commit_prefix(list(range(128)), page, now=0)
    alloc.free(page)                     # cache holds the only ref
    assert pool.free_pages == 0 and alloc.cached_pages == 1
    got += alloc.allocate(1, for_decode=True)  # evicts, then fits
    assert alloc.cached_pages == 0 and pool.free_pages == 0


def test_allocator_prefix_cache_hit_miss_eviction():
    pool = PagePool(6)
    alloc = BlockAllocator(pool, 4, watermark_pages=0)  # tiny pages
    toks_a = tuple(range(10, 21))        # 11 tokens -> 2 full pages
    pages_a = alloc.allocate(3)
    assert alloc.lookup_prefix(toks_a, now=0) == []      # cold miss
    assert alloc.prefix_misses == 1
    alloc.commit_prefix(toks_a, pages_a, now=0)
    assert alloc.cached_pages == 2
    assert all(pool.refcount(p) == 2 for p in pages_a[:2])  # owner+cache

    # same full-page prefix, different tail: 2-page hit, pages incref'd
    toks_b = toks_a[:8] + (99, 98, 97)
    hit = alloc.lookup_prefix(toks_b, now=1)
    assert hit == pages_a[:2]
    assert alloc.prefix_hits == 1 and alloc.prefix_hit_tokens == 8
    assert all(pool.refcount(p) == 3 for p in pages_a[:2])
    # a prompt that exactly equals the cached prefix must leave >= 1
    # token uncached (the last token produces the first-sample logits)
    assert alloc.lookup_prefix(toks_a[:8], now=1) == [pages_a[0]]
    alloc.free([pages_a[0]])

    # release both requests; pages stay cached (refcount 1 = cache)
    alloc.free(hit)
    alloc.free(pages_a)
    assert pool.free_pages == 6 - 2
    # demand > free: LRU leaf evicts first, then its parent
    fresh = alloc.allocate(6)
    assert alloc.prefix_evictions == 2 and alloc.cached_pages == 0
    assert sorted(fresh) == sorted(set(fresh))
    alloc.free(fresh)


def test_allocator_prefix_chain_evicts_leaf_before_parent():
    pool = PagePool(4)
    alloc = BlockAllocator(pool, 2, watermark_pages=0)
    toks = (1, 2, 3, 4, 5)               # 2 full pages at page_size 2
    pages = alloc.allocate(3)
    alloc.commit_prefix(toks, pages, now=0)
    alloc.free(pages)                    # cache-only now
    # parent (page 0 of the chain) is protected while its child lives
    assert alloc.evict_lru() == pages[1]  # leaf first
    assert alloc.evict_lru() == pages[0]  # then the parent
    assert alloc.evict_lru() is None
    assert pool.free_pages == 4


# ----------------------------------------------------- engine end-to-end


def test_engine_token_parity_prefix_and_mixed_batching(tiny_model):
    """Acceptance: 8 overlapping requests; engine output == sequential
    `generate_paged` per request; at least one step batches prefill
    chunks and decode tokens together; the prefix-cache hit is pinned
    by page refcounts (computing request + cache + reusing request)."""
    model, params = tiny_model
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 43, 128).tolist()
    prompts = [
        shared + rng.integers(1, 43, 4).tolist(),   # r0 commits the prefix
        shared + rng.integers(1, 43, 9).tolist(),   # r1 reuses it
    ] + [rng.integers(1, 43, n).tolist() for n in (5, 7, 9, 11, 13, 16)]
    arrivals = [0, 7, 1, 2, 3, 4, 5, 6]
    maxtoks = [5, 5, 4, 4, 4, 4, 4, 4]

    cfg = EngineConfig(num_pages=24, page_size=128, max_seq_len=256,
                       max_decode_batch=4, max_prefill_rows=2,
                       prefill_chunk=32, token_budget=80,
                       watermark_pages=1)
    eng = ServingEngine(model, params, cfg)
    reqs = [eng.add_request(p, SamplingParams(max_tokens=mt),
                            request_id=f"r{i}", arrival=a)
            for i, (p, a, mt) in enumerate(zip(prompts, arrivals, maxtoks))]

    max_shared_ref = 0
    r0_first_page = None
    steps = 0
    while eng.scheduler.has_work():
        eng.step()
        steps += 1
        assert steps < 200
        if reqs[0].pages and r0_first_page is None:
            r0_first_page = reqs[0].pages[0]
        if reqs[1].pages:
            # r1 adopted r0's committed first page by reference
            assert reqs[1].pages[0] == r0_first_page
            max_shared_ref = max(
                max_shared_ref, eng.pool.refcount(reqs[1].pages[0])
            )

    # prefix hit, proven by refcounts: r0's hold + the cache's own
    # reference + r1's incref were simultaneously live
    assert max_shared_ref == 3
    assert reqs[1].prefix_cached_tokens == 128
    assert eng.allocator.prefix_hits == 1
    # after the run every request released its pages and only the
    # cache's own reference keeps the committed prefix page resident
    assert all(r.pages == [] for r in reqs)
    assert eng.allocator.cached_pages == 1
    assert eng.pool.used_pages == 1
    assert eng.pool.refcount(r0_first_page) == 1

    # iteration-level batching: some step ran prefill chunks and decode
    # tokens together
    mixed = [m for m in eng.metrics.steps
             if m.decode_tokens and m.prefill_tokens]
    assert mixed, "no step batched prefill and decode together"
    # chunked prefill: the long prompts took several steps of slices
    assert sum(1 for m in eng.metrics.steps if m.prefill_tokens) >= 4

    # token parity, request for request
    for i, (p, mt) in enumerate(zip(prompts, maxtoks)):
        want = _sequential_reference(model, params, p, mt)
        assert reqs[i].output_tokens == want, f"r{i} diverged"

    # per-request metrics landed
    assert len(eng.metrics.requests) == 8
    summary = eng.metrics.summary()
    assert summary["output_tokens"] == sum(maxtoks)
    assert summary["prefix_cached_tokens"] == 128
    assert summary["mixed_batch_steps"] == len(mixed)


def test_engine_preemption_by_recompute_keeps_parity(tiny_model):
    """Pages run out mid-decode: the youngest running requests are
    preempted (pages freed, KV recomputed on readmission) and every
    request still finishes with exactly its sequential tokens."""
    model, params = tiny_model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 43, 120).tolist() for _ in range(3)]
    maxtoks = [12, 12, 8]

    cfg = EngineConfig(num_pages=3, page_size=128, max_seq_len=256,
                       max_decode_batch=4, max_prefill_rows=2,
                       prefill_chunk=32, token_budget=80,
                       watermark_pages=0)
    eng = ServingEngine(model, params, cfg)
    reqs = [eng.add_request(p, SamplingParams(max_tokens=mt),
                            request_id=f"p{i}", arrival=i)
            for i, (p, mt) in enumerate(zip(prompts, maxtoks))]
    eng.run(max_steps=400)

    assert eng.scheduler.num_preemptions >= 1
    assert sum(r.preemptions for r in reqs) >= 1
    # FCFS preemption picks the youngest victim: the oldest request is
    # never preempted
    assert reqs[0].preemptions == 0
    for i, (p, mt) in enumerate(zip(prompts, maxtoks)):
        want = _sequential_reference(model, params, p, mt)
        assert reqs[i].output_tokens == want, f"p{i} diverged"
    assert eng.pool.used_pages == 0  # everything recycled


def test_engine_sampled_replay_is_deterministic(tiny_model):
    """Per-request seeded sampling: the same trace through two fresh
    engines yields identical streams; different seeds diverge."""
    from attention_tpu.engine import replay

    model, params = tiny_model
    trace = synthetic_trace(3, vocab=43, seed=5, prompt_len_min=4,
                            prompt_len_max=10, max_tokens=4,
                            temperature=0.8)
    cfg = EngineConfig(num_pages=24, page_size=128, max_seq_len=256,
                       max_decode_batch=4, max_prefill_rows=2,
                       prefill_chunk=32, token_budget=80,
                       watermark_pages=1)
    _, out_a = replay(ServingEngine(model, params, cfg), trace)
    _, out_b = replay(ServingEngine(model, params, cfg), trace)
    assert out_a == out_b
    for r in trace:
        r["seed"] += 100
    _, out_c = replay(ServingEngine(model, params, cfg), trace)
    assert out_c != out_a  # astronomically unlikely to collide


def test_engine_rejects_oversized_and_bad_requests(tiny_model):
    model, params = tiny_model
    cfg = EngineConfig(num_pages=4, page_size=128, max_seq_len=128,
                       max_decode_batch=2, max_prefill_rows=1,
                       prefill_chunk=32, token_budget=32,
                       watermark_pages=0)
    eng = ServingEngine(model, params, cfg)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.add_request([1] * 125, SamplingParams(max_tokens=8))
    with pytest.raises(ValueError, match="vocab"):
        eng.add_request([1, 2, 99], SamplingParams(max_tokens=1))
    with pytest.raises(ValueError, match="impl='flash'"):
        ServingEngine(
            TinyDecoder(vocab=43, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="xla", dtype=jnp.float32),
            params, cfg,
        )


def test_engine_config_has_one_step_lowering_and_no_switch_for_it():
    """The packed step is the only lowering, and nothing names it: the
    two fields that once chose another are no fields, refused as any
    unknown one is."""
    for gone in ("step_mode", "async_steps"):
        with pytest.raises(TypeError, match=gone):
            EngineConfig(**{gone: "ragged"})


def test_scheduler_respects_token_budget_and_fcfs():
    """Pure-host scheduling: the budget caps a step's real tokens and
    admission follows (arrival, seq) order."""
    pool = PagePool(16)
    alloc = BlockAllocator(pool, 128, watermark_pages=0)
    sched = Scheduler(alloc, max_decode_batch=8, max_prefill_rows=2,
                      prefill_chunk=32, token_budget=40)
    reqs = [Request(request_id=f"q{i}", prompt=tuple([1] * 50),
                    sampling=SamplingParams(max_tokens=4), arrival=0,
                    seq=i)
            for i in range(3)]
    for r in reqs:
        sched.add(r)
    step = sched.schedule(0)
    # two prefill rows of 32 tokens = 64 > budget 40: second chunk is
    # trimmed to the remaining 8 tokens, third request waits
    assert [r.request_id for r, _ in step.prefill] == ["q0", "q1"]
    assert [n for _, n in step.prefill] == [32, 8]
    assert step.num_prefill_tokens == 40
    assert sched.waiting[0].request_id == "q2"


def test_serve_sim_cli_and_trace_roundtrip(tmp_path, capsys):
    """`cli serve-sim` end to end: synthesize + write a trace, replay
    it from the file, identical outputs both ways, valid metrics JSON."""
    import json

    from attention_tpu.cli import main

    trace_path = str(tmp_path / "trace.json")
    base = [
        "serve-sim", "--num-requests", "3", "--max-tokens", "2",
        "--prompt-len-min", "4", "--prompt-len-max", "8",
        "--vocab", "32", "--dim", "32", "--depth", "1",
        "--q-heads", "2", "--kv-heads", "1",
        "--num-pages", "8", "--max-seq-len", "128",
        "--max-decode-batch", "2", "--prefill-chunk", "16",
        "--token-budget", "32", "--watermark-pages", "0",
        "--outputs", "--per-step",
    ]
    assert main(base + ["--trace-out", trace_path]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    steps = [json.loads(ln) for ln in lines[:-1]]
    rec = json.loads(lines[-1])
    assert steps and all("decode_tokens" in s for s in steps)
    assert rec["summary"]["num_requests"] == 3
    assert rec["summary"]["output_tokens"] == 6
    assert rec["run_record"]["extra"]["tokens_per_s"] > 0

    assert main(base + ["--trace", trace_path]) == 0
    rec2 = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rec2["outputs"] == rec["outputs"]
