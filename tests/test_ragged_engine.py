"""Ragged single-launch serving engine tests (PR: one launch per step).

The engine lowers a whole mixed decode/prefill scheduler step onto ONE
jitted attention launch over a packed token axis (`ops/ragged_paged`).
Pinned here, on tiny CPU shapes:

  * the kernel itself against the fp64 packed reference
    (`ops.reference.ragged_paged_reference`), mixed and windowed;
  * `ScheduledStep.pack` — the host-side flattening the launch
    consumes — layout, decode-first ordering;
  * token parity with what the deleted two-call lowering (a fixed-shape
    decode call plus a fixed-shape prefill call) sampled on the same
    trace at its last commit, greedy and sampled;
  * a preempt / watermark fault plan drains with no invariant fired
    and the fault-free run's tokens;
  * the single-launch property, asserted against the
    ``engine.step.launches`` telemetry counter (ticks per host
    dispatch; the per-trace ``ops.*.calls`` counters corroborate that
    no paged kernel of `generate_paged` is dispatched);
  * the step returns the logits of the rows it can sample only
    (``(1, slots, vocab)`` once the packed axis is wider than the
    slots), bit-identical to those rows of the whole projection, with
    no compiled signature added, and the fetch span says so;
  * a step that compiled says so (`StepMetrics.compile_s`,
    `compiled_programs`, the `engine.program.compiled` mark), and a
    step at a shape the process has run says nothing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu import obs
from attention_tpu.chaos.faults import FaultEvent, FaultPlan, run_plan
from attention_tpu.chaos.invariants import token_parity_violations
from attention_tpu.engine import (
    EngineConfig,
    SamplingParams,
    ServingEngine,
    synthetic_trace,
)
from attention_tpu.engine import engine as engine_mod
from attention_tpu.engine.engine import _ragged_apply
from attention_tpu.engine.request import Request
from attention_tpu.engine.scheduler import ScheduledStep
from attention_tpu.engine.sim import replay, sampling_of
from attention_tpu.engine.snapshot import restore, save, state_fingerprint
from attention_tpu.models import TinyDecoder
from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    live_pages,
    packed_bucket,
    ragged_paged_append,
    ragged_paged_attention,
    tile_tokens,
    work_items,
)
from attention_tpu.ops.reference import ragged_paged_reference

pytestmark = pytest.mark.engine


@pytest.fixture(scope="module")
def tiny_model():
    model = TinyDecoder(vocab=43, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    probe = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), probe)["params"]
    return model, params


def _cfg(**overrides):
    kw = dict(num_pages=24, page_size=128, max_seq_len=256,
              max_decode_batch=4, max_prefill_rows=2,
              prefill_chunk=32, token_budget=80, watermark_pages=1)
    kw.update(overrides)
    return EngineConfig(**kw)


# ------------------------------------------------------ kernel vs oracle


_PAGE, _HQ, _HKV, _D = 128, 4, 2, 16
_GROUP = _HQ // _HKV
_SLOTS, _MAX_PAGES = 4, 3


def _kernel_case(specs, *, window=None, sinks=None, softcap=None, seed=0):
    """Build one packed step from ``specs`` (per active slot, decode
    first: (pre-append kv_len, q_len)), append, run kernel + oracle."""
    r = np.random.default_rng(seed)
    num_pool = _SLOTS * _MAX_PAGES + 2
    k_pool = r.standard_normal(
        (num_pool, _HKV, _PAGE, _D)).astype(np.float32)
    v_pool = r.standard_normal(
        (num_pool, _HKV, _PAGE, _D)).astype(np.float32)
    table = np.full((_SLOTS, _MAX_PAGES), -1, np.int32)
    kv_lens = np.zeros((_SLOTS,), np.int32)
    total = sum(q for _, q in specs)
    num_decode = sum(1 for _, q in specs if q == 1)
    q_tile = tile_tokens(
        packed_bucket(max(q for _, q in specs), minimum=1), _GROUP)
    width = packed_bucket(max(total, q_tile))
    cu = np.zeros((_SLOTS + 1,), np.int32)
    tok_pos = np.zeros((width,), np.int32)
    tok_slot = np.full((width,), -1, np.int32)
    off = nxt = 0
    for s, (kv_pre, q_len) in enumerate(specs):
        npages = -(-(kv_pre + q_len) // _PAGE)
        table[s, :npages] = np.arange(nxt, nxt + npages)
        nxt += npages
        kv_lens[s] = kv_pre
        tok_pos[off:off + q_len] = np.arange(kv_pre, kv_pre + q_len)
        tok_slot[off:off + q_len] = s
        off += q_len
        cu[s + 1] = off
    cu[len(specs) + 1:] = off
    q = r.standard_normal((1, _HQ, width, _D)).astype(np.float32)
    cache = RaggedPagedStep(
        jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
        jnp.asarray(kv_lens), jnp.asarray(cu),
        jnp.asarray([num_decode, len(specs)], jnp.int32),
        jnp.asarray(tok_pos), jnp.asarray(tok_slot),
        np.zeros((q_tile,), np.int32),
    )
    cache = ragged_paged_append(
        cache,
        jnp.asarray(r.standard_normal((1, _HKV, width, _D)), jnp.float32),
        jnp.asarray(r.standard_normal((1, _HKV, width, _D)), jnp.float32),
    )
    got = np.asarray(ragged_paged_attention(
        jnp.asarray(q), cache,
        softcap=softcap, window=window, sinks=sinks))
    want = ragged_paged_reference(
        q, np.asarray(cache.k_pool), np.asarray(cache.v_pool),
        np.asarray(cache.page_table), np.asarray(cache.kv_lens),
        cu, [num_decode, len(specs)],
        softcap=softcap, window=window, sinks=sinks)
    return got, want, cache, total


@pytest.mark.parametrize("specs,kw", [
    # 2 decode rows + 1 prefill chunk, one row crossing a page boundary
    ([(37, 1), (129, 1), (0, 12)], {}),
    # windowed + sinks over a decode row and a fresh prefill
    ([(200, 1), (0, 8)], {"window": 24, "sinks": 4}),
], ids=["mixed", "windowed"])
def test_kernel_matches_fp64_reference(specs, kw):
    got, want, cache, total = _kernel_case(specs, **kw)
    err = np.abs(got[..., :total, :].astype(np.float64)
                 - want[..., :total, :]).max()
    assert err < 2e-5, err
    # pad rows are exactly zero (masked finalize never touches them)
    assert np.all(got[..., total:, :] == 0.0)
    # append advanced every active slot's length
    assert np.asarray(cache.kv_lens)[:len(specs)].tolist() == \
        [kv + q for kv, q in specs]


# ------------------------------------------------- the append, in place


def _append_case(hkv, dtype, width, seed=0):
    """A packed step that holds every clause of the append's contract,
    and what a NumPy loop makes of it.  Slots: two decode rows (one on
    a page's first row), two requests behind ONE shared prefix page (a
    decode row and a chunk that runs over page boundaries), a row at an
    unclaimed (-1) entry, a row past the table, a row whose slot was
    poisoned before, and pad tokens up to ``width``."""
    r = np.random.default_rng(seed)
    d, slots, max_pages, num_pool = 16, 8, 4, 14
    chunk = {8: 2, 32: 20, 384: 300}[width]
    shared = 9
    table = np.full((slots, max_pages), -1, np.int32)
    table[0, :1] = [0]
    table[1, :2] = [1, 2]
    table[2, :2] = [shared, 3]
    table[3, :4] = [shared, 4, 5, 6]
    table[4, :1] = [7]              # its token is for entry 1: unclaimed
    table[5, :4] = [8, 10, 11, 12]  # its token is for entry 4: none
    table[6, :1] = [13]
    kv_lens = np.array([37, 128, 130, 128, 128, 4 * _PAGE, -1, 0], np.int32)
    q_lens = [1, 1, 1, chunk, 1, 1, 1, 0]
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    pos = np.zeros((width,), np.int32)
    slot = np.full((width,), -1, np.int32)
    for s, n in enumerate(q_lens):
        slot[cu[s]:cu[s + 1]] = s
        pos[cu[s]:cu[s + 1]] = max(kv_lens[s], 0) + np.arange(n)
    pools = [jnp.asarray(r.standard_normal((num_pool, hkv, _PAGE, d)),
                         dtype) for _ in range(2)]
    rows = [jnp.asarray(r.standard_normal((1, hkv, width, d)), dtype)
            for _ in range(2)]
    cache = RaggedPagedStep(
        *pools, jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(cu),
        jnp.asarray([3, 7], jnp.int32), jnp.asarray(pos), jnp.asarray(slot),
        np.zeros((8,), np.int32))
    want = [np.array(p) for p in pools]
    want_lens = kv_lens + np.asarray(q_lens, np.int32)
    for t in range(int(cu[-1])):
        s, page = slot[t], pos[t] // _PAGE
        if kv_lens[s] < 0 or page >= max_pages or table[s, page] < 0:
            want_lens[s] = -1
            continue
        for pool, new in zip(want, rows):
            pool[table[s, page], :, pos[t] % _PAGE] = np.asarray(new)[0, :, t]
    return cache, rows, want, want_lens, shared


@pytest.mark.parametrize("width", [8, 32, 384])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("hkv", [1, 4, 30])
def test_append_writes_the_new_rows_and_nothing_else(hkv, dtype, width):
    """Against a NumPy loop, to the bit: the new rows land, every other
    element of both pools is as it was, pad tokens drop, a token at a
    -1 entry or past the table writes nothing and its slot's length
    reads -1, a poisoned slot stays poisoned, the shared prefix page is
    untouched."""
    cache, rows, want, want_lens, shared = _append_case(hkv, dtype, width)
    before = [np.array(cache.k_pool), np.array(cache.v_pool)]
    out = ragged_paged_append(cache, *rows)
    assert np.asarray(out.kv_lens).tolist() == want_lens.tolist()
    assert want_lens[4:7].tolist() == [-1, -1, -1]
    for got, pool, old in zip((out.k_pool, out.v_pool), want, before):
        assert got.dtype == dtype
        got = np.asarray(got)
        assert got.tobytes() == pool.tobytes()
        assert got[shared].tobytes() == old[shared].tobytes()
        changed = (got != old).any(axis=(1, 3))      # (pages, rows)
        assert changed.sum() == int(np.asarray(cache.cu_q_lens)[-1]) - 3


#: what the parent of the in-place append (b9da22c) sampled for
#: `_starcoder2_like_replay`: the same rows at the same addresses give
#: the same tokens, sampled ones included
_PARENT_TOKENS = {
    "req-0": [12, 54, 27, 42, 31, 46], "req-1": [35, 4, 48, 48, 47, 16],
    "req-2": [55, 12, 30, 1, 12, 49], "req-3": [27, 37, 6, 12, 44, 49],
    "req-4": [46, 57, 6, 27, 44, 6], "req-5": [49, 22, 11, 41, 12, 24],
}


def _starcoder2_like():
    """StarCoder2's mixer at toy size: GQA 6 / 2, rope at theta 1e6, a
    sliding window shorter than the prompts."""
    model = TinyDecoder(vocab=61, dim=48, depth=2, num_q_heads=6,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32,
                        window=96, rope=True, rope_theta=1e6)
    params = model.init(jax.random.PRNGKey(5),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    trace = synthetic_trace(6, vocab=61, seed=11, max_tokens=6,
                            prompt_len_min=4, prompt_len_max=150,
                            shared_prefix_len=129, shared_count=2,
                            temperature=0.8)
    return model, params, trace


def test_replay_gives_the_parents_recorded_tokens():
    model, params, trace = _starcoder2_like()
    _, out = replay(ServingEngine(model, params, _cfg()), trace)
    assert out == _PARENT_TOKENS


def test_a_step_consumes_the_pools_it_was_given(tiny_model):
    """The step donates its pools: where the backend honours donation
    (this one does) the arrays bound before a step are deleted after
    it, and the engine holds the step's results."""
    model, params = tiny_model
    eng = ServingEngine(model, params, _cfg())
    eng.add_request(list(range(1, 20)), SamplingParams(max_tokens=4))
    before = eng.page_pools()
    eng.step()
    after = eng.page_pools()
    assert len(before) == len(after) == 2
    assert all(a.is_deleted() for a in before)
    assert not any(a.is_deleted() for a in after)
    assert float(jnp.abs(after[0]).sum()) > 0.0     # rows were written
    eng.run()


def _add(eng, entry, **kw):
    eng.add_request(entry["prompt"], sampling_of(entry),
                    request_id=entry["id"], **kw)


def _stepped_engine(model, params, trace, steps):
    """An engine that has taken ``trace`` and made ``steps`` steps."""
    eng = ServingEngine(model, params, _cfg())
    for entry in trace:
        _add(eng, entry, arrival=entry["arrival"])
    for _ in range(steps):
        eng.step()
    return eng


def _drain(eng):
    """Run ``eng`` dry; the tokens it hands out from here on."""
    out = {}
    eng.on_token = lambda req, tok: out.setdefault(
        req.request_id, []).append(int(tok))
    eng.run()
    return out


def _snapshot_then_restore(model, params, trace, tmp_path):
    """Save after three steps, step on, restore the image: the restored
    engine finishes as the one that never stopped."""
    eng = _stepped_engine(model, params, trace, 3)
    path = str(tmp_path / "cut.snap")
    save(eng, path)
    want = state_fingerprint(eng)
    for _ in range(2):
        eng.step()
    back = restore(path, model, params)
    assert state_fingerprint(back) == want
    return _drain(back), _drain(_stepped_engine(model, params, trace, 3))


def _page_poison(model, params, trace, tmp_path):
    """The chaos injector poisons a private page of a running request
    in the pools as they are after a step; the engine steps on, and the
    requests it did not touch finish as in a clean run."""
    from attention_tpu.chaos.faults import FaultInjector, FaultPlan

    eng = _stepped_engine(model, params, trace, 4)
    target = next(r.request_id for r in eng.scheduler.running if r.pages)
    assert FaultInjector(eng, FaultPlan(0, ()))._corrupt(target)
    assert bool(jnp.isnan(eng.page_pools()[0]).any())
    got = _drain(eng)
    clean = _drain(_stepped_engine(model, params, trace, 4))
    got.pop(target, None), clean.pop(target, None)
    return got, clean


def _prefix_store_import(model, params, trace, tmp_path):
    """One engine publishes a prompt's full page to a store; a second,
    which has stepped on other work, imports it into its pools and
    serves the prompt as a cold engine does."""
    from attention_tpu.prefixstore import PrefixStore
    from attention_tpu.prefixstore.adapter import import_chain

    shared = trace[0]
    store = PrefixStore()
    src = ServingEngine(model, params, _cfg())
    src.prefix_store = store
    _add(src, shared)
    _drain(src)                      # commits, hence exports, the page
    dest = _stepped_engine(model, params, trace[2:], 3)
    dest.prefix_store = store
    assert import_chain(dest, shared["prompt"], now=3) == 128
    _add(dest, shared)
    cold = _stepped_engine(model, params, trace[2:], 3)
    _add(cold, shared)
    return _drain(dest), _drain(cold)


def _handoff_import(model, params, trace, tmp_path):
    """A request with a committed page leaves one stepped engine as a
    hand-off blob and enters another stepped engine's pools."""
    from attention_tpu.engine.snapshot import _request_to_dict
    from attention_tpu.fleet.handoff import export_handoff, import_handoff

    shared = trace[0]
    src = _stepped_engine(model, params, [shared], 8)
    req = next(r for r in src.scheduler.running if r.output_tokens)
    blob = export_handoff(src, req, _request_to_dict(req, "running"))
    dest = _stepped_engine(model, params, trace[2:], 3)
    assert import_handoff(dest, blob, now=3) == 128
    _add(dest, shared)
    cold = _stepped_engine(model, params, trace[2:], 3)
    _add(cold, shared)
    return _drain(dest), _drain(cold)


@pytest.mark.parametrize("holder", [
    _snapshot_then_restore, _page_poison, _prefix_store_import,
    _handoff_import])
def test_pool_holders_work_on_an_engine_that_has_stepped(holder, tmp_path):
    """Everything that reads or rewrites the pools from outside the
    step takes the engine's current arrays and rebinds: none keeps an
    array that a later step has consumed."""
    model, params, trace = _starcoder2_like()
    got, want = holder(model, params, trace, tmp_path)
    assert got == want and all(want.values())


# ----------------------------------------------------------------- pack


def _decode_req(rid, prompt, pending, pages):
    req = Request(request_id=rid, prompt=tuple(prompt),
                  sampling=SamplingParams(max_tokens=8))
    req.computed_tokens = len(prompt)
    req.pending_token = pending
    req.pages = list(pages)
    return req


def _prefill_req(rid, prompt, computed, pages):
    req = Request(request_id=rid, prompt=tuple(prompt),
                  sampling=SamplingParams(max_tokens=8))
    req.computed_tokens = computed
    req.pages = list(pages)
    return req


def test_pack_layout_decode_first():
    d0 = _decode_req("d0", (1, 2, 3), 7, [4, 5])
    p0 = _prefill_req("p0", (9, 8, 7, 6, 5), 2, [0])
    sched = ScheduledStep(step=0, decode=[d0], prefill=[(p0, 3)])
    batch = sched.pack(width=8, slots=4, table_width=3)

    assert batch.width == 8 and batch.num_real == 4
    assert batch.distribution.tolist() == [1, 2]
    # decode slot 0 packs its fed pending token at its append position
    assert batch.tokens[0, :4].tolist() == [7, 7, 6, 5]
    assert d0.tokens == [1, 2, 3, 7]  # pack CONSUMED the pending token
    assert batch.token_slot.tolist() == [0, 1, 1, 1, -1, -1, -1, -1]
    assert batch.token_pos[:4].tolist() == [3, 2, 3, 4]
    # kv_lens are PRE-append; cu spans are contiguous, flat after the
    # last active slot
    assert batch.kv_lens.tolist() == [3, 2, 0, 0]
    assert batch.cu_q_lens.tolist() == [0, 1, 4, 4, 4]
    assert batch.tables[0].tolist() == [4, 5, -1]
    assert batch.tables[1].tolist() == [0, -1, -1]
    assert (batch.tables[2:] == -1).all()


def test_pack_rejects_overflow():
    reqs = [_decode_req(f"d{i}", (1,), 2, [i]) for i in range(3)]
    with pytest.raises(ValueError, match="slots"):
        ScheduledStep(step=0, decode=reqs).pack(
            width=8, slots=2, table_width=2)
    big = _prefill_req("p0", tuple(range(1, 12)), 0, [0])
    with pytest.raises(ValueError, match="width"):
        ScheduledStep(step=0, prefill=[(big, 11)]).pack(
            width=8, slots=4, table_width=2)


# ----------------------------------------------------- engine token parity


#: what the two-call lowering (`step_mode="two_call"`, deleted with
#: PR 29) sampled at ba2a1ab, its last commit, for the trace of
#: `test_ragged_matches_two_call_token_parity`, by temperature
_TWO_CALL_TOKENS = {
    0.0: {
        "req-0": [29, 32, 8, 29, 32, 8], "req-1": [26, 34, 36, 29, 11, 30],
        "req-2": [24, 26, 34, 36, 29, 11], "req-3": [32, 36, 29, 4, 29, 4],
        "req-4": [40, 30, 40, 33, 29, 30], "req-5": [14, 24, 24, 24, 24, 24],
        "req-6": [30, 38, 10, 36, 29, 10], "req-7": [9, 28, 29, 32, 8, 29],
    },
    0.7: {
        "req-0": [29, 11, 31, 4, 8, 19], "req-1": [34, 14, 40, 15, 40, 7],
        "req-2": [24, 33, 29, 41, 24, 9], "req-3": [29, 22, 10, 17, 13, 29],
        "req-4": [8, 29, 16, 15, 40, 14], "req-5": [27, 36, 12, 28, 11, 14],
        "req-6": [7, 29, 15, 40, 37, 25], "req-7": [9, 14, 8, 29, 11, 34],
    },
}


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_ragged_matches_two_call_token_parity(tiny_model, temperature):
    """The packed single-launch step produces, request for request,
    EXACTLY the tokens the two-call lowering sampled — mixed
    prefill/decode steps, prefix-cache hits, greedy and sampled."""
    model, params = tiny_model
    trace = synthetic_trace(8, vocab=model.vocab, seed=3, max_tokens=6,
                            prompt_len_min=4, prompt_len_max=40,
                            shared_prefix_len=129, shared_count=3,
                            temperature=temperature)
    _, ragged = replay(ServingEngine(model, params, _cfg()), trace)
    assert ragged == _TWO_CALL_TOKENS[temperature]


def test_pad_and_occupancy_account_for_the_packed_width(tiny_model):
    model, params = tiny_model
    trace = synthetic_trace(6, vocab=model.vocab, seed=5, max_tokens=5)
    eng = ServingEngine(model, params, _cfg())
    summary, _ = replay(eng, trace)
    assert 0.0 < summary["mean_ragged_occupancy"] <= 1.0
    padded = 0
    for m in eng.metrics.steps:
        total = m.decode_tokens + m.prefill_tokens
        if not total:
            # an idle step dispatches nothing and pads nothing
            assert m.pad_tokens == 0 and m.ragged_occupancy == 0.0
            continue
        # a busy step measured the launch width: a pow2 bucket that
        # holds the real tokens, the remainder counted as pad
        width = total + m.pad_tokens
        assert width == packed_bucket(width) and m.pad_tokens < width
        assert m.ragged_occupancy == pytest.approx(total / width)
        padded += m.pad_tokens
    assert summary["pad_tokens_total"] == padded


def test_preempt_watermark_plan_keeps_token_parity(tiny_model):
    """A deterministic preempt / watermark plan on the step loop: it
    drains, no invariant fires, and every request's tokens are the
    fault-free replay's."""
    model, params = tiny_model
    trace = synthetic_trace(6, vocab=model.vocab, seed=13, max_tokens=5)
    plan = FaultPlan(seed=0, events=(
        FaultEvent(step=2, kind="preempt", arg=1),
        FaultEvent(step=4, kind="watermark", arg=2),
        FaultEvent(step=6, kind="preempt", arg=1),
    ))
    _, clean = replay(ServingEngine(model, params, _cfg()), trace)
    r = run_plan(model, params, _cfg(), trace, plan)
    assert r.drained and r.preemptions >= 1
    assert r.violations == []
    assert token_parity_violations(clean, r.outputs) == []


def test_rope_sinks_window_model_is_refused_at_its_first_step():
    """The packed step carries no rotated sink read copy: a rope +
    sink-token + window model constructs an engine and is refused,
    typed, by the first step that would serve it."""
    model = TinyDecoder(vocab=43, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32,
                        rope=True, attn_sinks=4, window=64)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServingEngine(model, params, _cfg())
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=2))
    with pytest.raises(ValueError, match="packed step does not carry.*"
                                         "generate_paged"):
        eng.step()


# ------------------------------------------------------- launch counters


def _counter_total(snap, name, **labels):
    total = 0.0
    for row in snap["counters"]:
        if row["name"] != name:
            continue
        if all(row["labels"].get(k) == v for k, v in labels.items()):
            total += row["value"]
    return total


def test_exactly_one_launch_per_busy_step(tiny_model):
    """The single-launch property, from telemetry: the step loop
    dispatches EXACTLY one jitted launch per non-empty step and never
    touches the paged kernels of `generate_paged`."""
    model, params = tiny_model
    trace = synthetic_trace(6, vocab=model.vocab, seed=7, max_tokens=5,
                            shared_prefix_len=129, shared_count=2)
    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        # ops.*.calls tick at jit-TRACE time; drop the cached executable
        # so this replay's traces land in the freshly reset registry
        _ragged_apply.clear_cache()
        eng = ServingEngine(model, params, _cfg())
        replay(eng, trace)
        snap = obs.REGISTRY.snapshot()
        busy = sum(1 for m in eng.metrics.steps
                   if m.decode_tokens or m.prefill_tokens)
        assert busy > 0
        assert _counter_total(snap, "engine.step.launches") == busy
        # the ragged op traced (>= once; ticks per jit trace, not per
        # execution) and no paged attention was dispatched
        assert _counter_total(snap, "ops.ragged.calls") >= 1
        assert _counter_total(snap, "ops.paged.calls") == 0
        # pad accounting reached the registry
        padded = sum(m.pad_tokens for m in eng.metrics.steps)
        assert _counter_total(snap, "engine.step.pad_tokens") == padded
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()


# ------------------------------------------- logits of the sampled rows

_SLOTS10 = dict(max_decode_batch=8, max_prefill_rows=2)  # 10 slots


@pytest.fixture
def ragged_calls(monkeypatch):
    """Every `_ragged_apply` dispatch of the engines run under this
    fixture, as ``(tokens, caches, logits)``.  The step consumes its
    pools, so ``caches`` holds copies of them as they were before it:
    a recorded call can be run again."""
    calls = []

    def spy(model, params, buffer, pools, layout):
        before = jax.tree.map(jnp.copy, pools)
        out = _ragged_apply(model, params, buffer, pools, layout)
        tokens, index = engine_mod._step_inputs(model, buffer, layout)
        calls.append((tokens, model.cache_layout().steps(before, index),
                      out[0]))
        return out

    monkeypatch.setattr(engine_mod, "_ragged_apply", spy)
    return calls


def _mixed_widths_run(tiny_model):
    """Ten slots under prompts of 4-40 tokens: decode-only steps pack
    to width 8 (within the slots), steps with a chunk to 16-48."""
    model, params = tiny_model
    trace = synthetic_trace(8, vocab=model.vocab, seed=3, max_tokens=6,
                            prompt_len_min=4, prompt_len_max=40)
    eng = ServingEngine(model, params, _cfg(**_SLOTS10))
    _, out = replay(eng, trace)
    assert all(out[e["id"]] for e in trace)
    return eng


@pytest.mark.parametrize("wide", [True, False],
                         ids=["width_over_slots", "width_within_slots"])
def test_step_returns_the_sampled_rows_bit_identical(tiny_model,
                                                     ragged_calls, wide):
    """Over the slot count the step hands back ``(1, slots, vocab)``,
    row ``s`` = the projection's row ``cu[s + 1] - 1`` (clipped at 0
    for empty slots) to the bit; within it the program is the whole
    projection, as it was."""
    model, params = tiny_model
    _mixed_widths_run(tiny_model)
    whole = jax.jit(lambda t, c: model.apply({"params": params}, t, c)[0])
    seen = mixed = 0
    for tokens, caches, got in ragged_calls:
        width, slots = tokens.shape[1], caches[0].cu_q_lens.shape[0] - 1
        assert slots == 10
        if (width > slots) != wide:
            continue
        seen += 1
        full = np.asarray(whole(tokens, caches))
        assert full.shape == (1, width, model.vocab)
        if not wide:
            np.testing.assert_array_equal(np.asarray(got), full)
            continue
        assert got.shape == (1, slots, model.vocab)
        cu = np.asarray(caches[0].cu_q_lens)
        rows = np.maximum(cu[1:] - 1, 0)
        decoding, active = (int(n) for n in caches[0].distribution)
        mixed += 0 < decoding < active  # decode rows beside a chunk
        np.testing.assert_array_equal(np.asarray(got)[0], full[0, rows])
    assert seen >= 3 and (mixed >= 3 or not wide)


def test_sampled_rows_add_no_compiled_signature(tiny_model, ragged_calls):
    """The gathered row count is a function of the input shapes: one
    executable per ``(width, q_tile)`` dispatched, as before."""
    _ragged_apply.clear_cache()
    _mixed_widths_run(tiny_model)
    shapes = {(t.shape[1], c[0].q_tile) for t, c, _ in ragged_calls}
    widths = {w for w, _ in shapes}
    assert min(widths) <= 10 < max(widths) and len(shapes) >= 3
    assert _ragged_apply._cache_size() == len(shapes)


def test_fetch_span_and_counter_report_rows_fetched_and_used(tiny_model):
    """`engine.step.fetch` carries what the sync moved (``bytes``,
    ``rows``) and what the host reads of it (``used``); the counter
    ``engine.step.logit_rows`` sums both under `obs.enable`.  A step
    over the slot count moves ``4 * slots * vocab`` bytes."""
    model, _ = tiny_model
    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        eng = _mixed_widths_run(tiny_model)
        events = obs.events()
        snap = obs.REGISTRY.snapshot()
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    by_name = {n: [e.get("fields", {}) for e in events if e["name"] == n]
               for n in ("engine.step.dispatch", "engine.step.fetch",
                         "engine.step.sample")}
    fetches = by_name["engine.step.fetch"]
    assert len(fetches) == len(by_name["engine.step.sample"]) > 0
    for fetch, sample in zip(fetches, by_name["engine.step.sample"]):
        assert fetch["bytes"] == 4 * model.vocab * fetch["rows"]
        assert fetch["used"] == sample["rows"] <= fetch["rows"]
    busy = [m for m in eng.metrics.steps
            if m.decode_tokens or m.prefill_tokens]
    assert len(fetches) == len(busy)
    wide = 0
    for fetch, dispatch, m in zip(
            fetches, by_name["engine.step.dispatch"], busy):
        assert fetch["rows"] == min(dispatch["width"], 10)
        assert fetch["used"] == m.num_decode_reqs + m.num_prefill_reqs
        wide += dispatch["width"] > 10
    assert 0 < wide < len(fetches)
    assert _counter_total(snap, "engine.step.logit_rows", kind="fetched") \
        == sum(f["rows"] for f in fetches)
    assert _counter_total(snap, "engine.step.logit_rows", kind="used") \
        == sum(f["used"] for f in fetches)


def test_dispatch_span_and_metrics_carry_the_kernels_grid_bound(
        tiny_model, ragged_calls):
    """``kv_pages``, counted on the host in the pack phase, is the
    count of work items the device builds for the same step; it rides
    the `engine.step.dispatch` span and `StepMetrics`, and the summary
    gives its mean as a share of the 10 x 2 page table."""
    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        eng = _mixed_widths_run(tiny_model)
        spans = [e["fields"] for e in obs.events()
                 if e["name"] == "engine.step.dispatch"]
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    busy = [m for m in eng.metrics.steps
            if m.decode_tokens or m.prefill_tokens]
    assert len(spans) == len(busy) == len(ragged_calls) > 0
    table = 10 * eng.config.table_width
    for span, m, (_, caches, _) in zip(spans, busy, ragged_calls):
        c = caches[0]  # as the step was handed it: lengths pre-append
        _, n = work_items(live_pages(
            c.kv_lens + jnp.diff(c.cu_q_lens), c.cu_q_lens, c.distribution,
            max_pages=eng.config.table_width, page=c.page_size,
            q_tile=c.q_tile, window=None, sinks=None))
        assert span["kv_pages"] == m.kv_pages == int(n)
        assert m.num_decode_reqs + m.num_prefill_reqs <= m.kv_pages < table
        # a walk reads its pages whole: the rows one sublayer reads
        assert (span["attn_rows"] == m.attn_rows_read
                == m.kv_pages * c.page_size)
        # this model's group is 2: the kernel has one tile for every span
        assert span["own_tile_spans"] == m.own_tile_spans == 0
        # and a grid step carries both of its KV heads: a layer's grid
        # is its work items
        assert (span["ragged_grid_steps"] == m.ragged_grid_steps
                == len(caches) * m.kv_pages)
    idle = [m for m in eng.metrics.steps if m not in busy]
    assert all(m.kv_pages == 0 and m.attn_rows_read == 0 for m in idle)
    summary = eng.metrics.summary()
    assert summary["mean_kv_page_share"] == round(
        sum(m.kv_pages for m in busy) / (len(busy) * table), 4)
    # no selector, no choice to read by
    assert "rows_read_per_key_attended" not in summary


def test_a_step_that_compiled_says_so():
    """The first step of a ``(width, q_tile)`` the process has not run
    has `StepMetrics.compile_s` > 0 and `compiled_programs` >= 1, the
    next step of that shape 0 / 0; the summary counts the steps that
    compiled and their distinct shapes; under `obs.enable` the ring
    holds one `engine.program.compiled` row a compiling step."""
    # a model of this test's own: nothing of it is compiled yet
    model = TinyDecoder(vocab=41, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        eng = ServingEngine(model, params, _cfg())
        eng.add_request(list(range(1, 20)), SamplingParams(max_tokens=5))
        # two more of the first's shapes, after it: nothing compiles
        while eng.scheduler.has_work():
            eng.step()
        eng.add_request(list(range(2, 21)), SamplingParams(max_tokens=3))
        while eng.scheduler.has_work():
            eng.step()
        events = obs.events()
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    shape_of = {}      # step -> (width, q_tile), from the dispatch spans
    for e in events:
        if e["name"] == "engine.step.dispatch":
            shape_of[len(shape_of)] = (e["fields"]["width"],
                                       e["fields"]["q_tile"])
    busy = [m for m in eng.metrics.steps
            if m.decode_tokens or m.prefill_tokens]
    assert len(busy) == len(shape_of) == len(eng.metrics.steps)
    seen = set()
    for i, m in enumerate(busy):
        if shape_of[i] in seen:
            assert (m.compile_s, m.compiled_programs) == (0.0, 0)
        else:
            seen.add(shape_of[i])
            assert m.compile_s > 0 and m.compiled_programs >= 1
            assert m.compile_s <= m.wall_s + 1e-3
    assert len(seen) == 2 < len(busy)
    summary = eng.metrics.summary()
    assert summary["compiled_steps"] == summary["programs"] == len(seen)
    assert eng.metrics.compiled_shapes == seen
    assert summary["compile_s_total"] == round(
        sum(m.compile_s for m in busy), 4) > 0
    marks = [e["fields"] for e in events
             if e["name"] == "engine.program.compiled"]
    compiled = [m for m in busy if m.compile_s]
    assert [(f["step"], (f["width"], f["q_tile"])) for f in marks] \
        == [(m.step, shape_of[m.step]) for m in compiled]
    for f in marks:
        assert f["cache"] == "off" and "_ragged_apply" in f["function"]
        assert f["compile_ms"] > 0 and f["trace_ms"] > 0


def test_31_decode_rows_beside_a_chunk_are_31_spans_at_their_own_tile():
    """`StepMetrics.own_tile_spans` and the dispatch span's field, at a
    group of 8: 31 on a step of 31 decode rows and a chunk (the
    kernel's program holds two tile bodies there), 0 on a decode-only
    step (one)."""
    model = TinyDecoder(vocab=43, dim=64, depth=1, num_q_heads=8,
                        num_kv_heads=1, impl="flash", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServingEngine(model, params, _cfg(
        num_pages=40, max_decode_batch=32, max_prefill_rows=1,
        token_budget=128))
    rng = np.random.default_rng(5)
    for _ in range(31):
        eng.add_request(rng.integers(0, model.vocab, size=4).tolist(),
                        SamplingParams(max_tokens=48))
    while len([r for r in eng.scheduler.running if r.output_tokens]) < 31:
        eng.step()
        assert eng.current_step < 40
    eng.add_request(rng.integers(0, model.vocab, size=40).tolist(),
                    SamplingParams(max_tokens=4))
    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        steps = [eng.step() for _ in range(4)]
        spans = [e["fields"] for e in obs.events()
                 if e["name"] == "engine.step.dispatch"]
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    # the prompt's chunks of 32 and 8 beside the 31, then 32 decode rows
    assert [(m.num_decode_reqs, m.prefill_tokens) for m in steps] == [
        (31, 32), (31, 8), (32, 0), (32, 0)]
    assert [m.own_tile_spans for m in steps] == [31, 31, 0, 0]
    assert [s["own_tile_spans"] for s in spans] == [31, 31, 0, 0]
    assert [s["ragged_grid_steps"] for s in spans] == [
        m.kv_pages for m in steps]          # one layer, one KV head
    assert [(s["width"], s["q_tile"]) for s in spans] == [
        (64, 32), (48, 8), (32, 1), (32, 1)]
