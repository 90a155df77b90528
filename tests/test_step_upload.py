"""One host-to-device transfer a busy step.

`ScheduledStep.pack` lays a step out in ONE int32 buffer whose named
fields are views; `ServingEngine._upload` puts that buffer once;
`_ragged_apply` splits it inside the jit (`_step_inputs`) into the
tokens and the index the layers always got.  Pinned here on tiny CPU
shapes: the segments against the fields and against a pack written as
a plain loop, one put and no host array in the dispatch, the logits of
the step as it was before the buffer (seven arrays and a host
``q_span``) to the bit, the tokens of `generate_paged`, one compiled
program a ``(width, q_tile)``, and the replicated buffer of a mesh
engine."""

import functools
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.engine import (
    EngineConfig,
    SamplingParams,
    ServingEngine,
    synthetic_trace,
)
from attention_tpu.engine import engine as engine_mod
from attention_tpu.engine.engine import (
    RaggedStepIndex,
    StepLayout,
    _ragged_apply,
    _slot_last_rows,
    _step_inputs,
)
from attention_tpu.engine.request import Request
from attention_tpu.engine.scheduler import (
    ScheduledStep,
    split_step_buffer,
    step_buffer_len,
)
from attention_tpu.engine.sim import replay
from attention_tpu.models import TinyDecoder, decoder_from_config
from attention_tpu.models.cache_layout import (
    STATE_ROWS,
    CacheLayout,
    LayerCache,
    state_step,
)
from attention_tpu.models.decode import generate_paged
from attention_tpu.obs import compiles

pytestmark = pytest.mark.engine


# --------------------------------------------------- the buffer's layout

_SLOTS, _TABLE = 4, 3


def _decoding(rid, tokens, pending, pages, state_slot=-1):
    req = Request(request_id=rid, prompt=tuple(tokens),
                  sampling=SamplingParams(max_tokens=8))
    req.computed_tokens = len(tokens)
    req.pending_token = pending
    req.pages = list(pages)
    req.state_slot = state_slot
    return req


def _prefilling(rid, prompt, computed, pages, state_slot=-1):
    req = Request(request_id=rid, prompt=tuple(prompt),
                  sampling=SamplingParams(max_tokens=8))
    req.computed_tokens = computed
    req.pages = list(pages)
    req.state_slot = state_slot
    return req


def _cases():
    """name -> (decode requests, prefill chunks, width, recurrent)."""
    return {
        "decode_only": lambda: (
            [_decoding("d0", (1, 2, 3), 7, [4, 5]),
             _decoding("d1", (9,), 5, [2])], [], 8, False),
        "one_chunk": lambda: (
            [], [(_prefilling("p0", range(1, 12), 2, [0, 6]), 9)], 16,
            False),
        "chunk_and_decode": lambda: (
            [_decoding("d0", (1, 2, 3), 7, [4, 5])],
            [(_prefilling("p0", (9, 8, 7, 6, 5), 2, [0]), 3)], 8, False),
        "recurrent_layers": lambda: (
            [_decoding("d0", (1, 2, 3), 7, [4, 5], state_slot=2)],
            [(_prefilling("p0", (9, 8, 7, 6, 5), 0, [1], state_slot=0),
              5)], 8, True),
    }


def _packed_by_a_loop(decode, prefill, width, recurrent):
    """The step's arrays made one by one, as `pack` made them before
    it wrote into one buffer: the reference its views are held to.
    Reads the requests BEFORE `pack` consumes their pending tokens."""
    items = [(r, 1) for r in decode] + list(prefill)
    out = {
        "tokens": np.zeros((1, width), np.int32),
        "token_slot": np.full((width,), -1, np.int32),
        "token_pos": np.zeros((width,), np.int32),
        "kv_lens": np.zeros((_SLOTS,), np.int32),
        "cu_q_lens": np.zeros((_SLOTS + 1,), np.int32),
        "distribution": np.asarray([len(decode), len(items)], np.int32),
        "tables": np.full((_SLOTS, _TABLE), -1, np.int32),
    }
    if recurrent:
        out["state_rows"] = np.full((_SLOTS,), -1, np.int32)
    off = 0
    for s, (req, n) in enumerate(items):
        c = req.computed_tokens
        fed = ([req.pending_token] if s < len(decode)
               else list(req.tokens[c:c + n]))
        for t, token in enumerate(fed):
            out["tokens"][0, off + t] = token
            out["token_slot"][off + t] = s
            out["token_pos"][off + t] = c + t
        out["kv_lens"][s] = c
        for p, page in enumerate(req.pages):
            out["tables"][s, p] = page
        if recurrent:
            out["state_rows"][s] = req.state_slot
        off += n
        out["cu_q_lens"][s + 1:] = off
    return out


@pytest.mark.parametrize("case", list(_cases()))
def test_the_buffers_segments_are_the_batchs_fields(case):
    """ONE contiguous int32 buffer of the documented length; every
    named field a VIEW of it; split by the function the jit uses, on
    the device, the segments equal the fields value for value and the
    loop's arrays; ``q_span`` carries the tile in its shape."""
    decode, prefill, width, recurrent = _cases()[case]()
    want = _packed_by_a_loop(decode, prefill, width, recurrent)
    batch = ScheduledStep(step=0, decode=decode, prefill=prefill).pack(
        width=width, slots=_SLOTS, table_width=_TABLE, recurrent=recurrent)

    buf = batch.buffer
    assert buf.dtype == np.int32 and buf.ndim == 1
    assert buf.flags["C_CONTIGUOUS"] and buf.flags["OWNDATA"]
    assert len(buf) == step_buffer_len(
        width, slots=_SLOTS, table_width=_TABLE, recurrent=recurrent)
    for name, expected in want.items():
        field = getattr(batch, name)
        assert np.shares_memory(field, buf), name
        np.testing.assert_array_equal(field, expected, err_msg=name)
    if not recurrent:
        assert batch.state_rows is None
    # the segments lie in the documented order, end to end
    order = ["tokens", "token_slot", "token_pos", "kv_lens", "cu_q_lens",
             "distribution", "tables"] + ["state_rows"] * recurrent
    np.testing.assert_array_equal(
        buf, np.concatenate([want[name].ravel() for name in order]))

    kept = CacheLayout((LayerCache(
        STATE_ROWS, (((2,), jnp.float32),) * 2, state_step),) * recurrent)
    model = types.SimpleNamespace(cache_layout=lambda: kept)
    layout = StepLayout(_SLOTS, _TABLE, q_tile=16)
    tokens, index = jax.jit(
        functools.partial(_step_inputs, model, layout=layout))(
            jax.device_put(buf))
    got = dict(index._asdict(), tokens=tokens, tables=index.page_table)
    for name, expected in want.items():
        assert got[name].dtype == jnp.int32
        assert got[name].shape == expected.shape, name
        np.testing.assert_array_equal(got[name], expected, err_msg=name)
    assert index.q_span.shape == (16,) and index.q_span.dtype == jnp.int32
    assert (index.state_rows is None) == (not recurrent)


def test_a_buffer_of_another_engines_length_is_refused():
    """The width is read from the buffer's length: a length that is no
    ``3 * width`` over the engine's constants is an error, not a step
    split at the wrong offsets."""
    n = step_buffer_len(8, slots=_SLOTS, table_width=_TABLE,
                        recurrent=False)
    with pytest.raises(ValueError, match="no packed step"):
        split_step_buffer(np.zeros((n + 1,), np.int32), slots=_SLOTS,
                          table_width=_TABLE, recurrent=False)
    with pytest.raises(ValueError, match="no packed step"):
        split_step_buffer(np.zeros((5,), np.int32), slots=_SLOTS,
                          table_width=_TABLE, recurrent=False)


def test_each_step_packs_into_a_buffer_of_its_own():
    """A buffer kept across steps would be rewritten under an upload
    that has not read it yet."""
    def pack():
        step = ScheduledStep(
            step=0, decode=[_decoding("d0", (1, 2, 3), 7, [4, 5])])
        return step.pack(width=8, slots=_SLOTS, table_width=_TABLE)

    first, second = pack(), pack()
    assert not np.shares_memory(first.buffer, second.buffer)


# ------------------------------------------------------ the engine's step


@pytest.fixture(scope="module")
def dense():
    model = TinyDecoder(vocab=43, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def recurrent():
    """Three gated-delta-rule layers to one attention layer (the toy
    cut of `tests/test_hybrid_engine.py`)."""
    model = decoder_from_config({
        "post_norm": True, "qk_norm": True, "vocab_size": 43,
        "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 4,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "hidden_act": "silu",
        "layer_types": ["linear_attention"] * 3 + ["full_attention"],
        "linear_num_key_heads": 2, "linear_num_value_heads": 2,
        "linear_key_head_dim": 16, "linear_value_head_dim": 32,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}, "torch_dtype": "float32",
    })
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _cfg(**overrides):
    kw = dict(num_pages=24, page_size=128, max_seq_len=256,
              max_decode_batch=8, max_prefill_rows=2, prefill_chunk=32,
              token_budget=80, watermark_pages=1)
    kw.update(overrides)
    return EngineConfig(**kw)


def _mixed_trace(model, n=8):
    """Prompts of 4-40 tokens over ten slots: decode-only steps, steps
    of one chunk, steps of chunks beside decode rows."""
    return synthetic_trace(n, vocab=model.vocab, seed=3, max_tokens=6,
                           prompt_len_min=4, prompt_len_max=40)


@pytest.fixture
def step_calls(monkeypatch):
    """Every packed batch and every `_ragged_apply` dispatch of the
    engines run under this fixture.  The dispatch runs with implicit
    host-to-device transfers DISALLOWED (an explicit `jax.device_put`
    stays allowed: the seam's), so a NumPy array among its arguments
    raises; the pools are copied first, because the step consumes
    them."""
    batches, calls = [], []
    pack = ScheduledStep.pack

    def packing(self, **kw):
        batches.append(pack(self, **kw))
        return batches[-1]

    def spy(model, params, buffer, pools, layout):
        before = jax.tree.map(jnp.copy, pools)
        with jax.transfer_guard_host_to_device("disallow"):
            out = _ragged_apply(model, params, buffer, pools, layout)
        calls.append(types.SimpleNamespace(
            buffer=buffer, pools=before, layout=layout, logits=out[0]))
        return out

    monkeypatch.setattr(ScheduledStep, "pack", packing)
    monkeypatch.setattr(engine_mod, "_ragged_apply", spy)
    return batches, calls


@pytest.mark.parametrize("family", ["dense", "recurrent"])
def test_a_busy_step_makes_one_put_and_hands_the_dispatch_no_host_array(
        family, request, step_calls, monkeypatch):
    """Through the engine's seam, once a busy step, goes the batch's
    own buffer and nothing else is put (`jax.device_put`,
    `jnp.asarray` and `jnp.array` of a NumPy array are counted over
    every step); the dispatch is handed device arrays only (the guard
    of `step_calls`); an idle step puts nothing."""
    model, params = request.getfixturevalue(family)
    batches, calls = step_calls
    eng = ServingEngine(model, params, _cfg())
    puts, seam = [], []
    upload = eng._upload

    def uploading(buffer):
        seam.append(buffer)
        return upload(buffer)

    eng._upload = uploading

    def counting(real, name):
        def put(x, *a, **kw):
            if isinstance(x, np.ndarray):   # not a trace's own arrays
                puts.append(name)
            return real(x, *a, **kw)
        return put

    for owner, name in ((jax, "device_put"), (jnp, "asarray"),
                        (jnp, "array")):
        monkeypatch.setattr(owner, name,
                            counting(getattr(owner, name), name))

    for t in _mixed_trace(model):
        eng.add_request(t["prompt"], SamplingParams(max_tokens=6),
                        request_id=t["id"], arrival=t["arrival"])
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=2),
                    request_id="late", arrival=200)
    busy = idle = 0
    while eng.scheduler.has_work():
        before = len(puts), len(seam), len(calls)
        m = eng.step()
        made = (len(puts) - before[0], len(seam) - before[1],
                len(calls) - before[2])
        if m.decode_tokens or m.prefill_tokens:
            busy += 1
            assert made == (1, 1, 1), (m.step, puts[before[0]:])
            assert seam[-1] is batches[-1].buffer
            assert isinstance(calls[-1].buffer, jax.Array)
            assert calls[-1].buffer.shape == batches[-1].buffer.shape
        else:
            idle += 1
            assert made == (0, 0, 0)
    assert busy >= 10 and idle >= 1
    assert puts == ["device_put"] * busy
    assert {c.layout[:2] for c in calls} == {(10, eng.config.table_width)}
    # steps of every kind were among them
    dists = [tuple(int(x) for x in b.distribution) for b in batches]
    assert any(d == a and a for d, a in dists)            # decode only
    assert any(d == 0 and a for d, a in dists)            # chunks only
    assert any(0 < d < a for d, a in dists)               # both
    assert all((b.state_rows is not None) == (family == "recurrent")
               for b in batches)


@functools.partial(jax.jit, static_argnames=("model",))
def _step_from_seven_arrays(model, params, tokens, pools, index):
    """The packed step as it was lowered before the one buffer: the
    tokens and a `RaggedStepIndex` of separately uploaded arrays, with
    a host ``q_span``.  The reference the buffer's step is held to."""
    rows = None
    if tokens.shape[1] > index.cu_q_lens.shape[0] - 1:
        rows = _slot_last_rows(index.cu_q_lens)
    logits, _ = model.apply({"params": params}, tokens,
                            model.cache_layout().steps(pools, index),
                            logit_rows=rows)
    return logits


@pytest.mark.parametrize("family", ["dense", "recurrent"])
def test_the_steps_logits_are_those_of_seven_uploads_bit_for_bit(
        family, request, step_calls):
    """Every step of a mixed run: the logits `_ragged_apply` made of
    the buffer equal, to the bit, those of the same model over the
    batch's fields uploaded one by one (what the engine did before)."""
    model, params = request.getfixturevalue(family)
    batches, calls = step_calls
    eng = ServingEngine(model, params, _cfg())
    _, out = replay(eng, _mixed_trace(model))
    assert all(len(tokens) == 6 for tokens in out.values())
    assert len(batches) == len(calls) >= 10
    mixed = 0
    for batch, call in zip(batches, calls):
        index = RaggedStepIndex(
            *(jnp.asarray(a, jnp.int32) for a in (
                batch.tables, batch.kv_lens, batch.cu_q_lens,
                batch.distribution, batch.token_pos, batch.token_slot)),
            np.zeros((call.layout.q_tile,), np.int32),
            None if batch.state_rows is None
            else jnp.asarray(batch.state_rows, jnp.int32))
        want = _step_from_seven_arrays(
            model, params, jnp.asarray(batch.tokens, jnp.int32),
            call.pools, index)
        np.testing.assert_array_equal(np.asarray(call.logits),
                                      np.asarray(want))
        decoding, active = (int(n) for n in batch.distribution)
        mixed += 0 < decoding < active
    assert mixed >= 3


def test_a_mixed_run_serves_the_tokens_of_generate_paged(dense):
    """Request for request, the tokens sequential `generate_paged`
    samples (greedy): the run before the change was held to the same."""
    model, params = dense
    trace = _mixed_trace(model)
    _, out = replay(ServingEngine(model, params, _cfg()), trace)
    for t in trace:
        toks, _caches, _pools = generate_paged(
            model, params, jnp.asarray([t["prompt"]], jnp.int32),
            jnp.asarray([len(t["prompt"])], jnp.int32), steps=6)
        assert out[t["id"]] == np.asarray(toks)[0].tolist(), t["id"]


@pytest.mark.parametrize("family", ["dense", "recurrent"])
def test_a_run_compiles_one_program_a_width_and_tile(family, request,
                                                     step_calls):
    """The buffer's length is a function of the width alone in one
    engine and the tile is static: as many compiled programs as
    ``(width, q_tile)`` shapes dispatched, as before."""
    model, params = request.getfixturevalue(family)
    batches, calls = step_calls
    _ragged_apply.clear_cache()
    replay(ServingEngine(model, params, _cfg()), _mixed_trace(model))
    shapes = {(b.width, c.layout.q_tile) for b, c in zip(batches, calls)}
    lengths = {(b.width, len(b.buffer)) for b in batches}
    assert len(shapes) >= 3
    assert len(lengths) == len({w for w, _ in shapes})
    assert _ragged_apply._cache_size() == len(shapes)


def test_a_mesh_engine_steps_with_the_replicated_buffer(dense):
    """``mesh_shards=2``: the one put lands the buffer whole on both
    devices of the mesh (the parameters' sharding), one put a busy
    step, and the run serves the single-device run's tokens."""
    model, params = dense
    trace = _mixed_trace(model, n=5)
    _, alone = replay(ServingEngine(model, params, _cfg()), trace)

    eng = ServingEngine(model, params, _cfg(mesh_shards=2))
    uploaded = []
    upload = eng._upload
    eng._upload = lambda buffer: (uploaded.append(upload(buffer)),
                                  uploaded[-1])[1]
    _, meshed = replay(eng, trace)
    assert meshed == alone
    busy = sum(1 for m in eng.metrics.steps
               if m.decode_tokens or m.prefill_tokens)
    assert len(uploaded) == busy > 0
    for buffer in uploaded:
        assert buffer.sharding.is_fully_replicated
        assert buffer.sharding.mesh.devices.tolist() == \
            eng.mesh.devices.tolist()
        assert len(buffer.addressable_shards) == 2


# ---------------------------------------------------- the set-up surface


@pytest.mark.parametrize("family", ["dense", "recurrent"])
def test_the_put_and_the_split_compile_nothing_of_their_own(
        family, request, monkeypatch):
    """The program's own compile log (`obs.compiles`) over a run of
    four ``(width, q_tile)`` shapes: a step at a new shape compiles
    exactly ONE program, whose costliest row is `_ragged_apply`; a
    step at a seen shape moves the log by nothing, so neither the put
    nor the split inside the jit traces, lowers or compiles anything
    that `setup_s` would pay for apart from the step itself."""
    base, _ = request.getfixturevalue(family)
    # a model of this test's own (the step is jitted on the model):
    # nothing of it is compiled yet, whatever ran before in the process
    model = base.clone(vocab=47)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    shapes = []
    apply = engine_mod._ragged_apply

    def spy(model, params, buffer, pools, layout):
        shapes.append((buffer.shape[0], layout.q_tile))
        return apply(model, params, buffer, pools, layout)

    monkeypatch.setattr(engine_mod, "_ragged_apply", spy)
    eng = ServingEngine(model, params, _cfg())
    # chunks of 5, 12 and 32 tokens alone, decode rows alone, and a
    # chunk of 32 beside a decode row: tiles of 8 to 32, widths 8 to 48
    seen, again = set(), 0
    for prompt, arrival in ((5, 0), (12, 8), (40, 16), (70, 18)):
        eng.add_request([1 + t % 40 for t in range(prompt)],
                        SamplingParams(max_tokens=4), arrival=arrival)
    while eng.scheduler.has_work():
        rows, stamp, calls = compiles.count, time.perf_counter(), len(shapes)
        eng.step()
        if len(shapes) == calls or shapes[-1] in seen:
            # an idle step, or a shape that has its program
            assert compiles.count == rows, shapes[-1:]
            again += len(shapes) > calls
            continue
        seen.add(shapes[-1])
        log = compiles.summary(since=stamp)
        assert log["programs"] == 1, (shapes[-1], log["by_function"])
        assert "_ragged_apply" in log["by_function"][0]["function"]
    assert len(seen) >= 4 and again >= 4
    assert len({tile for _, tile in seen}) >= 2
    assert len({length for length, _ in seen}) >= 2
