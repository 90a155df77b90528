"""Compile the kernels for a real TPU v5e from the CPU test process.

The CPU suite runs every Pallas kernel interpreted, and
interpret-green does not imply Mosaic-green.  The installed libtpu can
describe a compile-only ``v5e:2x2`` topology without a chip, and
lowering against its devices runs the real Mosaic / XLA:TPU compiler —
so what the compiler refuses is found here, not on the chip budget.
Nothing executes: these tests pin "compiles", the chip run pins "is
right" (`chip_smoke.py`, `scripts/tpu_smoke.py`).

Marked ``slow`` (run it with ``-m slow``; ~30 s): building the topology
goes through libtpu's multi-process lockfile.  On a TPU host whose chip
another process holds it fails ("Internal error when accessing libtpu
multi-process lockfile" — the fixture skips with that reason), and the
other way round a test process sitting on that lockfile is in a chip
process's way, so tier-1 does not touch it.  In the CPU sandbox there
is no chip and nothing to contend for.
"""

import functools
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from attention_tpu.engine.engine import StepLayout, _ragged_apply
from attention_tpu.engine.scheduler import step_buffer_len
from attention_tpu.models import TinyDecoder, decoder_from_config
from attention_tpu.ops import (
    decode,
    experts,
    flash,
    gated_delta,
    paged,
    quant,
    ragged_paged,
    sparse_index,
    ssm,
)
from attention_tpu.ops.flash_vjp import flash_attention_diff
from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    ragged_paged_attention,
)

pytestmark = pytest.mark.slow

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def v5e():
    """The four compile-only v5e devices, with the kernels' interpret
    default (`ops.flash._should_interpret`: anything but a TPU default
    backend interprets) pinned off in every module that imported it."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 - any plugin failure is a skip
        pytest.skip(f"no compile-only TPU topology here: "
                    f"{type(e).__name__}: {str(e)[:200]}")
    with pytest.MonkeyPatch.context() as mp:
        for mod in (flash, decode, paged, quant, ragged_paged, gated_delta,
                    ssm, experts, sparse_index):
            mp.setattr(mod, "_should_interpret", lambda: False)
        yield list(topo.devices)


def _placed(sharding, args):
    """Abstract ``args`` (pytrees of ShapeDtypeStructs) placed by
    ``sharding`` (one sharding, or a pytree prefix of ``args``)."""
    return jax.tree.map(
        lambda s, sub: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            sub),
        sharding, args,
        is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))


def _compile(fn, sharding, *args):
    """Lower ``fn`` for the placed ``args`` and run the TPU compiler."""
    return jax.jit(fn).lower(*_placed(sharding, args)).compile()


def _compile_step(model, sharding, params, pools, width, q_tile, *,
                  slots=10, max_pages=34):
    """The engine's own jitted step, donation and all (a jit around it
    would keep the caller's pools alive), over the ONE int32 buffer a
    step of ``width`` uploads.  ``sharding`` places params, buffer and
    pools (one sharding, or one each)."""
    buffer = _a((step_buffer_len(
        width, slots=slots, table_width=max_pages,
        recurrent=bool(getattr(model, "recurrent_layers", ())),
        window_tables=bool(getattr(model, "window_layers", ()))),), I32)
    return _ragged_apply.lower(
        model, *_placed(sharding, (params, buffer, pools)),
        StepLayout(slots, max_pages, q_tile)).compile()


def _a(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ragged_cache(hkv, width, q_tile, dtype, *, pages=64, d=128, slots=10,
                  max_pages=34):
    pool = _a((pages, hkv, 128, d), dtype)
    return RaggedPagedStep(
        pool, pool, _a((slots, max_pages), I32), _a((slots,), I32),
        _a((slots + 1,), I32), _a((2,), I32), _a((width,), I32),
        _a((width,), I32), _a((q_tile,), I32))


def _device_bytes(sharding, pools):
    """What ``pools`` take on one device, tile padding and all: the
    arguments of a program that takes nothing else."""
    compiled = _compile(lambda tree: jax.tree.map(lambda a: a + 1, tree),
                        sharding, pools)
    return compiled.memory_analysis().argument_size_in_bytes


def _pool_shaped(compiled, pools, ops):
    """Lines of the compiled step in which one of ``ops`` makes an
    array of the shape of one of ``pools``."""
    shapes = {"[" + ",".join(map(str, a.shape)) + "]"
              for a in jax.tree.leaves(pools)}
    return [line.strip()[:200] for line in compiled.as_text().splitlines()
            if re.search(rf"= \S+ ({ops})\(", line)
            and any(s in line for s in shapes)]


def _assert_in_place(compiled, model, pools, sharding):
    """Every donated pool is aliased to the step's result, none is
    copied or transposed (the out-of-place update, the relayouts around
    XLA's scatter), and no K / V pool goes through a scatter at all.
    (The convolution tails, ten rows of 69 KB, are an XLA scatter on
    their leading axis, in place.)"""
    assert (compiled.memory_analysis().alias_size_in_bytes
            == _device_bytes(sharding, pools))
    assert _pool_shaped(compiled, pools, "copy|transpose") == []
    kv_pools = [pools[layer] for layer in model.attention_layers]
    assert _pool_shaped(compiled, kv_pools, "scatter") == []


def _ragged_kernel_calls(compiled):
    """The compiled step's attention kernels, as (the Mosaic calls, the
    arrays they take their work lists from): `_ragged_paged_attention_jit`
    names the custom call, and its sixth operand, after the grid's
    bound and ``lens`` / ``cu`` / ``dist`` / the page table, is the
    list."""
    calls = [line for line in compiled.as_text().splitlines()
             if re.match(r"\s*%_ragged_paged_attention_jit\S* = ", line)
             and 'custom_call_target="tpu_custom_call"' in line]
    lists = {re.search(r"/\*index=5\*/(%[\w.\-]+)", line).group(1)
             for line in calls}
    return calls, lists


def _assert_one_kernel_a_layer(compiled, model):
    """One Mosaic call an attention layer whatever the shape, each
    with a traced grid bound (its first operand, a scalar), and ONE
    work list a step: the layers' identical copies are merged."""
    calls, lists = _ragged_kernel_calls(compiled)
    assert len(calls) == len(model.attention_layers)
    assert all("operand_layout_constraints={s32[]," in c for c in calls)
    assert len(lists) == 1


def test_ragged_engine_step_at_smoke_width(v5e):
    """The whole jitted engine step `chip_smoke.py` serves: dim 4096,
    32q/4kv x 128, depth 4, vocab 32768, bf16, packed width 512."""
    model = TinyDecoder(vocab=32768, dim=4096, depth=4, num_q_heads=32,
                        num_kv_heads=4, impl="flash", rope=True)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), I32))["params"]
    pool = _a((2048, 4, 128, 128), BF16)
    pools = ((pool, pool),) * model.depth
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    compiled = _compile_step(model, one, params, pools, 512, 256)
    # the pools are donated and written in place: the step holds them
    # once, and every byte of them is the caller's buffer
    _assert_in_place(compiled, model, pools, one)
    assert _device_bytes(one, pools) == 2 * 4 * 2048 * 4 * 128 * 128 * 2
    _assert_one_kernel_a_layer(compiled, model)


def _starcoder2_cell():
    """One layer of the benchmark's StarCoder2 cells: 4 KV heads, 768
    pages, 32 + 1 slots."""
    model = TinyDecoder(vocab=49152, dim=4608, depth=1, num_q_heads=36,
                        num_kv_heads=4, impl="flash", window=4096,
                        rope=True, rope_theta=1e6)
    pool = _a((768, 4, 128, 128), BF16)
    return model, ((pool, pool),), dict(slots=33, max_pages=34)


def _olmo_hybrid_cell():
    """One period (three recurrent layers, one attention layer) of the
    benchmark's Olmo-Hybrid cell: 30 KV heads, 416 pages, 8 + 1 slots
    with a state row each and the spare."""
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "configs"
            / "olmo-hybrid-7b.json")
    config = dict(json.loads(path.read_text()), num_hidden_layers=4)
    model = decoder_from_config(config)
    state, conv = model.recurrent_state_shapes()
    pool = _a((416, 30, 128, 128), BF16)
    pair = (_a((10, *state), F32), _a((10, *conv), BF16))
    pools = tuple(pair if layer in model.recurrent_layers else (pool, pool)
                  for layer in range(model.depth))
    return model, pools, dict(slots=9, max_pages=52)


@pytest.mark.parametrize("width,q_tile", [(384, 256), (8, 8)],
                         ids=["chunk_step", "decode_only"])
@pytest.mark.parametrize("cell", [_starcoder2_cell, _olmo_hybrid_cell],
                         ids=["starcoder2_4kv_768pages",
                              "olmo_hybrid_30kv_416pages"])
def test_ragged_engine_step_updates_the_cells_pools_in_place(
        v5e, cell, width, q_tile):
    """At both served configurations' pool shapes the compiled step
    aliases every donated pool (K, V, recurrent state, convolution
    tail) to its result and holds no copy, transpose or scatter of a
    pool's shape; and the decode-only step, like the chunk step, holds
    one attention kernel a layer over a list of the step's live
    (slot, page) pairs (33 x 34 at 4 KV heads; 9 x 52 at 30)."""
    model, pools, index = cell()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), I32))["params"]
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    compiled = _compile_step(model, one, params, pools, width, q_tile,
                             **index)
    _assert_in_place(compiled, model, pools, one)
    _assert_one_kernel_a_layer(compiled, model)


@pytest.mark.parametrize("width,q_tile,rows", [
    (384, 256, 33),   # a 256-token chunk beside decode rows: gathered
    (32, 8, 32),      # decode-only, within the 33 slots: every row
])
def test_ragged_engine_step_projects_the_sampled_rows(v5e, width, q_tile,
                                                      rows):
    """The step of the benchmark's StarCoder2 cells (hidden 4608,
    36q/4kv x 128, vocab 49152, window 4096, 32 + 1 slots; one layer
    of the eight): over the slot count it gathers the slots' last rows
    ahead of the final norm and the float32 head and returns
    ``(1, 33, vocab)``, 6.5 MB where ``(1, 384, vocab)`` was 75 MB."""
    model = TinyDecoder(vocab=49152, dim=4608, depth=1, num_q_heads=36,
                        num_kv_heads=4, impl="flash", window=4096,
                        rope=True, rope_theta=1e6)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), I32))["params"]
    pool = _a((768, 4, 128, 128), BF16)
    compiled = _compile_step(
        model, jax.sharding.SingleDeviceSharding(v5e[0]),
        params, ((pool, pool),), width, q_tile, slots=33, max_pages=32)
    logits = compiled.out_info[0]
    assert (logits.shape, logits.dtype) == ((1, rows, 49152), F32)


def test_ragged_engine_step_head_sharded_over_four_devices(v5e):
    mesh = Mesh(np.asarray(v5e), ("tp",))
    model = TinyDecoder(vocab=32768, dim=4096, depth=1, num_q_heads=32,
                        num_kv_heads=4, impl="flash", rope=True,
                        tp_axis="tp", mesh=mesh)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), I32))["params"]
    rep = NamedSharding(mesh, P())
    by_head = NamedSharding(mesh, P(None, "tp", None, None))
    pool = _a((256, 4, 128, 128), BF16)
    compiled = _compile_step(model, (rep, rep, by_head),
                             params, ((pool, pool),), 512, 256)
    # each device's quarter of the pools, in place there too
    _assert_in_place(compiled, model, ((pool, pool),), by_head)
    assert _device_bytes(by_head, (pool, pool)) == 2 * 256 * 128 * 128 * 2
    _assert_one_kernel_a_layer(compiled, model)


_CELL_TABLES = (dict(slots=33, max_pages=34), dict(slots=9, max_pages=52),
                dict(slots=33, max_pages=266))


@pytest.mark.parametrize("hq,hkv,width,q_tile,dtype,table,band", [
    # 16-bit with group < 8: Mosaic refused the unaligned dynamic
    # sublane slice ("cannot statically prove ... a multiple of 8")
    (8, 2, 512, 256, BF16, {}, {}),     # group 4
    (8, 4, 24, 4, BF16, {}, {}),        # group 2, a decode-only step
    (8, 8, 512, 64, BF16, {}, {}),      # group 1 (MHA)
    (8, 2, 512, 256, F32, {}, {}),
    (32, 4, 2048, 1024, BF16, {}, {}),  # 26 MB scoped VMEM > 16 MB default
    # the benchmark's cells, decode-only and MIXED (a chunk of 256
    # beside decode rows): a grid of (4, n <= 33 x 34) at a group of 9,
    # of (30, n <= 9 x 52) at 1, one tile body each; of (4, n <= 33 x
    # 266) at Trinity's 8, where the mixed programs hold TWO, the
    # chunk's 2,048 rows and a decode row's 8, a window layer's and a
    # full layer's
    (36, 4, 8, 8, BF16, _CELL_TABLES[0], {}),
    (36, 4, 384, 256, BF16, _CELL_TABLES[0], {}),
    (30, 30, 8, 8, BF16, _CELL_TABLES[1], {}),
    (30, 30, 384, 256, BF16, _CELL_TABLES[1], {}),
    (32, 4, 32, 1, BF16, _CELL_TABLES[2], {}),
    (32, 4, 384, 256, BF16, _CELL_TABLES[2], {}),
    (32, 4, 384, 256, BF16, _CELL_TABLES[2], dict(window=2048)),
    (32, 4, 256, 256, BF16, _CELL_TABLES[2], dict(window=2048)),
    # Nemotron's group of 16, mixed: two bodies, 4,096 rows and 16
    (32, 2, 384, 256, BF16, {}, {}),
    # a grid step carries every KV head of its page (`head_block`): the
    # cells' widest steps, 4 heads x 4,096 rows of query and result
    # resident at Trinity's width of 512, the chunk's tile a loop over
    # the heads and a decode row's a batch of them; a decode-only window
    # layer; StarCoder2's window of 4,096 (a batch of 4 x 80 rows, and a
    # loop at 2,312); Olmo's 30 heads at a width of 512; Nemotron's 2 at
    # its 64 decode rows
    (32, 4, 512, 256, BF16, _CELL_TABLES[2], {}),
    (32, 4, 512, 256, BF16, _CELL_TABLES[2], dict(window=2048)),
    (32, 4, 32, 1, BF16, _CELL_TABLES[2], dict(window=2048)),
    (36, 4, 8, 8, BF16, _CELL_TABLES[0], dict(window=4096)),
    (36, 4, 512, 256, BF16, _CELL_TABLES[0], dict(window=4096)),
    (30, 30, 512, 256, BF16, _CELL_TABLES[1], {}),
    (32, 2, 64, 1, BF16, dict(slots=65, max_pages=16), {}),
])
def test_ragged_kernel_compiles(v5e, hq, hkv, width, q_tile, dtype, table,
                                band):
    # every head in one block at the cells' widths; half of them where
    # four heads' rows and scratch are over the core's VMEM
    assert ragged_paged.head_block(
        hkv, q_tile, width, hq // hkv, d=128, dv=128, page=128,
        q_itemsize=dtype.dtype.itemsize, kv_itemsize=dtype.dtype.itemsize,
    ) == (hkv if width < 2048 else 2)
    compiled = _compile(
        functools.partial(ragged_paged_attention, **band),
        jax.sharding.SingleDeviceSharding(v5e[0]),
        _a((1, hq, width, 128), dtype),
        _ragged_cache(hkv, width, q_tile, dtype, **table))
    calls, _ = _ragged_kernel_calls(compiled)
    assert len(calls) == 1


@pytest.mark.parametrize("width, q_tile", [
    (8, 8), (48, 24), (128, 96), (384, 256)])
def test_gated_delta_kernel_compiles_at_the_published_widths(
        v5e, width, q_tile):
    """The recurrent layers' kernel at Olmo-Hybrid-7B's head sizes (30
    heads, keys of 96, values of 192), 9 slots: a decode-only step,
    chunks of 8, 32 and 64."""
    heads, dk, dv, slots = 30, 96, 192, 9
    step = gated_delta.RaggedStateStep(
        _a((slots + 1, heads, dk, dv), F32),
        _a((slots + 1, 3, heads * (2 * dk + dv)), BF16),
        _a((slots,), I32), _a((slots,), I32), _a((slots + 1,), I32),
        _a((width,), I32), _a((q_tile,), I32))
    _compile(gated_delta.ragged_gated_delta,
             jax.sharding.SingleDeviceSharding(v5e[0]),
             _a((width, heads, dk), F32), _a((width, heads, dk), F32),
             _a((width, heads, dv), BF16), _a((width, heads), F32),
             _a((width, heads), F32), step)


@pytest.mark.parametrize("width, q_tile", [
    (64, 1), (96, 8), (48, 24), (256, 192), (384, 256)])
def test_ssm_scan_kernel_compiles_at_the_published_widths(v5e, width,
                                                          q_tile):
    """The state-space layers' kernel at Nemotron-3-Super's sizes (128
    heads of 64, state 128, 8 groups), 65 slots: decode-only steps at
    a tile of one token, chunks of 8, 64 and 128 (the cell's widest
    step: a chunk of 256 beside 64 decode rows)."""
    heads, p, n, groups, slots = 128, 64, 128, 8, 65
    step = gated_delta.RaggedStateStep(
        _a((slots + 1, heads, p, n), F32),
        _a((slots + 1, 3, heads * p + 2 * groups * n), BF16),
        _a((slots,), I32), _a((slots,), I32), _a((slots + 1,), I32),
        _a((width,), I32), _a((q_tile,), I32))
    _compile(ssm.ragged_ssm_scan, jax.sharding.SingleDeviceSharding(v5e[0]),
             _a((width, heads, p), BF16), _a((width, heads), F32),
             _a((width, heads), F32), _a((width, groups, n), BF16),
             _a((width, groups, n), BF16), step)


@pytest.mark.parametrize("tokens", [8, 64, 192, 768])
def test_grouped_experts_kernel_compiles_at_the_published_widths(v5e,
                                                                 tokens):
    """The routed experts' grouped product at Nemotron-3-Super's sizes
    (64 held experts of 1024 x 2688, float32 as stored, top-22): row
    tiles of 8, 16 and 32."""
    tile = experts.row_tile(tokens)
    rows = experts.layout_rows(tokens, 22, 64, tile)
    layout = experts.ExpertLayout(
        _a((tokens, 22), I32), _a((rows,), I32), _a((rows // tile,), I32),
        _a((), I32), _a((64,), I32))
    _compile(functools.partial(experts.grouped_experts, tile=tile),
             jax.sharding.SingleDeviceSharding(v5e[0]),
             _a((rows, 1024), BF16), _a((64, 1024, 2688), F32),
             _a((64, 2688, 1024), F32), layout)


def _nemotron_cell():
    """The benchmark's Nemotron-3-Super cell whole: 11 one-sublayer
    blocks (5 state-space, 5 sparse-expert, 1 attention), 64 + 1 slots
    with a state row each and the spare, 1048 pages at 2 KV heads."""
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "configs"
            / "nemotron-3-super-120b.json")
    model = decoder_from_config(json.loads(path.read_text()))
    state, conv = model.recurrent_state_shapes()
    pool = _a((1048, 2, 128, 128), BF16)
    pair = (_a((66, *state), F32), _a((66, *conv), BF16))
    pools = tuple(pair if layer in model.recurrent_layers
                  else (pool, pool) if layer in model.attention_layers
                  else None for layer in range(model.depth))
    return model, pools, dict(slots=65, max_pages=18)


@pytest.mark.parametrize("width,q_tile", [(384, 256), (64, 1)],
                         ids=["chunk_step", "decode_only"])
def test_the_nemotron_cells_step_fits_the_chip_in_place(v5e, width, q_tile):
    """The whole served step of the Nemotron cell compiles for one v5e
    chip: every donated pool aliased to its result, and arguments +
    temporaries inside the chip's 16 GB."""
    model, pools, index = _nemotron_cell()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), I32))["params"]
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    compiled = _compile_step(model, one, params, pools, width, q_tile,
                             **index)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 15.0e9, total     # 12.55 GB of arguments + 0.13 GB
    pooled = sum(np.prod(a.shape) * a.dtype.itemsize
                 for a in jax.tree.leaves(pools))
    assert mem.alias_size_in_bytes >= pooled


def _longcat_cell():
    """The benchmark's LongCat-Flash-Omni cell whole: 4 double layers
    (8 latent-attention, 8 dense and 4 expert sublayers), 32 + 1 slots,
    a table row of 200 pages, 1,824 pages of ONE latent pool a
    sublayer; parameters in bfloat16, the router's in float32, as the
    cell's reference makes them."""
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "configs"
            / "longcat-flash-omni.json")
    config = json.loads(path.read_text())
    model = decoder_from_config(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), I32))["params"]
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: _a(a.shape, F32 if "router" in jax.tree_util.keystr(p)
                        else BF16), shapes)
    heads, (width,) = model.kv_pool_widths()
    pool = _a((config["engine"]["num_pages"], heads, 128, width), BF16)
    return model, params, tuple((pool, pool) for _ in range(model.depth)), \
        dict(slots=33, max_pages=200)


@pytest.mark.parametrize("width,q_tile", [(288, 256), (384, 256), (32, 1)],
                         ids=["chunk_step", "widest_step", "decode_only"])
def test_the_longcat_cells_step_fits_the_chip_in_place(v5e, width, q_tile):
    """(k): the whole served step of the LongCat cell compiles for one
    v5e chip at the cell's sizes, the ragged kernel in its row-blocked
    form on pools of (1824, 1, 128, 640): every donated pool aliased to
    its result, and arguments + temporaries inside the chip's 16 GB
    (12.77 GB of arguments, 10.35 of them parameters and 2.39 the
    latent cache, + 0.22 GB of temporaries at a chunk step)."""
    model, params, pools, index = _longcat_cell()
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    compiled = _compile_step(model, one, params, pools, width, q_tile,
                             **index)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 12.7e9 < total < 13.4e9, total
    pooled = sum(np.prod(a.shape) * a.dtype.itemsize
                 for a in jax.tree.leaves(pools))
    assert pooled == 8 * 1824 * 128 * 640 * 2
    assert mem.alias_size_in_bytes >= pooled
    # what a token costs in the cache, tile padding and all: 640 lanes
    # of 2 bytes in each of 8 pools, and no V pool
    assert _device_bytes(one, pools) == pooled == 1824 * 128 * 10240


def test_the_gated_experts_kernel_compiles_at_the_cells_sizes(v5e):
    """16 held experts of 6144 x 2048 in bfloat16, top-12 of 768: the
    row tiles of a decode step (8) and of a chunk step (32)."""
    for tokens in (32, 288):
        tile = experts.row_tile(tokens)
        rows = experts.layout_rows(tokens, 12, 16, tile)
        layout = experts.ExpertLayout(
            _a((tokens, 12), I32), _a((rows,), I32), _a((rows // tile,), I32),
            _a((), I32), _a((16,), I32))
        _compile(functools.partial(experts.grouped_gated_experts, tile=tile),
                 jax.sharding.SingleDeviceSharding(v5e[0]),
                 _a((rows, 6144), BF16), _a((16, 6144, 2048), BF16),
                 _a((16, 6144, 2048), BF16), _a((16, 2048, 6144), BF16),
                 layout)


def _config_cell(name: str):
    """A benchmark configuration's file, the model the program builds
    from it, and its parameters' shapes in bfloat16 (the router's in
    float32), as the cells' references make them."""
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "configs"
            / f"{name}.json")
    config = json.loads(path.read_text())
    model = decoder_from_config(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), I32))["params"]
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: _a(a.shape, F32 if "router" in jax.tree_util.keystr(p)
                        else BF16), shapes)
    return config, model, params


def _deepseek_cell():
    """The benchmark's DeepSeek-V3.2-Exp cell whole: one dense and four
    expert layers, 32 + 1 slots, a table row of 392 pages, 1,856 pages
    of a latent pool AND an index pool a layer; parameters in bfloat16,
    the router's in float32, as the cell's reference makes them."""
    config, model, params = _config_cell("deepseek-v3.2-exp")
    heads, widths = model.kv_pool_widths()
    pools = tuple(
        tuple(_a((config["engine"]["num_pages"], heads, 128, w), BF16)
              for w in widths) for _ in range(model.depth))
    return model, params, pools, dict(slots=33, max_pages=392)


@pytest.mark.parametrize("width,q_tile", [(384, 256), (32, 1)],
                         ids=["widest_step", "decode_only"])
def test_the_deepseek_cells_step_fits_the_chip_in_place(v5e, width, q_tile):
    """The whole served step of the DeepSeek cell compiles for one v5e
    chip at the cell's sizes: `index_scores` over pools of (1856, 1,
    128, 128), `index_select` over 8 x 50,176 scores a group and the
    lists of 2,048 positions it makes of them, the list kernel
    fetching those rows of pools of (1856, 1, 128, 640): every donated
    pool aliased to its result, and
    arguments + temporaries inside the chip's 16 GB (11.11 GB of
    arguments, 9.29 of them parameters and 1.82 the two caches, + 0.34
    GB of temporaries at the widest step)."""
    model, params, pools, index = _deepseek_cell()
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    compiled = _compile_step(model, one, params, pools, width, q_tile,
                             **index)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 11.2e9 < total < 11.6e9, total
    pooled = sum(np.prod(a.shape) * a.dtype.itemsize
                 for a in jax.tree.leaves(pools))
    # what a token costs in the caches: 640 + 128 lanes of 2 bytes in
    # each of 5 layers
    assert pooled == 1856 * 128 * 5 * 1536
    assert mem.alias_size_in_bytes >= pooled
    text = compiled.as_text()
    for name in ("index_scores", "index_select", "kv_row_append",
                 "ragged_paged_list_attention"):
        assert text.count(f'"{name}"') >= 5 or text.count(name) >= 5, name


def _trinity_cell():
    """The benchmark's Trinity-Mini cell whole: one dense and eight
    expert layers, 7 of the 9 behind a window; 32 + 1 slots, TWO table
    rows of 266 pages a slot, 9,024 pages of K and V in each full
    layer and 1,280 in each window layer; parameters in bfloat16, the
    router's in float32, as the cell's reference makes them."""
    config, model, params = _config_cell("trinity-mini")
    heads, widths = model.kv_pool_widths()
    engine = config["engine"]
    pools = tuple(
        tuple(_a((engine["num_window_pages" if layer in model.window_layers
                         else "num_pages"], heads, 128, w), BF16)
              for w in widths) for layer in range(model.depth))
    return model, params, pools, dict(slots=33, max_pages=266)


@pytest.mark.parametrize("width,q_tile", [(384, 256), (32, 1)],
                         ids=["widest_step", "decode_only"])
def test_the_trinity_cells_step_fits_the_chip_in_place(v5e, width, q_tile):
    """The whole served step of the Trinity-Mini cell compiles for one
    v5e chip at the cell's sizes: the K / V ragged kernel at 32 query
    heads on 4 of 128 lanes behind a window of 2,048 in seven layers
    and without one in two, pools of two sizes under two tables, the
    gated experts at a width of 1,024: every donated pool aliased to
    its result, and arguments + temporaries inside the chip's 16 GB
    (9.57 GB of arguments: 2.49 parameters, 4.73 + 2.35 the two page
    spaces)."""
    model, params, pools, index = _trinity_cell()
    assert model.window_layers == (0, 1, 2, 3, 5, 6, 7)
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    compiled = _compile_step(model, one, params, pools, width, q_tile,
                             **index)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print("trinity step", width, q_tile, mem.argument_size_in_bytes,
          mem.temp_size_in_bytes, total)
    assert 9.5e9 < total < 11e9, total
    pooled = sum(np.prod(a.shape) * a.dtype.itemsize
                 for a in jax.tree.leaves(pools))
    assert pooled == (2 * 9024 + 7 * 1280) * 262144
    assert mem.alias_size_in_bytes >= pooled
    calls, _ = _ragged_kernel_calls(compiled)
    assert len(calls) == 9


def test_the_choosing_kernels_compile_at_the_cells_sizes(v5e):
    """`select_keys` (the scoring, the threshold and the list-making)
    and the list kernel alone, at the widest step and a decode-only
    step of the cell: 128 heads, rows of 640 lanes, a table row of 392
    pages, lists of 2,048 positions."""
    one = jax.sharding.SingleDeviceSharding(v5e[0])

    def chosen_attention(q, q_i, w_i, cache):
        select = sparse_index.select_keys(q_i, w_i, cache, top_k=2048,
                                          group=128)
        assert select.shape == (q.shape[2], 2048)
        return ragged_paged_attention(q, cache, scale=0.1, value_dim=512,
                                      select=select)

    for width, q_tile in ((384, 256), (32, 1)):
        cache = RaggedPagedStep(
            _a((1856, 1, 128, 640), BF16), None, _a((33, 392), I32),
            _a((33,), I32), _a((34,), I32), _a((2,), I32), _a((width,), I32),
            _a((width,), I32), _a((q_tile,), I32),
            _a((1856, 1, 128, 128), BF16))
        _compile(chosen_attention, one, _a((1, 128, width, 640), BF16),
                 _a((width, 64, 128), BF16), _a((width, 64), F32), cache)


def test_ladder_kernels_compile(v5e):
    """One row each of the old kernel ladder: flash forward in bound
    mode, the fused backward, bf16 / int8 / paged decode."""
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    seq = _a((8192, 128), BF16)
    _compile(functools.partial(flash.flash_attention, max_mode="bound"),
             one, seq, seq, seq)

    def grads(q, k, v):
        return jax.grad(
            lambda *qkv: jnp.sum(flash_attention_diff(
                *qkv, causal=True).astype(F32)), argnums=(0, 1, 2))(q, k, v)

    _compile(grads, one, seq, seq, seq)

    q = _a((8, 32, 128), BF16)
    lens = _a((8,), I32)
    kv = _a((8, 4, 8192, 128), BF16)
    _compile(decode.flash_decode, one, q, kv, kv, lens)
    kv8, scale = _a((8, 4, 8192, 128), jnp.int8), _a((8, 4, 8, 8192), F32)
    _compile(quant.flash_decode_quantized, one, q,
             quant.QuantizedKV(kv8, scale, kv8, scale), lens)
    pool = _a((512, 4, 128, 128), BF16)
    _compile(paged.paged_flash_decode, one, q,
             paged.PagedKV(pool, pool, _a((8, 64), I32), lens))
