"""Mesh-sharded serving engine tests (EngineConfig.mesh_shards).

The tentpole contract, pinned on the 8-device simulated CPU mesh from
conftest: an engine whose jitted launches lower onto KV-head-sharded
paged kernels (`parallel.serving.head_sharded_ragged_step`) is
TOKEN-FOR-TOKEN identical to the single-device engine — greedy and
sampled, through preemption, warm restart from a
per-shard snapshot, and a kill+migrate chaos storm — while still
making exactly one launch per busy step.  Geometry that cannot split
is a typed `MeshConfigError` at call/construct time, and damage to
ONE shard's snapshot section is a typed per-shard refusal that
degrades to cold recovery, never to wrong tokens.
"""

import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from attention_tpu import obs
from attention_tpu.chaos.faults import run_crash_campaign
from attention_tpu.chaos.invariants import snapshot_roundtrip_violations
from attention_tpu.engine import EngineConfig, ServingEngine, synthetic_trace
from attention_tpu.engine.errors import SnapshotCorruptError, SnapshotError
from attention_tpu.engine.request import SamplingParams
from attention_tpu.engine.sim import replay
from attention_tpu.engine.snapshot import (
    inspect,
    recover_engine,
    restore,
    save,
    state_fingerprint,
    verify,
)
from attention_tpu.models import TinyDecoder
from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    ragged_paged_append,
    ragged_paged_attention,
)
from attention_tpu.parallel.serving import (
    MeshConfigError,
    head_sharded_ragged_step,
)

pytestmark = pytest.mark.engine

SHARDS = 2


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


def _trace(model, **kw):
    base = dict(vocab=model.vocab, seed=11, max_tokens=6,
                shared_prefix_len=129, shared_count=3)
    base.update(kw)
    return synthetic_trace(8, **base)


def _serve(model, params, config, trace):
    engine = ServingEngine(model, params, config)
    _, outputs = replay(engine, trace)
    return engine, outputs


# -------------------------------------------------------- token parity


@pytest.mark.parametrize("tkw", [
    {},                                   # greedy
    {"temperature": 0.7},                 # sampled (seeded RNG chains)
], ids=["greedy", "sampled"])
def test_mesh_token_parity_ragged(tiny_model, tkw):
    """Sharding the KV heads must never change a token: the mesh
    engine's streams equal the single-device engine's, request for
    request, through chunked prefill + prefix cache hits."""
    model, params = tiny_model
    trace = _trace(model, **tkw)
    _, single = _serve(model, params, _cfg(), trace)
    _, mesh = _serve(model, params, _cfg(mesh_shards=SHARDS), trace)
    assert mesh == single
    assert single  # non-vacuous: every request finished with tokens
    assert all(single.values())


def test_mesh_preemption_parity(tiny_model):
    """Page pressure preempts on the mesh engine exactly as on the
    single-device one — same victims, same recompute, same tokens."""
    model, params = tiny_model
    trace = synthetic_trace(3, vocab=model.vocab, seed=3,
                            prompt_len_min=120, prompt_len_max=120,
                            max_tokens=12)
    tight = dict(num_pages=3, watermark_pages=0)
    eng_s, single = _serve(model, params, _cfg(**tight), trace)
    eng_m, mesh = _serve(model, params,
                         _cfg(mesh_shards=SHARDS, **tight), trace)
    assert eng_m.scheduler.num_preemptions >= 1
    assert eng_m.scheduler.num_preemptions == \
        eng_s.scheduler.num_preemptions
    assert mesh == single and single


# --------------------------------------------- typed geometry refusals


def test_mesh_config_error_on_indivisible_kv_heads():
    """Call-time validation in parallel/serving.py, both paths: a KV
    head count the mesh cannot split is a typed `MeshConfigError`; a
    divisible one runs the sharded step bit-identically to the
    unsharded kernels."""
    r = np.random.default_rng(0)
    page, hkv, hq, d = 128, 2, 4, 16
    k_pool = jnp.asarray(r.standard_normal((6, hkv, page, d)), jnp.float32)
    v_pool = jnp.asarray(r.standard_normal((6, hkv, page, d)), jnp.float32)
    # one decode slot (kv_len 37) + one fresh 4-token prefill slot
    cache = RaggedPagedStep(
        k_pool, v_pool,
        page_table=jnp.asarray([[0, -1], [1, -1]], jnp.int32),
        kv_lens=jnp.asarray([37, 0], jnp.int32),
        cu_q_lens=jnp.asarray([0, 1, 5], jnp.int32),
        distribution=jnp.asarray([1, 2], jnp.int32),
        token_pos=jnp.asarray([37, 0, 1, 2, 3, 0, 0, 0], jnp.int32),
        token_slot=jnp.asarray([0, 1, 1, 1, 1, -1, -1, -1], jnp.int32),
        q_span=np.zeros((4,), np.int32),
    )
    q = jnp.asarray(r.standard_normal((1, hq, 8, d)), jnp.float32)
    k_new = jnp.asarray(r.standard_normal((1, hkv, 8, d)), jnp.float32)
    v_new = jnp.asarray(r.standard_normal((1, hkv, 8, d)), jnp.float32)

    # error path: 2 KV heads cannot split over 3 devices
    bad = Mesh(np.asarray(jax.devices()[:3]), ("tp",))
    with pytest.raises(MeshConfigError, match="not divisible"):
        head_sharded_ragged_step(q, cache, k_new, v_new, mesh=bad)

    # success path: 2-way split equals the unsharded append+attention
    good = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    out_s, cache_s = head_sharded_ragged_step(q, cache, k_new, v_new,
                                              mesh=good)
    cache_1 = ragged_paged_append(cache, k_new, v_new)
    out_1 = ragged_paged_attention(q, cache_1)
    assert np.array_equal(np.asarray(out_s), np.asarray(out_1))
    assert np.array_equal(np.asarray(cache_s.k_pool),
                          np.asarray(cache_1.k_pool))
    assert np.array_equal(np.asarray(cache_s.kv_lens),
                          np.asarray(cache_1.kv_lens))


def test_mesh_config_error_at_engine_construction(tiny_model):
    model, params = tiny_model
    # 2 KV heads over 8 devices: 8 does not divide 2
    with pytest.raises(MeshConfigError, match="not divisible"):
        ServingEngine(model, params, _cfg(mesh_shards=8))
    with pytest.raises(MeshConfigError, match="available device"):
        ServingEngine(model, params, _cfg(mesh_shards=9))
    with pytest.raises(ValueError, match="mesh_shards"):
        _cfg(mesh_shards=-1).validate()


# ------------------------------------------------- telemetry contracts


def _counter_total(snap, name, **labels):
    total = 0.0
    for row in snap["counters"]:
        if row["name"] != name:
            continue
        if all(row["labels"].get(k) == v for k, v in labels.items()):
            total += row["value"]
    return total


def test_mesh_exactly_one_launch_per_busy_step(tiny_model):
    """The single-launch property survives sharding: the mesh engine
    still dispatches exactly one jitted ragged launch per non-empty
    step, the mesh gauge carries the shard count, and the step's one
    device sync (where the shards' replicated logits reassemble) is
    one `engine.step.fetch` span per busy step."""
    model, params = tiny_model
    trace = _trace(model)
    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        eng = ServingEngine(model, params, _cfg(mesh_shards=SHARDS))
        replay(eng, trace)
        snap = obs.REGISTRY.snapshot()
        busy = sum(1 for m in eng.metrics.steps
                   if m.decode_tokens or m.prefill_tokens)
        assert busy > 0
        assert _counter_total(snap, "engine.step.launches") == busy
        shards = [g["value"] for g in snap["gauges"]
                  if g["name"] == "engine.mesh.shards"]
        assert shards == [float(SHARDS)]
        fetches = [e for e in obs.events()
                   if e["name"] == "engine.step.fetch"]
        assert len(fetches) == busy
        assert all(e["fields"]["bytes"] > 0 for e in fetches)
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()


def test_mesh_obs_zero_overhead_token_identity(tiny_model):
    """The obs zero-overhead contract extends to mesh engines: tokens
    with telemetry on are byte-identical to tokens with it off."""
    model, params = tiny_model
    trace = _trace(model, temperature=0.7)
    was = obs.enabled()
    obs.disable()
    try:
        _, off = _serve(model, params, _cfg(mesh_shards=SHARDS), trace)
        obs.enable()
        obs.reset()
        _, on = _serve(model, params, _cfg(mesh_shards=SHARDS), trace)
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    assert off == on and off


# ------------------------------------------- per-shard snapshot format


def _midflight_mesh_engine(model, params, trace, steps=8):
    engine = ServingEngine(model, params, _cfg(mesh_shards=SHARDS))
    for t in trace:
        engine.add_request(
            t["prompt"],
            SamplingParams(max_tokens=t["max_tokens"],
                           temperature=t["temperature"], seed=t["seed"]),
            request_id=t["id"])
    for _ in range(steps):
        engine.step()
    return engine


def _drain(engine, max_steps=200):
    outs = {}
    engine.on_finish = lambda req: outs.__setitem__(
        req.request_id, list(req.output_tokens))
    for _ in range(max_steps):
        engine.step()
        if not engine.scheduler.waiting and not engine.scheduler.running:
            break
    return outs


def test_mesh_snapshot_per_shard_sections_and_warm_restart(
        tiny_model, tmp_path):
    """A mesh engine's snapshot carries one independently-CRC'd pool
    section per shard; restore reassembles it and the restored engine
    finishes every in-flight (sampled) request token-identically."""
    model, params = tiny_model
    trace = _trace(model, temperature=0.6)
    engine = _midflight_mesh_engine(model, params, trace)
    path = str(tmp_path / "snap-00000008.atpsnap")
    save(engine, path)

    info = inspect(path)
    assert info["valid"] and info["shards"] == SHARDS
    names = [s["name"] for s in info["sections"]]
    assert [n for n in names if n.startswith("pools")] == \
        [f"pools.{s}" for s in range(SHARDS)]
    assert verify(path) == []

    clone = restore(path, model, params)
    assert state_fingerprint(clone) == state_fingerprint(engine)
    assert _drain(clone) == _drain(engine)


def test_mesh_snapshot_roundtrip_invariant_midflight(tiny_model):
    """Chaos invariant 7 over the per-shard layout: round trip is
    fingerprint-identical AND the manifest carries the shard
    structure (a single-blob pool section would be a violation)."""
    model, params = tiny_model
    engine = _midflight_mesh_engine(
        model, params, _trace(model, temperature=0.6))
    assert snapshot_roundtrip_violations(engine) == []


def _corrupt_section(path, out_path, name, mutate):
    """Rewrite one section's payload through ``mutate``; the manifest
    is re-CRC'd so only structural meaning changes, not framing."""
    blob = open(path, "rb").read()
    nl = blob.find(b"\n")
    manifest = json.loads(blob[:nl])
    payloads = {}
    off = nl + 1
    for s in manifest["sections"]:
        payloads[s["name"]] = blob[off:off + s["nbytes"]]
        off += s["nbytes"]
    payloads[name] = mutate(payloads[name])
    for s in manifest["sections"]:
        s["nbytes"] = len(payloads[s["name"]])
        s["crc32"] = zlib.crc32(payloads[s["name"]])
    out = (json.dumps(manifest, sort_keys=True,
                      separators=(",", ":")).encode() + b"\n"
           + b"".join(payloads[s["name"]]
                      for s in manifest["sections"]))
    open(out_path, "wb").write(out)


def test_mesh_snapshot_one_shard_corruption_is_typed(
        tiny_model, tmp_path):
    """Bit-flip ONE shard's section: verify names exactly that shard,
    restore is a typed `SnapshotCorruptError`, and `recover_engine`
    skips the damaged snapshot for an older valid one — degraded
    warmth, never wrong tokens."""
    model, params = tiny_model
    trace = _trace(model)
    engine = _midflight_mesh_engine(model, params, trace, steps=4)
    older = str(tmp_path / "snap-00000004.atpsnap")
    save(engine, older)
    for _ in range(4):
        engine.step()
    newer = str(tmp_path / "snap-00000008.atpsnap")
    save(engine, newer)

    blob = open(newer, "rb").read()
    nl = blob.find(b"\n")
    manifest = json.loads(blob[:nl])
    off = nl + 1
    for s in manifest["sections"]:
        if s["name"] == "pools.1":
            mid = off + s["nbytes"] // 2
            blob = blob[:mid] + bytes([blob[mid] ^ 0xFF]) + blob[mid + 1:]
            break
        off += s["nbytes"]
    open(newer, "wb").write(blob)

    problems = verify(newer)
    assert problems and "pools.1" in problems[0]
    with pytest.raises(SnapshotCorruptError, match="pools.1"):
        restore(newer, model, params)
    recovered, report = recover_engine(model, params, str(tmp_path))
    assert report["snapshot_step"] == 4
    assert any("pools.1" in s["error"] for s in report["skipped"])
    assert recovered.config.mesh_shards == SHARDS


def test_mesh_snapshot_geometry_mismatch_is_not_corruption(
        tiny_model, tmp_path):
    """A snapshot that needs more shards than this host has devices is
    a plain typed `SnapshotError` (cold-fallback cue) — NOT a
    `SnapshotCorruptError` — because the file itself is undamaged."""
    model, params = tiny_model
    engine = _midflight_mesh_engine(model, params, _trace(model))
    path = str(tmp_path / "snap-00000008.atpsnap")
    save(engine, path)
    hostile = str(tmp_path / "snap-00000009.atpsnap")

    def _demand_nine_shards(meta_payload):
        meta = json.loads(meta_payload)
        meta["config"]["mesh_shards"] = 9  # host has only 8 devices
        return json.dumps(meta, sort_keys=True,
                          separators=(",", ":")).encode()

    _corrupt_section(path, hostile, "meta", _demand_nine_shards)
    with pytest.raises(SnapshotError, match="mesh geometry") as ei:
        restore(hostile, model, params)
    assert not isinstance(ei.value, SnapshotCorruptError)


# -------------------------------------------------- chaos composition


def test_mesh_kill_migrate_chaos_campaign(tiny_model, tmp_path):
    """Mesh replicas join the crash storm by config alone: kills,
    warm restarts from per-shard snapshots, and migrations across
    replicas — all eight invariants, zero violations."""
    model, params = tiny_model
    rep = run_crash_campaign(
        3, str(tmp_path / "mesh-storm"), num_plans=2, num_requests=5,
        num_replicas=2, temperature=0.6, model=model, params=params,
        config=_cfg(mesh_shards=SHARDS))
    assert rep.ok, [v for r in rep.reports for v in r.violations]
