"""Crash-consistent durability (attention_tpu/engine/snapshot + journal).

The contract under test, end to end: ``restore(save(engine))`` is
state-identical (equal deterministic fingerprints, byte-identical
continuation), any damaged snapshot raises the typed
`SnapshotCorruptError` (never garbage, never a crash), recovery =
newest valid snapshot + journal replay reproduces the fault-free token
streams exactly, and the frontend's ``restart_replica`` degrades
warm → cold without losing a request.  Tiny CPU shapes throughout;
the broad crash-storm sweep rides ``-m slow``.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from attention_tpu.chaos.configs import sample_campaign
from attention_tpu.chaos.faults import (
    FaultEvent,
    FaultPlan,
    default_frontend_config,
    run_crash_campaign,
    run_frontend_plan,
)
from attention_tpu.engine import (
    EngineConfig,
    ReplicaStateError,
    ServingEngine,
    SnapshotCorruptError,
    replay,
    sampling_of,
    synthetic_trace,
)
from attention_tpu.engine.journal import (
    Journal,
    journal_path,
    list_journals,
)
from attention_tpu.engine.snapshot import (
    SNAPSHOT_VERSION,
    SnapshotManager,
    inspect,
    list_snapshots,
    recover_engine,
    restore,
    save,
    state_fingerprint,
    verify,
)
from attention_tpu.frontend import ReplicaHandle
from attention_tpu.models import TinyDecoder

pytestmark = [pytest.mark.engine, pytest.mark.snapshot]


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


def _collecting_engine(model, params, config=None):
    """Engine whose finished streams land in the returned dict."""
    outs: dict[str, list[int]] = {}
    eng = ServingEngine(
        model, params, config or _cfg(),
        on_finish=lambda r: outs.__setitem__(
            r.request_id, list(r.output_tokens)))
    return eng, outs


def _admit_all(engine, trace):
    for e in trace:
        engine.add_request(e["prompt"], sampling_of(e),
                           request_id=e["id"], arrival=e["arrival"])


def _drain(engine, *, max_steps=500):
    steps = 0
    while engine.scheduler.has_work():
        engine.step()
        steps += 1
        assert steps < max_steps, "engine failed to drain"


# -------------------------------------------------- save/restore round trip


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_roundtrip_fingerprint_and_continuation_parity(
        tiny_model, tmp_path, temperature):
    """The tentpole contract: mid-flight save → restore yields an
    engine with an identical state fingerprint whose continued streams
    are byte-identical to the fault-free run — greedy and sampled."""
    model, params = tiny_model
    trace = synthetic_trace(5, vocab=model.vocab, seed=11, max_tokens=6,
                            temperature=temperature)
    baseline_engine = ServingEngine(model, params, _cfg())
    _, baseline = replay(baseline_engine, trace)

    eng1, outs1 = _collecting_engine(model, params)
    _admit_all(eng1, trace)
    for _ in range(4):
        eng1.step()

    path = str(tmp_path / "snap-00000004.atpsnap")
    save(eng1, path)
    assert verify(path) == []

    outs2: dict[str, list[int]] = {}
    eng2 = restore(path, model, params,
                   on_finish=lambda r: outs2.__setitem__(
                       r.request_id, list(r.output_tokens)))
    assert state_fingerprint(eng2) == state_fingerprint(eng1)
    assert eng2.current_step == eng1.current_step

    _drain(eng1)
    _drain(eng2)
    # every request still live at the cut finishes identically on the
    # restored engine; together the two runs cover the whole trace
    assert outs2
    for rid, toks in outs2.items():
        assert toks == baseline[rid], rid
    for rid, toks in outs1.items():
        assert toks == baseline[rid], rid
    assert set(outs1) >= set(baseline) - set(outs2)


def test_roundtrip_property_sweep(tiny_model, tmp_path):
    """Satellite: property-style round trip over fuzzer-derived engine
    states.  The chaos config grids (`chaos/configs.py`) seed the
    diversity — each sampled kernel config deterministically maps to a
    (trace seed, size, temperature, cut point) engine state — and every
    state must fingerprint-match through save → restore → step."""
    model, params = tiny_model
    for i, cfg in enumerate(sample_campaign(99, 6)):
        trace = synthetic_trace(
            3 + cfg.m % 3, vocab=model.vocab, seed=cfg.seed % 1000,
            max_tokens=4 + cfg.n % 3,
            temperature=0.8 if cfg.causal else 0.0,
        )
        eng1 = ServingEngine(model, params, _cfg())
        _admit_all(eng1, trace)
        for _ in range(1 + cfg.heads):
            eng1.step()
        path = str(tmp_path / f"case-{i}.atpsnap")
        save(eng1, path)
        eng2 = restore(path, model, params)
        assert state_fingerprint(eng2) == state_fingerprint(eng1), cfg
        # step parity: one more step on each side stays identical
        if eng1.scheduler.has_work():
            eng1.step()
            eng2.step()
            assert state_fingerprint(eng2) == state_fingerprint(eng1), cfg


# ---------------------------------------------------- corruption table


def _sections_layout(blob: bytes) -> dict[str, tuple[int, int]]:
    nl = blob.find(b"\n")
    manifest = json.loads(blob[:nl])
    layout = {}
    offset = nl + 1
    for s in manifest["sections"]:
        layout[s["name"]] = (offset, s["nbytes"])
        offset += s["nbytes"]
    return layout


def _corrupt_blob(blob: bytes, mode: str) -> bytes:
    layout = _sections_layout(blob)
    nl = blob.find(b"\n")
    if mode.startswith("bitflip_"):
        offset, nbytes = layout[mode.removeprefix("bitflip_")]
        i = offset + nbytes // 2
        return blob[:i] + bytes([blob[i] ^ 0xFF]) + blob[i + 1:]
    if mode == "truncate_mid":
        start, nbytes = layout["state"]
        return blob[:start + nbytes // 2]
    if mode == "truncate_tail":
        return blob[:-7]
    if mode == "trailing_garbage":
        return blob + b"\x00cruft"
    if mode in ("stale_version", "older_version"):
        manifest = json.loads(blob[:nl])
        manifest["version"] = (SNAPSHOT_VERSION + 1
                               if mode == "stale_version" else 1)
        return (json.dumps(manifest, sort_keys=True,
                           separators=(",", ":")).encode()
                + blob[nl:])
    if mode == "bad_magic":
        manifest = json.loads(blob[:nl])
        manifest["magic"] = "not-a-snapshot"
        return (json.dumps(manifest, sort_keys=True,
                           separators=(",", ":")).encode()
                + blob[nl:])
    raise AssertionError(mode)


@pytest.mark.parametrize("mode", [
    "bitflip_meta", "bitflip_pools", "bitflip_state",
    "bitflip_requests", "truncate_mid", "truncate_tail",
    "trailing_garbage", "stale_version", "older_version", "bad_magic",
])
def test_corruption_is_typed_refusal(tiny_model, tmp_path, mode):
    """Every damage class — per-section bit flip, truncation, trailing
    bytes, version skew either way, foreign magic — reads as a
    non-empty `verify()` report and a `SnapshotCorruptError` from
    `restore()`; a sound image of the older format is named as that,
    not as damage."""
    model, params = tiny_model
    eng = ServingEngine(model, params, _cfg())
    _admit_all(eng, synthetic_trace(3, vocab=model.vocab, seed=5,
                                    max_tokens=5, temperature=0.5))
    for _ in range(3):
        eng.step()
    good = str(tmp_path / "good.atpsnap")
    save(eng, good)
    blob = open(good, "rb").read()

    bad = str(tmp_path / f"{mode}.atpsnap")
    with open(bad, "wb") as f:
        f.write(_corrupt_blob(blob, mode))
    assert verify(bad), mode
    assert not inspect(bad)["valid"]
    refused_as = {"older_version": "unsupported snapshot version 1"}
    with pytest.raises(SnapshotCorruptError, match=refused_as.get(mode)):
        restore(bad, model, params)
    # the pristine file still round-trips (corruption helper sanity)
    assert verify(good) == []


def test_save_fsyncs_file_and_directory_around_replace(
        tiny_model, tmp_path, monkeypatch):
    """Durability of a landed snapshot: `save` fsyncs the temp fd
    BEFORE the atomic rename and the directory after it, so a power
    loss can't leave an empty/partial file at the final path."""
    model, params = tiny_model
    eng = ServingEngine(model, params, _cfg())
    events = []
    real_fsync, real_replace = os.fsync, os.replace
    monkeypatch.setattr(
        os, "fsync",
        lambda fd: (events.append("fsync"), real_fsync(fd))[1])
    monkeypatch.setattr(
        os, "replace",
        lambda a, b: (events.append("replace"), real_replace(a, b))[1])
    save(eng, str(tmp_path / "snap.atpsnap"))
    assert events == ["fsync", "replace", "fsync"]


def test_restore_rejects_model_fingerprint_mismatch(tiny_model, tmp_path):
    model, params = tiny_model
    eng = ServingEngine(model, params, _cfg())
    path = str(tmp_path / "snap.atpsnap")
    save(eng, path)
    other = TinyDecoder(vocab=44, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    with pytest.raises(SnapshotCorruptError):
        restore(path, other, params)


# ----------------------------------------------------------- journal


def test_journal_roundtrip_and_torn_tail(tmp_path):
    """Append-only WAL: records round-trip with their CRCs; a torn
    tail (any cut into the final record) silently drops ONLY the torn
    record — the valid prefix survives."""
    path = str(tmp_path / "journal-00000000.wal")
    j = Journal(path, snapshot_step=0)
    j.record_token("r1", 7)
    j.record_token("r1", 9)
    j.record_cancel("r2")
    recs = Journal.read(path)
    assert [r["kind"] for r in recs] == ["begin", "token", "token",
                                         "cancel"]
    assert recs[1]["token"] == 7 and recs[0]["snapshot_step"] == 0

    size = os.path.getsize(path)
    os.truncate(path, size - 5)
    torn = Journal.read(path)
    assert [r["kind"] for r in torn] == ["begin", "token", "token"]

    # a bit flip mid-file stops replay at the damaged record
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(data))
    assert len(Journal.read(path)) < len(torn)
    assert Journal.read(str(tmp_path / "missing.wal")) == []


def test_manager_periodic_snapshots_journals_and_prune(
        tiny_model, tmp_path):
    """SnapshotManager wraps ``engine.step``: genesis snapshot at
    attach, one snapshot every N steps, journal rotation AFTER the
    snapshot lands, prune keeps the newest ``keep`` snapshots plus the
    journals that chain from the oldest kept one."""
    model, params = tiny_model
    eng = ServingEngine(model, params, _cfg())
    d = str(tmp_path / "snaps")
    mgr = SnapshotManager(eng, d, every=2, keep=2)
    _admit_all(eng, synthetic_trace(4, vocab=model.vocab, seed=3,
                                    max_tokens=6))
    for _ in range(6):
        eng.step()
    steps = [s for s, _ in list_snapshots(d)]
    assert steps == [4, 6]          # 0 and 2 pruned, keep=2
    assert [s for s, _ in list_journals(d)] == [4, 6]
    assert mgr.saves >= 4 and mgr.last_snapshot_step == 6
    mgr.detach()
    assert eng.journal is None


def test_recovery_chains_past_corrupt_newest_snapshot(
        tiny_model, tmp_path):
    """The latest-valid-fallback contract: newest snapshot bit-flipped
    → recovery restores the previous one and chain-replays BOTH
    journals; a crash mid-snapshot (armed crash point) leaves only a
    ``.tmp`` that recovery never even considers.  Finished streams
    stay token-identical to the fault-free run."""
    model, params = tiny_model
    trace = synthetic_trace(5, vocab=model.vocab, seed=21, max_tokens=6,
                            temperature=0.7)
    base_engine = ServingEngine(model, params, _cfg())
    _, baseline = replay(base_engine, trace)

    eng, outs = _collecting_engine(model, params)
    d = str(tmp_path / "snaps")
    mgr = SnapshotManager(eng, d, every=3, keep=3)
    _admit_all(eng, trace)
    for _ in range(7):
        eng.step()
    # crash point: the step-9 snapshot dies mid-write (torn .tmp only)
    mgr.crash_next = True
    for _ in range(2):
        eng.step()
    assert any(n.endswith(".tmp") for n in os.listdir(d))
    # bit-flip the newest LANDED snapshot too: recovery must chain to
    # the one before it
    newest = list_snapshots(d)[-1][1]
    blob = bytearray(open(newest, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(newest, "wb") as f:
        f.write(bytes(blob))

    # process "dies" at step 9; recover from disk alone
    outs2: dict[str, list[int]] = {}
    eng2, info = recover_engine(
        model, params, d,
        on_finish=lambda r: outs2.__setitem__(
            r.request_id, list(r.output_tokens)))
    assert info["skipped"] and info["snapshot_step"] < 9
    _drain(eng2)
    for rid, toks in outs2.items():
        assert toks == baseline[rid], rid
    # everything that had not finished before the crash finishes now
    assert set(outs2) == set(baseline) - set(outs)


def test_recover_engine_raises_typed_when_nothing_valid(
        tiny_model, tmp_path):
    model, params = tiny_model
    with pytest.raises(SnapshotCorruptError):
        recover_engine(model, params, str(tmp_path / "empty"))


def test_manager_attach_starts_fresh_incarnation(tiny_model, tmp_path):
    """Attach clears a dead incarnation's step-keyed files and the
    genesis journal is created fresh — exactly one ``begin`` record,
    never an append onto stale pre-crash records."""
    model, params = tiny_model
    d = tmp_path / "snaps"
    d.mkdir()
    # debris from a "dead incarnation": a stale journal at the genesis
    # step, a stale higher-step snapshot, and a torn save
    stale = Journal(journal_path(str(d), 0), snapshot_step=0)
    stale.record_token("ghost", 7)
    (d / "snap-00000009.atpsnap").write_bytes(b"not a snapshot")
    (d / "tmpdead.tmp").write_bytes(b"torn")

    eng = ServingEngine(model, params, _cfg())
    SnapshotManager(eng, str(d), every=4)
    assert [s for s, _ in list_snapshots(str(d))] == [0]
    assert [s for s, _ in list_journals(str(d))] == [0]
    assert not (d / "tmpdead.tmp").exists()
    recs = Journal.read(journal_path(str(d), 0))
    assert [r["kind"] for r in recs] == ["begin"]


# --------------------------------------- incarnation / re-crash parity


def test_warm_restart_then_second_crash_token_parity(
        tiny_model, tmp_path):
    """Review regression (high): after a warm restart the manager's
    genesis snapshot already contains the replayed journal records, so
    a SECOND crash before the next periodic snapshot must not replay
    the dead incarnation's records again (duplicated tokens).  Two
    kill → warm-restart cycles stay token-identical to the fault-free
    run."""
    model, params = tiny_model
    trace = synthetic_trace(6, vocab=model.vocab, seed=53, max_tokens=6,
                            temperature=0.7)
    base_engine = ServingEngine(model, params, _cfg())
    _, baseline = replay(base_engine, trace)

    outs: dict[str, list[int]] = {}
    d = str(tmp_path / "snaps")
    handle = ReplicaHandle(
        "replica-0", model, params, _cfg(), snapshot_dir=d,
        snapshot_every=4,
        on_finish=lambda r: outs.__setitem__(
            r.request_id, list(r.output_tokens)))
    _admit_all(handle.engine, trace)
    for _ in range(6):
        handle.step()

    handle.kill()
    assert handle.restart(tick=6, warm_from=d) == "warm"
    assert handle.engine.scheduler.has_work()
    # fewer steps than snapshot_every: the second crash lands before
    # any periodic snapshot, so recovery leans on the genesis + the
    # incarnation's own journal alone
    for _ in range(2):
        handle.step()

    handle.kill()
    assert handle.restart(tick=8, warm_from=d) == "warm"
    steps = 0
    while handle.has_work():
        handle.step()
        steps += 1
        assert steps < 500, "replica failed to drain"
    assert set(outs) == set(baseline)
    for rid, toks in outs.items():
        assert toks == baseline[rid], rid


def test_cold_restart_cannot_resurrect_dead_incarnation(
        tiny_model, tmp_path):
    """Review regression (medium): a cold restart keeps the snapshot
    dir but must not leave the dead incarnation's higher-step files
    behind — a later kill + warm restart recovers the COLD
    incarnation's (empty) state, never the pre-restart one."""
    model, params = tiny_model
    d = str(tmp_path / "snaps")
    handle = ReplicaHandle("replica-0", model, params, _cfg(),
                           snapshot_dir=d, snapshot_every=2)
    _admit_all(handle.engine,
               synthetic_trace(3, vocab=model.vocab, seed=13,
                               max_tokens=6))
    for _ in range(5):
        handle.step()
    assert max(s for s, _ in list_snapshots(d)) > 0

    handle.kill()
    assert handle.restart(tick=10) == "cold"
    # the cold incarnation's genesis is now the ONLY recovery base
    assert [s for s, _ in list_snapshots(d)] == [0]
    assert [s for s, _ in list_journals(d)] == [0]

    handle.kill()
    assert handle.restart(tick=12, warm_from=d) == "warm"
    assert handle.engine.current_step == 0
    assert not handle.engine.scheduler.has_work()


# ----------------------------------------------- frontend warm recovery


def test_replica_restart_guards_and_warm_cold_modes(
        tiny_model, tmp_path):
    """Satellite: lifecycle guards are typed (`ReplicaStateError` on
    restarting a live replica), warm restart restores the engine's
    step/requests, and a fully corrupt snapshot dir degrades to the
    PR 6 cold path instead of erroring."""
    model, params = tiny_model
    d = str(tmp_path / "replica-snaps")
    handle = ReplicaHandle("replica-0", model, params, _cfg(),
                           snapshot_dir=d, snapshot_every=2)
    with pytest.raises(ReplicaStateError):
        handle.restart(tick=0)

    trace = synthetic_trace(3, vocab=model.vocab, seed=9, max_tokens=6)
    _admit_all(handle.engine, trace)
    for _ in range(5):
        handle.step()
    snap_step = max(s for s, _ in list_snapshots(d))

    handle.kill()
    assert handle.restart(tick=20, warm_from=d) == "warm"
    assert handle.last_restart_mode == "warm"
    # journal replay rewinds past the snapshot cut; the restored step
    # is the snapshot's and the clock anchors deadline translation
    assert handle.engine.current_step == snap_step
    assert handle.local_deadline(20) == handle.engine.current_step
    assert handle.engine.scheduler.has_work()

    handle.kill()
    for _, p in list_snapshots(d):
        blob = bytearray(open(p, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        with open(p, "wb") as f:
            f.write(bytes(blob))
    assert handle.restart(tick=30, warm_from=d) == "cold"
    assert handle.last_restart_mode == "cold"
    assert handle.engine.current_step == 0


def test_frontend_kill_mid_decode_warm_recovery_parity(
        tiny_model, tmp_path):
    """Acceptance headline: a replica killed mid-decode on a
    snapshot-configured front end restarts WARM (snapshot + journal
    replay), adopted streams resume in place, and every finished
    request is token-identical to the fault-free single-replica run —
    greedy and sampled alike."""
    model, params = tiny_model
    trace = synthetic_trace(6, vocab=model.vocab, seed=31, max_tokens=6,
                            temperature=0.6)
    base_engine = ServingEngine(model, params, _cfg())
    _, baseline = replay(base_engine, trace)

    plan = FaultPlan(seed=0, events=(
        FaultEvent(step=5, kind="replica_kill", target="replica-0"),
        FaultEvent(step=8, kind="replica_restart", target="replica-0"),
    ))
    fc = default_frontend_config(
        2, snapshot_dir=str(tmp_path / "fe"), snapshot_every=2)
    r = run_frontend_plan(model, params, _cfg(), fc, trace, plan,
                          baseline=baseline, snapshot_roundtrip=True)
    assert r.violations == []
    assert r.drained and r.injected == 2
    finished = [rid for rid, st in r.states.items() if st == "finished"]
    assert finished
    for rid in finished:
        assert r.outputs[rid] == baseline[rid], rid


def test_crash_points_cost_warmth_never_tokens(tiny_model, tmp_path):
    """Acceptance: kill mid-snapshot + torn journal tail + bit-flipped
    snapshot, all against the replica that then dies — recovery may
    land on an older snapshot or fall back cold, but finished streams
    stay byte-identical per seed and no invariant breaks."""
    model, params = tiny_model
    trace = synthetic_trace(6, vocab=model.vocab, seed=47, max_tokens=6,
                            temperature=0.6)
    base_engine = ServingEngine(model, params, _cfg())
    _, baseline = replay(base_engine, trace)

    plan = FaultPlan(seed=0, events=(
        FaultEvent(step=3, kind="snap_crash", target="replica-0"),
        FaultEvent(step=4, kind="journal_tear", target="replica-0",
                   arg=1),
        FaultEvent(step=5, kind="snap_corrupt", target="replica-0"),
        FaultEvent(step=6, kind="replica_kill", target="replica-0"),
        FaultEvent(step=9, kind="replica_restart", target="replica-0"),
    ))
    fc = default_frontend_config(
        2, snapshot_dir=str(tmp_path / "fe"), snapshot_every=2)
    r = run_frontend_plan(model, params, _cfg(), fc, trace, plan,
                          baseline=baseline, snapshot_roundtrip=True)
    assert r.violations == []
    assert r.drained
    finished = [rid for rid, st in r.states.items() if st == "finished"]
    for rid in finished:
        assert r.outputs[rid] == baseline[rid], rid


def test_crash_campaign_smoke(tiny_model, tmp_path):
    """Seeded crash-storm smoke: two plans through the full campaign
    harness (all eight invariants incl. round trip + warm parity)."""
    model, params = tiny_model
    rep = run_crash_campaign(3, str(tmp_path / "storm"), num_plans=2,
                             num_requests=5, num_replicas=2,
                             temperature=0.6, model=model,
                             params=params, config=_cfg())
    assert rep.ok, [v for r in rep.reports for v in r.violations]


@pytest.mark.slow
def test_crash_storm_sweep(tiny_model, tmp_path):
    """Broad crash-storm sweep (``-m slow``): many seeds × plans with
    every crash point in the mix; zero violations tolerated."""
    model, params = tiny_model
    for seed in (1, 2, 5, 8):
        rep = run_crash_campaign(
            seed, str(tmp_path / f"storm-{seed}"), num_plans=4,
            num_requests=6, num_replicas=2, temperature=0.6,
            events_per_plan=7, model=model, params=params,
            config=_cfg())
        assert rep.ok, (seed,
                        [v for r in rep.reports for v in r.violations])


# ------------------------------------------------------------ CLI


def test_cli_serve_sim_snapshots_and_inspect_verify(tmp_path, capsys):
    from attention_tpu.cli import main as cli_main

    d = str(tmp_path / "clisnaps")
    rc = cli_main([
        "serve-sim", "--num-requests", "3", "--max-tokens", "4",
        "--vocab", "43", "--dim", "32", "--depth", "1",
        "--q-heads", "4", "--kv-heads", "2",
        "--snapshot-dir", d, "--snapshot-every", "2",
    ])
    assert rc == 0
    capsys.readouterr()
    assert list_snapshots(d)

    assert cli_main(["snapshot", "verify", d]) == 0
    out = capsys.readouterr().out
    assert ": ok" in out

    assert cli_main(["snapshot", "inspect", d]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    infos = [json.loads(line) for line in lines]
    assert all(i["valid"] for i in infos)
    assert infos[0]["step"] >= infos[-1]["step"]  # newest first

    # damage one snapshot: verify now fails with a nonzero exit
    _, victim = list_snapshots(d)[-1]
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(victim, "wb") as f:
        f.write(bytes(blob))
    assert cli_main(["snapshot", "verify", d]) == 1
    capsys.readouterr()


def test_cli_snapshot_flags_must_pair(tmp_path, capsys):
    from attention_tpu.cli import main as cli_main

    rc = cli_main([
        "serve-sim", "--num-requests", "1", "--max-tokens", "2",
        "--snapshot-every", "4",
    ])
    assert rc == 2
    capsys.readouterr()


def test_pre_fleet_snapshot_format_unchanged_and_pages_reported(
        tiny_model, tmp_path):
    """ISSUE 19 regression pin: the disaggregation layer ships KV in
    its own handoff blobs, so the engine snapshot format is untouched
    — a snapshot written today carries exactly the pre-fleet section
    set (no `pages` payload section), restores to an identical state
    fingerprint, and `inspect` reports the per-request committed-page
    count the CLI now surfaces."""
    model, params = tiny_model
    eng = ServingEngine(model, params, _cfg())
    _admit_all(eng, synthetic_trace(3, vocab=model.vocab, seed=7,
                                    max_tokens=6))
    for _ in range(4):
        eng.step()
    path = str(tmp_path / "pre_fleet.atpsnap")
    save(eng, path)
    info = inspect(path)
    assert info["valid"]
    assert {s["name"] for s in info["sections"]} == \
        {"meta", "state", "requests", "pools"}
    assert all(isinstance(r["pages"], int) for r in info["requests"])
    assert any(r["pages"] > 0 for r in info["requests"])
    eng2 = restore(path, model, params)
    assert state_fingerprint(eng2) == state_fingerprint(eng)
    # and the CLI's inspect dispatch keeps reading it as a snapshot,
    # never as a fleet handoff blob
    from attention_tpu.fleet.handoff import is_handoff

    assert not is_handoff(open(path, "rb").read())
