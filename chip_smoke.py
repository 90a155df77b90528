"""The quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a
user calls, at the full width of the model the repo serves:

  1. **engine** — `replay(ServingEngine(model, params, config), trace)`
     (what ``cli serve-sim`` runs) on a `TinyDecoder` at dim 4096 /
     32 q heads / 4 kv heads / head_dim 128 / vocab 32768 / bf16 / rope,
     depth cut to 4, random weights from a seed; 8 greedy requests with
     prompts of 200-2000 tokens, three sharing a 300-token prefix, 16
     output tokens each.  Checked: every request finishes with its 16
     tokens, the finite guard never fired, the prefix cache was hit, and
     every emitted token is teacher-forced against the plain
     ``impl="xla"`` float32 path (`LOGIT_MARGIN`).
  2. **bin contract** — the source paper's own contract, what ``cli
     run`` does: `generate_testcase(8192, 8192, 128, 128)` -> ``.bin``
     -> `attention(..., backend="flash")` -> `verify_file` must say
     ``Correct!``.
  3. **mesh** — with >= 4 devices: the same engine trace through
     ``EngineConfig(mesh_shards=4)`` with the same checks, and
     `kv_sharded_attention` / `ring_attention` at 32k x 128 over the
     4-device mesh against the single-chip flash result (+-0.02).
     Otherwise "not run: N device(s)", which is not a failure.

Exits non-zero — printing no result line — when JAX finds no TPU, when
a phase raises, when a check fails, or when a Pallas kernel on the path
would run interpreted.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The phase functions take their sizes as arguments so
``tests/test_chip_smoke.py`` runs them at a toy width on the CPU.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from attention_tpu import obs
from attention_tpu.engine import EngineConfig, ServingEngine, replay
from attention_tpu.engine.sim import synthetic_trace
from attention_tpu.models import TinyDecoder
from attention_tpu.ops.flash import _should_interpret
from attention_tpu.utils.runtime import (
    configure_compile_cache,
    describe_run,
    device_summary,
    require_tpu,
)

#: BASELINE config 5's width (the width every GQA/decode row of the old
#: records used); depth is the one thing cut.
SMOKE_MODEL = dict(vocab=32768, dim=4096, depth=4, num_q_heads=32,
                   num_kv_heads=4, rope=True)
SMOKE_ENGINE = dict(num_pages=2048, page_size=128, max_seq_len=4096,
                    max_decode_batch=8, max_prefill_rows=2,
                    prefill_chunk=256, token_budget=512)
#: prompts are 300 shared + 200..1700 own tokens for the first three
#: requests and 200..1700 for the rest: all inside 200-2000, the shared
#: prefix longer than two pages
SMOKE_TRACE = dict(num_requests=8, seed=21, prompt_len_min=200,
                   prompt_len_max=1700, max_tokens=16, arrival_every=1,
                   shared_prefix_len=300, shared_count=3)
SMOKE_BIN = dict(m=8192, n=8192, dk=128, dv=128)
SMOKE_MESH_ATTENTION = dict(seq=32768, dim=128)

#: How far below the reference argmax's logit an emitted token's
#: reference logit may sit.  With random weights the argmax flips on
#: rounding, so tokens cannot be compared; logits can.  The engine runs
#: bf16 (8 mantissa bits: ~2^-8 = 0.4% relative rounding per op) through
#: depth x (attention + MLP) against a float32 "highest" reference, and
#: the logits have unit scale (RMSNorm output times a lecun-normal
#: head), so the expected absolute logit error is a few hundredths —
#: the chip run measured a worst deficit of 0.03 (PERF.md).  0.25 leaves
#: that a wide berth and still sits far below the ~4 sigma = 4 gap
#: between the argmax of 32768 unit-normal logits and a typical one,
#: which is the deficit a wrong page, position or mask produces.
LOGIT_MARGIN = 0.25


class SmokeCheckError(AssertionError):
    """A phase ran to the end and one of its checks failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckError(what)


def require_compiled_kernels() -> None:
    """Fail if the Pallas kernels on the path would run interpreted
    (`ops.flash._should_interpret` is the one place that decides)."""
    _check(not _should_interpret(),
           f"Pallas kernels would run with interpret=True on backend "
           f"{jax.default_backend()!r}")


class CompileClock:
    """Sums JAX's own backend-compile events (the XLA/Mosaic compile, or
    the load from the persistent cache that replaces it) so a phase can
    report compile seconds apart from the rest, and persistent-cache
    hits apart from misses.  Listeners cannot be unregistered, so create
    one per process and read deltas with :meth:`snapshot`."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self._totals: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self._BACKEND:
            self._totals["backend_compile_s"] += seconds
            self._totals["executables"] += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self._totals["cache_hits"] += 1
        elif event == self._MISS:
            self._totals["cache_misses"] += 1

    def snapshot(self) -> collections.Counter:
        return collections.Counter(self._totals)


def _lowered(name: str) -> dict[str, int]:
    """``requested->lowered`` tallies of one ``ops.*.lowered`` counter
    (summed over any further label, the ragged kernel's ``bodies``)."""
    out: dict[str, int] = {}
    for s in obs.counter(name).series():
        key = f"{s['labels'].get('requested')}->{s['labels'].get('lowered')}"
        out[key] = out.get(key, 0) + int(s["value"])
    return out


def _memory(devices) -> list[dict]:
    """Per-device ``memory_stats()`` (bytes in use, peak), or a note
    where the backend reports none."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            out.append({"device": d.id, "memory_stats": "not reported"})
        else:
            out.append({"device": d.id,
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def run_phase(name: str, fn, clock: CompileClock, **kwargs) -> dict:
    """Run one phase with compile accounting and print its report.
    Exceptions propagate: a failed phase is a failed smoke."""
    obs.reset()
    before = clock.snapshot()
    t0 = time.perf_counter()
    report = fn(**kwargs)
    wall = time.perf_counter() - t0
    delta = clock.snapshot() - before
    report = {
        "phase": name,
        **device_summary(),
        "wall_s": round(wall, 2),
        "compile_s": round(delta["backend_compile_s"], 2),
        "executables": delta["executables"],
        "compile_cache_hits": delta["cache_hits"],
        "compile_cache_misses": delta["cache_misses"],
        "ragged_lowered": _lowered("ops.ragged.lowered"),
        "flash_lowered": _lowered("ops.flash.lowered"),
        "memory": _memory(jax.devices()),
        **report,
    }
    print(json.dumps(report))
    return report


def _reference_deficits(model, params, trace, outputs) -> dict[str, float]:
    """Teacher-forced check values: for every request, run prompt +
    emitted tokens through the plain ``impl="xla"`` float32 path at
    highest matmul precision, and return the largest amount by which an
    emitted token's reference logit sits below the reference argmax's.

    Every sequence is padded to one length (causal attention: the pad
    tail cannot reach back) so the reference compiles once."""
    ref = model.clone(impl="xla", dtype=jnp.float32)
    steps = len(next(iter(outputs.values())))
    longest = max(len(e["prompt"]) for e in trace) + steps
    padded = -(-longest // 128) * 128

    # params ride as an ARGUMENT: captured by closure they would be
    # baked into the module as gigabytes of constants
    @jax.jit
    def emitted_logits(params, tokens, first):
        logits = ref.apply({"params": params}, tokens)
        return jax.lax.dynamic_slice_in_dim(logits[0], first, steps)

    deficits = {}
    with jax.default_matmul_precision("highest"):
        for entry in trace:
            prompt, out = entry["prompt"], outputs[entry["id"]]
            seq = np.zeros((1, padded), np.int32)
            seq[0, :len(prompt) + steps - 1] = prompt + out[:-1]
            # logits at position p predict token p + 1: the emitted
            # tokens are predicted at len(prompt) - 1 onward
            rows = np.asarray(emitted_logits(
                params, jnp.asarray(seq), len(prompt) - 1), np.float64)
            picked = rows[np.arange(steps), out]
            deficits[entry["id"]] = float(np.max(rows.max(axis=1) - picked))
    return deficits


def engine_phase(*, model_kw: dict, engine_kw: dict, trace_kw: dict,
                 mesh_shards: int = 0,
                 margin: float = LOGIT_MARGIN) -> dict:
    """The serving engine end to end on a seeded trace, checked against
    the float32 reference."""
    model = TinyDecoder(impl="flash", dtype=jnp.bfloat16, **model_kw)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    config = EngineConfig(mesh_shards=mesh_shards, **engine_kw)
    trace = synthetic_trace(vocab=model.vocab, **trace_kw)
    engine = ServingEngine(model, params, config)
    t0 = time.perf_counter()
    summary, outputs = replay(engine, trace)
    first_replay_s = time.perf_counter() - t0
    # sampled while the engine is alive: on a mesh this is what shows
    # the pools and parameters sitting on every device
    serving_memory = _memory(
        [jax.devices()[0]] if engine.mesh is None
        else list(engine.mesh.devices.flat))

    for entry in trace:
        got = len(outputs.get(entry["id"], []))
        _check(got == entry["max_tokens"],
               f"{entry['id']} finished with {got} of "
               f"{entry['max_tokens']} tokens")
    nonfinite = engine.nonfinite_events
    _check(nonfinite == 0,
           f"finite guard held back {nonfinite} logits rows")
    _check(summary["prefix_cached_tokens"] >= config.page_size,
           f"prefix cache served {summary['prefix_cached_tokens']} tokens; "
           "the shared-prefix requests should hit at least one page")
    deficits = _reference_deficits(model, params, trace, outputs)
    worst = max(deficits.values())
    _check(worst <= margin,
           f"teacher-forced logit deficit {worst:.4f} exceeds the margin "
           f"{margin} ({deficits})")
    if engine.mesh is not None:
        placed = {d.id for leaf in jax.tree_util.tree_leaves(engine.params)
                  for d in leaf.devices()}
        _check(placed == {d.id for d in engine.mesh.devices.flat},
               f"parameters sit on devices {sorted(placed)}, not on the "
               "whole mesh")
    # the same trace again on a fresh engine: every executable is now
    # compiled, so this is the steady time, and a deterministic engine
    # must emit the same tokens
    del engine
    t0 = time.perf_counter()
    _, again = replay(ServingEngine(model, params, config), trace)
    steady_replay_s = time.perf_counter() - t0
    _check(again == outputs, "a second replay emitted different tokens")
    return {
        "first_replay_s": round(first_replay_s, 2),
        "steady_replay_s": round(steady_replay_s, 2),
        "memory_while_serving": serving_memory,
        "requests": summary["num_requests"],
        "steps": summary["num_steps"],
        "prompt_tokens": summary["prompt_tokens"],
        "output_tokens": summary["output_tokens"],
        "prefix_cached_tokens": summary["prefix_cached_tokens"],
        "nonfinite_events": nonfinite,
        "worst_logit_deficit": round(worst, 4),
        "logit_margin": margin,
        "mesh_shards": mesh_shards,
    }


def bin_contract_phase(*, m: int, n: int, dk: int, dv: int) -> dict:
    """The source paper's contract, as ``cli run`` executes it (float32
    inputs, ``backend="flash"``): the verdict must read ``Correct!``."""
    from attention_tpu import attention
    from attention_tpu.core.testcase import (
        generate_testcase,
        read_testcase,
        verify_file,
        write_testcase,
    )

    t0 = time.perf_counter()
    case = generate_testcase(m, n, dk, dv, seed=42)
    oracle_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        path = os.path.join(td, "case.bin")
        write_testcase(path, case)
        loaded = read_testcase(path)
        q, k, v = (x.astype(np.float32)
                   for x in (loaded.q, loaded.k, loaded.v))
        out = np.asarray(attention(q, k, v, backend="flash"), np.float64)
        ok, msg = verify_file(path, out)
    verdict = "Correct!" if ok else "Wrong!"
    _check(ok, f".bin contract {m}x{n}x{dk}x{dv}: {msg} {verdict}")
    return {
        "shape": [m, n, dk, dv],
        "verdict": verdict,
        "max_abs_err": float(np.max(np.abs(out - loaded.expected))),
        "oracle": "numpy fp64 (core.oracle.attention_oracle)",
        "oracle_s": round(oracle_s, 2),
    }


def mesh_attention_phase(*, seq: int, dim: int, n_devices: int = 4) -> dict:
    """`kv_sharded_attention` and `ring_attention` over a real
    ``n_devices`` mesh against the single-chip flash result (+-0.02)."""
    from attention_tpu.ops.flash import flash_attention
    from attention_tpu.parallel import kv_sharded_attention, ring_attention
    from attention_tpu.parallel.mesh import default_mesh

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (seq, dim), jnp.bfloat16)
    k = jax.random.normal(kk, (seq, dim), jnp.bfloat16)
    v = jax.random.normal(kv, (seq, dim), jnp.bfloat16)
    want = np.asarray(flash_attention(q, k, v), np.float32)
    devices = jax.devices()[:n_devices]
    errs = {}
    for name, fn, axis in (("kv_sharded", kv_sharded_attention, "kv"),
                           ("ring", ring_attention, "sp")):
        got = np.asarray(
            fn(q, k, v, mesh=default_mesh(axis, devices=devices)),
            np.float32)
        errs[name] = float(np.max(np.abs(got - want)))
        _check(errs[name] <= 0.02,
               f"{name} over {n_devices} devices differs from single-chip "
               f"flash by {errs[name]:.4f} (> 0.02)")
    return {"shape": [seq, dim], "n_devices": n_devices,
            "max_abs_err": errs}


def main() -> int:
    cache_dir = configure_compile_cache()
    device = require_tpu()
    print(describe_run(device, cache_dir))
    require_compiled_kernels()
    obs.enable()
    clock = CompileClock()

    serving = dict(model_kw=SMOKE_MODEL, engine_kw=SMOKE_ENGINE,
                   trace_kw=SMOKE_TRACE)
    run_phase("engine", engine_phase, clock, **serving)
    run_phase("bin_contract", bin_contract_phase, clock, **SMOKE_BIN)
    if device["count"] >= 4:
        run_phase("engine_mesh4", engine_phase, clock, **serving,
                  mesh_shards=4)
        run_phase("attention_mesh4", mesh_attention_phase, clock,
                  **SMOKE_MESH_ATTENTION)
    else:
        print(f"mesh phases not run: {device['count']} device(s)")

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
