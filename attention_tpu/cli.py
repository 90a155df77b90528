"""CLI harness: the reference's frozen main() contract, generalized.

The reference binary is ``./attention <testcase.bin>`` → load, compute,
verify, print "Correct!/Wrong!" + elapsed µs (`attention.c:164-196`,
`attention-mpi.c:497-541`).  This CLI preserves that exact interaction —
same output lines, same exit semantics — and adds what the course grader
provided externally: testcase generation and backend/precision selection
(the serial-vs-MPI binary split becomes ``--backend``).

Usage:
  python -m attention_tpu.cli run <testcase.bin> [--backend flash]
      [--dtype bf16|f32|f64] [--repeats 1] [--no-verify]
  python -m attention_tpu.cli generate <out.bin> --m 1024 --n 1024
      --dk 128 --dv 128 [--seed 0]
  python -m attention_tpu.cli suite <out_dir>     # simple..scale5 ladder
  python -m attention_tpu.cli backends
  python -m attention_tpu.cli tune --kernel flash --seq 32768 --dim 128
      # timed on-device tile search; winners persist in the per-device
      # cache (~/.cache/attention_tpu/), which dispatch reads back when
      # ATTN_TPU_TUNING_CACHE names it
  python -m attention_tpu.cli serve-sim [--trace trace.json]
      [--num-requests 8 --shared-prefix-len 129 --shared-count 4 ...]
      [--replicas 3 --deadline-ms 40 --tick-ms 1 --max-retries 3
       --chaos-plan plan.json --bursty --tenants 2]
      [--standbys 1 --suspect-after 2 --gray-plan gray.json]
      [--obs --obs-out run_dir [--obs-profile]]
      # continuous-batching engine over a request trace; prints
      # per-step (--per-step) and summary metrics JSON; --obs-out
      # persists the telemetry dump for `cli obs`; --replicas N serves
      # through the resilient multi-replica front end
      # (attention_tpu.frontend: deadlines, retry-with-backoff, load
      # shedding, graceful degradation) and --chaos-plan attaches a
      # replica-kill storm; --gray-plan attaches a gray-failure storm
      # (slow/flaky/stall/NaN windows) against the replica supervisor,
      # --standbys keeps warm spares for DEAD-verdict promotion, and
      # --trace-out embeds the gray plan so the run replays
      # byte-identically from the trace file alone
  python -m attention_tpu.cli analyze [paths ...] [--changed]
      [--format text|json|sarif] [--baseline FILE | --no-baseline]
      [--list-codes]
      # static analysis (attention_tpu.analysis): AST passes with
      # stable ATP### codes — trace purity, Pallas contracts,
      # precision, error taxonomy, tree conventions; exit 0 iff clean
      # modulo analysis/baseline.json; --changed lints only files
      # touched since `git merge-base HEAD --base`
  python -m attention_tpu.cli obs report --run run_dir
  python -m attention_tpu.cli obs export --run run_dir
      --format chrome|prom|jsonl [--out timeline.json]
      # unified telemetry (attention_tpu.obs): counters/spans summary,
      # or export — chrome lays out the ring's host spans; host vs
      # device on one clock is the --obs-profile capture itself
  python -m attention_tpu.cli chaos fuzz --seed 0 --cases 16
      [--families flash,decode,...] [--inject-failure] [--repro-dir DIR]
  python -m attention_tpu.cli chaos replay <repro.json|repro.bin>
  python -m attention_tpu.cli chaos shrink repro.json [--bin repro.bin]
  python -m attention_tpu.cli chaos faults --seed 0 --plans 5
      [--replicas 3]
      # differential fuzzing + engine fault injection
      # (attention_tpu.chaos): sampled kernel configs vs the fp64
      # oracle under the tolerance ledger; failing configs shrink to
      # minimal repros (plain ones to the reference .bin format `run`
      # replays); seeded fault plans storm the serving engine under
      # invariant checkers

Diagnostics (progress notes, warnings) go through the shared
``attention_tpu`` stdlib logger, stderr at INFO — the frozen
reference-contract lines (Correct!/Wrong!/Elapsed time) stay on
stdout, exactly as `attention.c` printed them.
"""

from __future__ import annotations

import argparse
import logging
import sys
import time

import numpy as np

_logger = logging.getLogger("attention_tpu.cli")


def _setup_logging(level: int = logging.INFO) -> None:
    """Attach one stderr handler to the shared ``attention_tpu`` logger
    (idempotent).  Library modules log under ``attention_tpu.*``; the
    CLI is the place that decides those records are user-visible."""
    root = logging.getLogger("attention_tpu")
    if not root.handlers:
        h = logging.StreamHandler(sys.stderr)
        h.setFormatter(logging.Formatter("%(name)s: %(message)s"))
        root.addHandler(h)
    # our handler is the single sink: without this, a root logger that
    # jax/absl already configured would print every record twice
    root.propagate = False
    root.setLevel(level)


def _cmd_run(args: argparse.Namespace) -> int:
    from attention_tpu import attention
    from attention_tpu.core.testcase import read_testcase, verify

    try:
        case = read_testcase(args.testcase)
    except FileNotFoundError:
        # reference diagnostic (attention.c:103-106)
        print(f"Cannot open file: {args.testcase}", file=sys.stderr)
        return 1
    except ValueError:
        print("Invalid testing data.", file=sys.stderr)  # attention.c:112
        return 1
    m, n, dk, dv = case.dims

    dtype = {"bf16": "bfloat16", "f32": "float32", "f64": "float64"}[args.dtype]
    if dtype == "bfloat16":
        import jax.numpy as jnp

        q, k, v = (jnp.asarray(x, dtype=jnp.bfloat16) for x in (case.q, case.k, case.v))
    else:
        q, k, v = (x.astype(dtype) for x in (case.q, case.k, case.v))

    from attention_tpu.utils.timing import benchmark

    # One untimed run produces the result and doubles as warmup, keeping
    # one-time costs (jit compilation; the native backend's first-use C
    # build) out of the timed region — the reference's timed region is
    # pure compute (attention.c:180-182), its compile happened at build
    # time.  Timing then follows the shared min-over-repeats discipline
    # on the fenced host clock.
    result = attention(q, k, v, backend=args.backend)
    timing = benchmark(
        attention, q, k, v, backend=args.backend,
        repeats=max(1, args.repeats), warmup=0,
    )
    best_us = timing.best_us
    result = np.asarray(result, dtype=np.float64)

    if args.no_verify or case.expected is None:
        print(f"Elapsed time: {best_us:.2f} us")
        return 0
    # Exact frozen output contract (attention.c:150-151,184-189): success
    # is "Correct!" + elapsed; failure is the first-mismatch diagnostic on
    # stdout then ONLY "Wrong!", and the exit status is 0 either way.
    # --stats appends one opt-in full-scan line AFTER the frozen lines
    # (max-abs-error / mismatch count — `core.testcase.verify_scan`).
    ok, msg = verify(case.expected, result)
    if ok:
        print("Correct!")
        print(f"Elapsed time: {best_us:.2f} us")
    else:
        print(msg)
        print("Wrong!")
    if args.stats:
        from attention_tpu.core.testcase import verify_scan

        print(verify_scan(case.expected, result).stats_line())
    return 0


def _cmd_generate(args: argparse.Namespace) -> int:
    from attention_tpu.core.testcase import generate_testcase, write_testcase

    case = generate_testcase(args.m, args.n, args.dk, args.dv, seed=args.seed)
    write_testcase(args.out, case)
    print(f"wrote {args.out}: m={args.m} n={args.n} dk={args.dk} dv={args.dv} "
          f"({case.nbytes()} bytes)")
    return 0


def _cmd_suite(args: argparse.Namespace) -> int:
    from attention_tpu.core.testcase import generate_suite

    for path in generate_suite(args.out_dir, seed=args.seed):
        print(f"wrote {path}")
    return 0


def _cmd_backends(_args: argparse.Namespace) -> int:
    from attention_tpu import available_backends

    for name in available_backends():
        print(name)
    return 0


def _build_sim_model(args: argparse.Namespace):
    """Deterministic tiny decoder for serving simulation: params come
    from PRNGKey(--model-seed), so a trace replays bit-identically."""
    import jax
    import jax.numpy as jnp

    from attention_tpu.models import TinyDecoder

    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[args.dtype]
    model = TinyDecoder(
        vocab=args.vocab, dim=args.dim, depth=args.depth,
        num_q_heads=args.q_heads, num_kv_heads=args.kv_heads,
        impl="flash", dtype=dtype,
    )
    probe = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(args.model_seed), probe)["params"]
    return model, params


def _cmd_serve_sim(args: argparse.Namespace) -> int:
    """Run the continuous-batching engine on a request trace (from
    --trace JSON, else synthetic) and print metrics JSON."""
    import json

    from attention_tpu.engine import (
        EngineConfig,
        ServingEngine,
        load_trace,
        replay,
        synthetic_trace,
    )

    obs_on = args.obs or args.obs_out or args.obs_profile
    if obs_on:
        from attention_tpu import obs

        obs.enable()
        obs.reset()

    model, params = _build_sim_model(args)
    if args.trace:
        trace = load_trace(args.trace)
    elif args.diurnal:
        from attention_tpu.engine import diurnal_trace

        trace = diurnal_trace(
            args.num_requests, vocab=args.vocab, seed=args.seed,
            period=args.diurnal_period, base_rate=args.base_rate,
            peak_rate=args.peak_rate, tenants=args.tenants,
            rag_every=args.rag_every,
            rag_prefill_len=args.rag_prefill_len,
            prompt_len_min=args.prompt_len_min,
            prompt_len_max=args.prompt_len_max,
            max_tokens=args.max_tokens,
            temperature=args.temperature,
        )
    elif args.disagg:
        from attention_tpu.engine.sim import disagg_trace

        trace = disagg_trace(
            args.num_requests, vocab=args.vocab, seed=args.seed,
            rate=args.base_rate, tenants=args.tenants,
            burst_every=args.burst_every, burst_size=args.burst_size,
            rag_prefill_len=args.rag_prefill_len,
            prompt_len_min=args.prompt_len_min,
            prompt_len_max=args.prompt_len_max,
            max_tokens=args.max_tokens,
            temperature=args.temperature,
        )
    elif args.bursty:
        from attention_tpu.engine import bursty_trace

        trace = bursty_trace(
            args.num_requests, vocab=args.vocab, seed=args.seed,
            tenants=args.tenants, burst_every=args.burst_every,
            burst_size=args.burst_size,
            shared_prefix_len=args.shared_prefix_len,
            prompt_len_min=args.prompt_len_min,
            prompt_len_max=args.prompt_len_max,
            max_tokens=args.max_tokens,
            temperature=args.temperature,
        )
    else:
        trace = synthetic_trace(
            args.num_requests, vocab=args.vocab, seed=args.seed,
            prompt_len_min=args.prompt_len_min,
            prompt_len_max=args.prompt_len_max,
            max_tokens=args.max_tokens, arrival_every=args.arrival_every,
            shared_prefix_len=args.shared_prefix_len,
            shared_count=args.shared_count,
            temperature=args.temperature,
        )
    # resolve the gray plan early: an explicit --gray-plan wins, else a
    # --trace file's embedded annotation attaches automatically (the
    # gray storm replays from the trace file alone)
    gray_plan_doc = None
    if args.gray_plan:
        with open(args.gray_plan) as f:
            gray_plan_doc = json.load(f)
    elif args.trace:
        from attention_tpu.engine.sim import load_gray_plan

        gray_plan_doc = load_gray_plan(args.trace)
    if args.trace_out:
        from attention_tpu.engine import save_trace

        save_trace(args.trace_out, trace, gray_plan=gray_plan_doc)
        _logger.info("wrote trace: %s", args.trace_out)

    config = EngineConfig(
        num_pages=args.num_pages, page_size=args.page_size,
        max_seq_len=args.max_seq_len,
        max_decode_batch=args.max_decode_batch,
        max_prefill_rows=args.max_prefill_rows,
        prefill_chunk=args.prefill_chunk,
        token_budget=args.token_budget,
        watermark_pages=args.watermark_pages,
        mesh_shards=args.mesh_shards,
    )
    if (args.snapshot_dir is None) != (args.snapshot_every is None):
        print("--snapshot-dir and --snapshot-every must be set "
              "together", file=sys.stderr)
        return 2
    if args.disagg and args.replicas < 2:
        print("--disagg needs at least two replicas (--replicas >= 2): "
              "one prefill pool member and one decode pool member",
              file=sys.stderr)
        return 2
    if args.autoscale and not args.disagg:
        print("--autoscale acts on the disaggregated fleet's pools; "
              "set --disagg too", file=sys.stderr)
        return 2
    if args.autoscale and not args.standbys:
        print("--autoscale needs warm spares to promote "
              "(--standbys > 0)", file=sys.stderr)
        return 2
    if args.prefix_store and not args.replicas:
        print("--prefix-store needs the multi-replica front end "
              "(--replicas > 0): fleet-wide reuse has no meaning on "
              "one engine", file=sys.stderr)
        return 2
    if args.replicas:
        return _serve_sim_frontend(args, model, params, config, trace,
                                   gray_plan=gray_plan_doc)
    if gray_plan_doc is not None:
        _logger.info("gray plan ignored on the single-engine path "
                     "(gray failures need --replicas)")
    if args.anomaly or args.incident_dir:
        _logger.info("--anomaly/--incident-dir ignored on the "
                     "single-engine path (the incident layer runs in "
                     "the front-end tick loop; needs --replicas)")

    engine = ServingEngine(model, params, config)
    if args.snapshot_dir is not None:
        from attention_tpu.engine import SnapshotManager

        SnapshotManager(engine, args.snapshot_dir,
                        every=args.snapshot_every)
        _logger.info("snapshotting every %d steps to %s",
                     args.snapshot_every, args.snapshot_dir)
    import contextlib

    profile_cm = contextlib.nullcontext()
    if args.obs_profile:
        import os

        from attention_tpu.obs.export import DUMP_DEVICE
        from attention_tpu.utils import profiling

        if not args.obs_out:
            print("--obs-profile requires --obs-out", file=sys.stderr)
            return 2
        profile_cm = profiling.trace(
            os.path.join(args.obs_out, DUMP_DEVICE))
    with profile_cm:
        summary, outputs = replay(engine, trace, max_steps=args.max_steps)
    if args.per_step:
        for m in engine.metrics.steps:
            print(m.to_json())
    record = engine.metrics.to_run_record(
        config="engine-serve-sim",
        extra={"num_pages": config.num_pages,
               "page_size": config.page_size,
               "prefill_chunk": config.prefill_chunk,
               "max_decode_batch": config.max_decode_batch,
               "token_budget": config.token_budget},
    )
    out = {"summary": summary, "run_record": json.loads(record.to_json())}
    if args.outputs:
        out["outputs"] = outputs
    if args.obs_out:
        from attention_tpu import obs

        obs.dump(args.obs_out)
        _logger.info("wrote telemetry dump: %s", args.obs_out)
    print(json.dumps(out))
    return 0


def _serve_sim_frontend(args: argparse.Namespace, model, params,
                        config, trace, *,
                        gray_plan: dict | None = None) -> int:
    """serve-sim through the resilient multi-replica front end
    (attention_tpu.frontend): N engine replicas, deadlines, retry,
    shedding, optional chaos storm and gray-failure plans."""
    import json

    from attention_tpu.frontend import (
        FrontendConfig,
        RetryPolicy,
        ServingFrontend,
        SupervisorPolicy,
        replay_frontend,
    )

    ttl = None
    if args.deadline_ms is not None:
        ttl = max(1, int(round(args.deadline_ms / args.tick_ms)))
    supervisor = (SupervisorPolicy(suspect_after=args.suspect_after)
                  if args.suspect_after is not None
                  else SupervisorPolicy())
    forecast_policy = None
    if args.forecast or args.forecast_advisory:
        from attention_tpu.frontend import ForecastPolicy

        season = args.forecast_season
        if season is None and args.diurnal:
            season = args.diurnal_period
        forecast_policy = ForecastPolicy(
            season_ticks=season, horizon=args.forecast_horizon,
            advisory=args.forecast_advisory)
    prefix_store = None
    if args.prefix_store:
        from attention_tpu.prefixstore import PrefixStoreConfig

        prefix_store = PrefixStoreConfig(
            max_bytes=args.prefix_store_bytes)
    anomaly_policy = None
    if args.anomaly:
        from attention_tpu.obs.anomaly import AnomalyPolicy

        anomaly_policy = AnomalyPolicy()
    fleet_topology = None
    autoscaler_policy = None
    if args.disagg:
        from attention_tpu.fleet import AutoscalerPolicy, FleetTopology

        # roughly 1:2 prefill:decode — prompts are bursty, streams are
        # steady — with the autoscaler free to rebalance at runtime
        prefill = max(1, args.replicas // 3)
        fleet_topology = FleetTopology(
            prefill_replicas=prefill,
            decode_replicas=args.replicas - prefill)
        if args.autoscale:
            autoscaler_policy = AutoscalerPolicy()
    frontend = ServingFrontend(
        model, params, config,
        FrontendConfig(
            num_replicas=args.replicas, seed=args.seed,
            retry=RetryPolicy(max_retries=args.max_retries),
            default_ttl_ticks=ttl,
            snapshot_dir=args.snapshot_dir,
            snapshot_every=args.snapshot_every,
            supervisor=supervisor,
            standbys=args.standbys,
            forecast=forecast_policy,
            prefix_store=prefix_store,
            anomaly=anomaly_policy,
            incident_dir=args.incident_dir,
            fleet=fleet_topology,
            autoscaler=autoscaler_policy,
        ),
    )
    if args.chaos_plan or gray_plan is not None:
        from attention_tpu.chaos.faults import (
            FaultPlan,
            FrontendFaultInjector,
        )

        if args.chaos_plan:
            with open(args.chaos_plan) as f:
                plan = FaultPlan.from_json(f.read())
            FrontendFaultInjector(frontend, plan)
            _logger.info("attached chaos plan: %s (%d events)",
                         args.chaos_plan, len(plan.events))
        if gray_plan is not None:
            plan = FaultPlan.from_json(json.dumps(gray_plan))
            FrontendFaultInjector(frontend, plan)
            _logger.info("attached gray plan (%d events)",
                         len(plan.events))
    summary, outputs = replay_frontend(frontend, trace,
                                       max_ticks=args.max_steps)
    record = frontend.to_run_record(
        config="frontend-serve-sim",
        extra={"num_pages": config.num_pages,
               "page_size": config.page_size,
               "deadline_ms": args.deadline_ms,
               "tick_ms": args.tick_ms},
    )
    # SLO observatory (obs.slo): deterministic error-budget accounting
    # over the run's latency rows, mirrored onto the frozen registry
    # series and persisted next to the telemetry dump for `cli obs slo`
    from attention_tpu.obs import slo as slo_mod

    slo_report = slo_mod.slo_report(frontend.latency_rows(),
                                    horizon_tick=summary["ticks"])
    slo_mod.publish(slo_report)
    out = {"summary": summary,
           "run_record": json.loads(record.to_json()),
           "slo": {"fleet": {ob["objective"]:
                             {"burn_rate": ob["burn_rate"],
                              "budget_remaining": ob["budget_remaining"],
                              "violations": ob["violations"]}
                             for ob in slo_report["fleet"]["slo"]}}}
    # forecast + capacity observatory (obs.forecast/capacity): a
    # deterministic document over the tracker's per-tick series,
    # persisted as forecast.json for `cli obs forecast`
    forecast_doc = None
    if frontend.forecast is not None:
        from attention_tpu.obs import capacity as capacity_mod
        from attention_tpu.obs import forecast as forecast_mod

        forecast_doc = frontend.forecast_report()
        forecast_mod.publish(forecast_doc)
        capacity_mod.publish(forecast_doc)
        pblk = next((b for b in forecast_doc["series"]
                     if b["name"] == forecast_mod.PRESSURE_SERIES), None)
        fleet = forecast_doc["capacity"]["fleet"]
        out["forecast"] = {
            "pressure_next": (pblk["forecast"][0]["mean"]
                              if pblk and pblk["forecast"] else None),
            "one_step_mape": (pblk["backtest"]["one_step_mape"]
                              if pblk else None),
            "headroom": fleet["headroom"],
            "cost_per_token": fleet["cost_per_token"],
            "time_to_saturation":
                forecast_doc["capacity"]["time_to_saturation"],
        }
    # incident layer: anomaly detector report + flight-recorder block.
    # The blackbox block lives at the CLI level (not in the frontend's
    # summary) so the off-path token streams stay byte-identical.
    anomaly_doc = None
    if frontend.anomaly is not None:
        anomaly_doc = frontend.anomaly.report()
        out["anomaly"] = {"firings": len(anomaly_doc["firings"]),
                          "active": anomaly_doc["active"]}
    if args.obs or args.obs_out or args.obs_profile or args.incident_dir:
        from attention_tpu.obs import blackbox as blackbox_mod

        out["blackbox"] = {
            "ring_depth": blackbox_mod.depth(),
            "events_total": blackbox_mod.total(),
            "incidents": (len(frontend.postmortem.written)
                          if frontend.postmortem is not None else 0),
        }
    if args.outputs:
        out["outputs"] = outputs
    if args.obs_out:
        from attention_tpu import obs

        obs.dump(args.obs_out)
        obs.write_slo(args.obs_out, slo_report)
        if forecast_doc is not None:
            obs.write_forecast(args.obs_out, forecast_doc)
        if anomaly_doc is not None:
            obs.write_anomaly(args.obs_out, anomaly_doc)
        _logger.info("wrote telemetry dump: %s", args.obs_out)
    print(json.dumps(out))
    return 0


def _snapshot_paths(path: str) -> list[str]:
    """A snapshot file as-is; a directory expands to its snapshots,
    newest first (the order recovery would consider them)."""
    import os

    if os.path.isdir(path):
        from attention_tpu.engine.snapshot import list_snapshots

        return [p for _, p in reversed(list_snapshots(path))]
    return [path]


def _cmd_snapshot_inspect(args: argparse.Namespace) -> int:
    """Print one JSON line per snapshot: manifest + reconstruction
    metadata, without loading pool payloads into an engine."""
    import json

    from attention_tpu.engine.errors import SnapshotError
    from attention_tpu.engine.snapshot import inspect
    from attention_tpu.fleet.handoff import inspect_handoff, is_handoff

    paths = _snapshot_paths(args.path)
    if not paths:
        print(f"no snapshots under {args.path}", file=sys.stderr)
        return 1
    rc = 0
    for p in paths:
        try:
            # handoff blobs (fleet.handoff) share the directory with
            # engine snapshots; sniff the manifest line and report the
            # per-section CRC verdicts instead of engine metadata
            with open(p, "rb") as f:
                blob = f.read()
            if is_handoff(blob):
                doc = inspect_handoff(blob)
                doc["path"] = p
                print(json.dumps(doc, sort_keys=True))
                if not doc["valid"]:
                    rc = 1
                continue
            print(json.dumps(inspect(p), sort_keys=True))
        except SnapshotError as e:
            print(json.dumps({"path": p, "error": str(e)},
                             sort_keys=True))
            rc = 1
    return rc


def _cmd_snapshot_verify(args: argparse.Namespace) -> int:
    """Validate snapshot integrity (magic, version, section table,
    per-section checksums); exit 0 iff every snapshot is restorable."""
    paths = _snapshot_paths(args.path)
    if not paths:
        print(f"no snapshots under {args.path}", file=sys.stderr)
        return 1
    from attention_tpu.engine.snapshot import verify

    rc = 0
    for p in paths:
        problems = verify(p)
        if problems:
            rc = 1
            for problem in problems:
                print(f"{p}: {problem}")
        else:
            print(f"{p}: ok")
    return rc


def _add_serve_sim_args(ss) -> None:
    """serve-sim's flag set, shared with scripts/engine_trace.py."""
    ss.add_argument("--trace", default=None,
                    help="JSON request trace to replay (default: "
                         "synthesize one from the --num-requests knobs)")
    ss.add_argument("--trace-out", default=None,
                    help="write the (possibly synthetic) trace here")
    ss.add_argument("--per-step", action="store_true",
                    help="emit one JSON line per engine step")
    ss.add_argument("--outputs", action="store_true",
                    help="include generated token ids in the summary")
    ss.add_argument("--max-steps", type=int, default=10000)
    # synthetic-trace knobs
    ss.add_argument("--num-requests", type=int, default=8)
    ss.add_argument("--seed", type=int, default=0)
    ss.add_argument("--prompt-len-min", type=int, default=4)
    ss.add_argument("--prompt-len-max", type=int, default=24)
    ss.add_argument("--max-tokens", type=int, default=8)
    ss.add_argument("--arrival-every", type=int, default=1)
    ss.add_argument("--shared-prefix-len", type=int, default=0)
    ss.add_argument("--shared-count", type=int, default=0)
    ss.add_argument("--temperature", type=float, default=0.0)
    # bursty multi-tenant trace knobs (engine.sim.bursty_trace)
    ss.add_argument("--bursty", action="store_true",
                    help="synthesize a multi-tenant bursty trace "
                         "(sessions, priorities, per-tenant shared "
                         "prefixes) instead of the plain one")
    ss.add_argument("--tenants", type=int, default=2)
    ss.add_argument("--burst-every", type=int, default=6)
    ss.add_argument("--burst-size", type=int, default=3)
    # diurnal trace knobs (engine.sim.diurnal_trace)
    ss.add_argument("--diurnal", action="store_true",
                    help="synthesize a sinusoidal diurnal trace (one "
                         "day of --diurnal-period ticks between "
                         "--base-rate and --peak-rate req/tick, with "
                         "periodic RAG prefill bursts) instead of the "
                         "plain one")
    ss.add_argument("--diurnal-period", type=int, default=48,
                    help="ticks per simulated day")
    ss.add_argument("--base-rate", type=float, default=1.0,
                    help="trough arrival rate, requests/tick")
    ss.add_argument("--peak-rate", type=float, default=4.0,
                    help="peak arrival rate, requests/tick")
    ss.add_argument("--rag-every", type=int, default=7,
                    help="every Nth diurnal request is a long-prefill "
                         "RAG burst")
    ss.add_argument("--rag-prefill-len", type=int, default=64,
                    help="shared retrieval-header length for RAG "
                         "bursts (0 disables them)")
    # load forecasting + capacity observatory (obs.forecast/capacity;
    # front-end path only)
    ss.add_argument("--forecast", action="store_true",
                    help="track per-tick fleet series and emit the "
                         "forecast + capacity report (front-end path "
                         "only; never changes scheduling)")
    ss.add_argument("--forecast-horizon", type=int, default=8,
                    help="forecast horizon in ticks")
    ss.add_argument("--forecast-season", type=int, default=None,
                    help="seasonal period in ticks (default: "
                         "--diurnal-period when --diurnal, else no "
                         "seasonal term)")
    ss.add_argument("--forecast-advisory", action="store_true",
                    help="log would-have-acted forecast events into "
                         "the event log (still never acts); implies "
                         "--forecast")
    # resilient multi-replica front end (attention_tpu.frontend)
    # disaggregated serving (attention_tpu.fleet)
    ss.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode fleet: fresh "
                         "admissions route to a prefill pool and hand "
                         "off to the decode pool at prompt commit, "
                         "shipping committed KV pages instead of "
                         "re-prefilling (needs --replicas >= 2); "
                         "without --trace, synthesizes the disagg "
                         "mixed workload (steady decode sessions + "
                         "RAG prefill bursts)")
    ss.add_argument("--autoscale", action="store_true",
                    help="closed-loop elastic autoscaler over the "
                         "fleet pools: promotes warm standbys on "
                         "forecast watermark crossings, drains + "
                         "demotes on sustained slack (needs --disagg "
                         "and --standbys > 0)")
    ss.add_argument("--replicas", type=int, default=0,
                    help="serve through the resilient front end with "
                         "N engine replicas (0 = single engine, the "
                         "legacy path)")
    ss.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request TTL in virtual ms "
                         "(converted to ticks via --tick-ms; "
                         "front-end path only)")
    ss.add_argument("--tick-ms", type=float, default=1.0,
                    help="virtual milliseconds per front-end tick")
    ss.add_argument("--max-retries", type=int, default=3,
                    help="front-end retry budget per request")
    ss.add_argument("--chaos-plan", default=None,
                    help="frontend fault-plan JSON (chaos.faults."
                         "FaultPlan) to attach to the run")
    # gray-failure supervision (attention_tpu.frontend.supervisor)
    ss.add_argument("--standbys", type=int, default=0,
                    help="warm spare replicas promoted on a DEAD "
                         "supervisor verdict (front-end path only)")
    ss.add_argument("--suspect-after", type=int, default=None,
                    help="supervisor hysteresis: consecutive bad ticks "
                         "before HEALTHY -> SUSPECT (default: policy "
                         "default)")
    ss.add_argument("--gray-plan", default=None,
                    help="gray-failure fault-plan JSON (slow_step/"
                         "flaky_step/stall/nan windows) to attach; a "
                         "--trace file's embedded gray_plan annotation "
                         "attaches automatically, and --trace-out "
                         "embeds the active plan")
    # crash-consistent durability (attention_tpu.engine.snapshot)
    ss.add_argument("--snapshot-dir", default=None,
                    help="persist checksummed engine snapshots + "
                         "journals here (per-replica subdirs on the "
                         "front-end path); requires --snapshot-every")
    ss.add_argument("--snapshot-every", type=int, default=None,
                    help="snapshot period in engine steps / front-end "
                         "ticks; requires --snapshot-dir")
    # global prefix tier (attention_tpu.prefixstore)
    ss.add_argument("--prefix-store", action="store_true",
                    help="attach the fleet-wide prefix store to the "
                         "multi-replica front end (--replicas > 0): "
                         "committed prompt pages export as CRC'd "
                         "records any replica imports on a miss, and "
                         "identical prompt storms prefill exactly "
                         "once fleet-wide (single-flight leases); "
                         "with --snapshot-dir the store persists as "
                         "its own checksummed section file")
    ss.add_argument("--prefix-store-bytes", type=int, default=1 << 22,
                    help="prefix-store byte budget (LRU-evicted)")
    # model knobs (deterministic from --model-seed)
    ss.add_argument("--vocab", type=int, default=64)
    ss.add_argument("--dim", type=int, default=64)
    ss.add_argument("--depth", type=int, default=2)
    ss.add_argument("--q-heads", type=int, default=4)
    ss.add_argument("--kv-heads", type=int, default=2)
    ss.add_argument("--dtype", choices=["f32", "bf16"], default="f32")
    ss.add_argument("--model-seed", type=int, default=0)
    # engine knobs
    ss.add_argument("--num-pages", type=int, default=64)
    ss.add_argument("--page-size", type=int, default=128)
    ss.add_argument("--max-seq-len", type=int, default=512)
    ss.add_argument("--max-decode-batch", type=int, default=8)
    ss.add_argument("--max-prefill-rows", type=int, default=2)
    ss.add_argument("--prefill-chunk", type=int, default=32)
    ss.add_argument("--token-budget", type=int, default=128)
    ss.add_argument("--watermark-pages", type=int, default=1)
    ss.add_argument("--mesh-shards", type=int, default=0,
                    help="serve through KV-head-sharded kernels on a "
                         "1D 'tp' mesh of N local devices (0 = "
                         "single-device; tokens are identical either "
                         "way; --kv-heads must divide by N; on CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    # incident layer (obs.anomaly / obs.blackbox / obs.postmortem;
    # front-end path only)
    ss.add_argument("--anomaly", action="store_true",
                    help="run the deterministic anomaly detectors "
                         "(residual band, burn slope, gray failure) "
                         "in the tick loop; advisory-only, never "
                         "changes scheduling (front-end path only)")
    ss.add_argument("--incident-dir", default=None,
                    help="dump an incident-<tick>/ postmortem bundle "
                         "here on every typed error or detector "
                         "firing (front-end path only); read back "
                         "with `cli obs postmortem --run DIR`")
    # telemetry (attention_tpu.obs)
    ss.add_argument("--obs", action="store_true",
                    help="enable the unified telemetry subsystem for "
                         "this run (default off, zero overhead)")
    ss.add_argument("--obs-out", default=None,
                    help="write the telemetry dump (metrics.json + "
                         "events.jsonl) here; implies --obs")
    ss.add_argument("--obs-profile", action="store_true",
                    help="also capture a jax.profiler trace under "
                         "<obs-out>/device: the program's spans and "
                         "the device lanes on one clock; implies --obs")


def _cmd_tune(args: argparse.Namespace) -> int:
    import json

    from attention_tpu.tuning.search import CLI_KERNELS, tune

    kernels = (list(CLI_KERNELS) if args.kernel == "all"
               else [args.kernel])
    rc = 0
    for name in kernels:
        _logger.info("tuning %s (seq=%d, dim=%d)...",
                     name, args.seq, args.dim)
        try:
            rec = tune(
                CLI_KERNELS[name],
                seq=args.seq, dim=args.dim, heads=args.heads,
                kv_heads=args.kv_heads, batch=args.batch,
                dtype=args.dtype, causal=args.causal,
                window=args.window, sinks=args.sinks, stats=args.stats,
                max_mode=args.max_mode,
                repeats=args.repeats, cache_path=args.cache,
                write=not args.dry_run,
                log=_logger.info,
            )
        except Exception as e:  # noqa: BLE001 - report and keep sweeping
            print(json.dumps({"kernel": name,
                              "error": f"{type(e).__name__}: "
                                       f"{str(e)[:200]}"}))
            rc = 1
            continue
        print(json.dumps(rec))
    return rc


def _chaos_defect(args: argparse.Namespace):
    """The synthetic-failure hook shared by the chaos subcommands."""
    if not getattr(args, "inject_failure", False):
        return None
    from attention_tpu.chaos.fuzzer import synthetic_defect

    return synthetic_defect


def _cmd_chaos_fuzz(args: argparse.Namespace) -> int:
    """Seeded differential fuzz campaign: sampled kernel configs vs the
    fp64 oracle, judged by the tolerance ledger.  Deterministic: same
    seed -> same cases -> same report."""
    import json

    from attention_tpu.chaos.configs import FAMILIES
    from attention_tpu.chaos.fuzzer import run_campaign
    from attention_tpu.chaos.shrink import write_repro_json

    families = (args.families.split(",") if args.families
                else list(FAMILIES))
    for fam in families:
        if fam not in FAMILIES:
            print(f"unknown family {fam!r}; known: {list(FAMILIES)}",
                  file=sys.stderr)
            return 2
    report = run_campaign(args.seed, args.cases, families=families,
                          max_mode=args.max_mode,
                          defect=_chaos_defect(args), log=_logger.info)
    if args.repro_dir and report.failures:
        import os

        os.makedirs(args.repro_dir, exist_ok=True)
        for i, r in enumerate(report.failures):
            path = os.path.join(args.repro_dir, f"repro-{i}.json")
            write_repro_json(path, r.config)
            _logger.info("wrote failing-config repro: %s", path)
    print(json.dumps(report.to_dict(), sort_keys=True))
    return 0 if report.ok else 1


def _cmd_chaos_replay(args: argparse.Namespace) -> int:
    """Re-run one repro: a `.bin` replays through the frozen run
    harness semantics (backend result vs embedded expected), a `.json`
    re-runs the exact fuzz case.  Exit 0 iff the case passes."""
    import json

    if args.repro.endswith(".bin"):
        from attention_tpu import attention
        from attention_tpu.core.testcase import read_testcase, verify_scan

        case = read_testcase(args.repro)
        if case.expected is None:
            print(f"no expected output in {args.repro}", file=sys.stderr)
            return 2
        result = np.asarray(
            attention(case.q, case.k, case.v, backend=args.backend),
            dtype=np.float64,
        )
        scan = verify_scan(case.expected, result)
        print("Correct!" if scan.ok else f"{scan.message}\nWrong!")
        print(scan.stats_line())
        return 0 if scan.ok else 1
    from attention_tpu.chaos.fuzzer import run_case
    from attention_tpu.chaos.shrink import read_repro_json

    result = run_case(read_repro_json(args.repro),
                      defect=_chaos_defect(args))
    print(json.dumps(result.to_dict(), sort_keys=True))
    return 0 if result.ok else 1


def _cmd_chaos_shrink(args: argparse.Namespace) -> int:
    """Minimize a failing repro config; write the minimal `.json` and,
    when the minimum is plain single-head attention, the reference
    `.bin` testcase that `cli run` replays."""
    import json

    from attention_tpu.chaos.shrink import (
        read_repro_json,
        shrink,
        write_repro_bin,
        write_repro_json,
    )

    config = read_repro_json(args.repro)
    try:
        res = shrink(config, defect=_chaos_defect(args),
                     log=_logger.info)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2
    if args.out:
        write_repro_json(args.out, res.minimal)
        _logger.info("wrote minimal repro: %s", args.out)
    wrote_bin = None
    if args.bin:
        if res.minimal.is_plain:
            write_repro_bin(args.bin, res.minimal)
            wrote_bin = args.bin
            _logger.info("wrote .bin repro: %s", args.bin)
        else:
            _logger.info(
                ".bin skipped: minimal config is not plain (%s)",
                res.minimal.to_json())
    print(json.dumps({
        "original": json.loads(res.original.to_json()),
        "minimal": json.loads(res.minimal.to_json()),
        "steps": res.steps,
        "attempts": res.attempts,
        "max_abs_err": res.final.max_abs_err,
        "tolerance": res.final.tolerance,
        "bin": wrote_bin,
    }, sort_keys=True))
    return 0


def _cmd_chaos_faults(args: argparse.Namespace) -> int:
    """Seeded fault-injection campaign against the serving engine
    (--replicas 1, default) or the multi-replica front end
    (--replicas N > 1: replica-kill/restart storms on top of the
    OOM/preempt/cancel kinds).  Every plan must hold the engine
    invariants — plus, for storms, no-request-lost and surviving-
    replica conservation.  Exit 0 iff no violations."""
    import json

    if args.replicas > 1:
        from attention_tpu.chaos.faults import run_frontend_campaign

        report = run_frontend_campaign(
            args.seed, num_plans=args.plans,
            num_requests=args.requests, num_replicas=args.replicas,
            temperature=args.temperature,
            events_per_plan=args.events, log=_logger.info,
        )
    else:
        from attention_tpu.chaos.faults import run_campaign

        report = run_campaign(
            args.seed, num_plans=args.plans,
            num_requests=args.requests,
            temperature=args.temperature,
            events_per_plan=args.events, log=_logger.info,
        )
    out = report.to_dict()
    if not args.outputs:
        for r in out["reports"]:
            r.pop("outputs", None)
    print(json.dumps(out, sort_keys=True))
    return 0 if report.ok else 1


def _changed_files(root: str, base: str) -> list[str]:
    """Repo-root-relative paths touched since ``merge-base HEAD base``
    (committed, staged, unstaged, and untracked).  On ``base``'s own
    branch the merge-base IS HEAD, so only working-tree changes show —
    exactly what a builder mid-PR wants to lint."""
    import subprocess

    def git(*argv: str) -> list[str]:
        out = subprocess.run(["git", "-C", root, *argv],
                             capture_output=True, text=True, check=True)
        return [line for line in out.stdout.splitlines() if line]

    try:
        mb = git("merge-base", "HEAD", base)[0]
        changed = set(git("diff", "--name-only", mb, "--"))
        changed |= set(git("ls-files", "--others", "--exclude-standard"))
    except (OSError, subprocess.SubprocessError, IndexError) as e:
        raise SystemExit(f"--changed needs a git checkout with ref "
                         f"{base!r}: {e}") from e
    return sorted(changed)


def _cmd_analyze(args: argparse.Namespace) -> int:
    """Run the static-analysis passes (attention_tpu.analysis): exit 0
    iff the selected files are clean modulo the committed baseline."""
    import os

    from attention_tpu import analysis
    from attention_tpu.analysis import report as areport

    root = analysis.repo_root()
    if args.list_codes:
        for code in sorted(analysis.CODES.values(),
                           key=lambda c: c.code):
            print(f"{code.code}  {code.severity.value:7s} "
                  f"{code.title}: {code.summary}")
        return 0

    rel_paths = None
    analyzer_changed = False
    if args.changed:
        rel_paths = _changed_files(root, args.base)
        # an edit under analysis/ changes what every pass would say
        # about every file — the call-graph closure below can't model
        # that (passes aren't callees), so escalate to a full run
        if any(p.startswith("attention_tpu/analysis/")
               for p in rel_paths):
            rel_paths = None
            analyzer_changed = True
    if args.paths and not analyzer_changed:
        rel_paths = (rel_paths or []) + [
            os.path.relpath(os.path.abspath(p), root).replace(os.sep, "/")
            for p in args.paths
        ]
    index = None
    if rel_paths is not None and any(
            p.needs_index for p in analysis.PASSES.values()):
        # interprocedural passes see hazards across call edges, so a
        # helper edit must re-lint the files that CALL the helper — the
        # call-graph reverse closure (--changed can't silently pass a
        # hazard introduced one level away)
        from attention_tpu.analysis import core as acore

        index = acore.build_index(root)
        closure = index.files_calling(
            [p for p in rel_paths if p.endswith(".py")])
        if closure:
            rel_paths = sorted(set(rel_paths) | closure)
    timings: dict[str, float] | None = {} if args.timings else None
    findings = analysis.analyze(root, rel_paths=rel_paths,
                                timings=timings, index=index)
    if timings is not None:
        total = sum(timings.values())
        for name, secs in sorted(timings.items(), key=lambda kv: -kv[1]):
            print(f"{secs * 1e3:9.1f} ms  {name}", file=sys.stderr)
        print(f"{total * 1e3:9.1f} ms  total", file=sys.stderr)

    problems: list[str] = []
    if not args.no_baseline:
        bpath = args.baseline or areport.default_baseline_path(root)
        if os.path.isfile(bpath):
            try:
                entries = areport.load_baseline(bpath)
            except ValueError as e:
                print(str(e), file=sys.stderr)
                return 2
            # a partial run can't tell a stale entry from an unscanned
            # file, so only full runs police baseline staleness
            findings, problems = areport.apply_baseline(findings, entries)
            if rel_paths is not None:
                problems = []
        elif args.baseline:
            print(f"no such baseline: {bpath}", file=sys.stderr)
            return 2

    render = {"text": areport.render_text, "json": areport.render_json,
              "sarif": areport.render_sarif,
              "github": areport.render_github}[args.format]
    text = render(findings, problems)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text)
        _logger.info("wrote %s report: %s", args.format, args.out)
    else:
        sys.stdout.write(text)
    return 1 if (findings or problems) else 0


def _obs_load(args: argparse.Namespace):
    """(snapshot, events, device_dir) for an ``obs`` subcommand: from a
    --run dump directory, else the live in-process state (useful when a
    caller invokes cli.main() programmatically after a run)."""
    from attention_tpu import obs

    if args.run:
        snapshot, events = obs.load_dump(args.run)
        device = args.device_trace or obs.device_dir_of(args.run)
    else:
        snapshot, events = obs.live_snapshot(), obs.events()
        device = args.device_trace
    return snapshot, events, device


def _cmd_obs_report(args: argparse.Namespace) -> int:
    """Human-oriented run picture: instrument families first (every
    layer that recorded anything, frontend.* through engine.step.*),
    then counters, gauges, histogram/digest aggregates, the compile
    log, span aggregates, and per-module device seconds when a capture
    exists."""
    snapshot, events, device = _obs_load(args)

    def _lbl(labels):
        return ("{" + ",".join(f"{k}={v}" for k, v in
                               sorted(labels.items())) + "}"
                if labels else "")

    # grouped family view: series counts per layer.component, so the
    # PR 6-11 families (frontend.*, engine.snapshot.*, engine.step.*)
    # and the new digest/SLO series are visible at a glance
    fams: dict[str, dict[str, int]] = {}
    for kind in ("counters", "gauges", "histograms", "digests"):
        for s in snapshot.get(kind, []):
            fam = ".".join(s["name"].split(".")[:2])
            fams.setdefault(fam, {}).setdefault(kind, 0)
            fams[fam][kind] += 1
    print("== families ==")
    for fam in sorted(fams):
        parts = ", ".join(f"{n} {k}" for k, n in
                          sorted(fams[fam].items()))
        print(f"  {fam}: {parts}")
    print("== counters ==")
    keys = {}
    for s in snapshot.get("counters", []):
        print(f"  {s['name']}{_lbl(s['labels'])} = {s['value']:g}")
        if s["name"] == "engine.attention.keys":
            keys[s["labels"].get("which")] = s["value"]
    if keys.get("attended"):
        # a selector's model: 1 where attention reads the chosen rows
        # alone (`EngineMetrics.summary`)
        print("  rows_read_per_key_attended = "
              f"{keys.get('rows_read', 0) / keys['attended']:g}")
    print("== gauges ==")
    for s in snapshot.get("gauges", []):
        print(f"  {s['name']}{_lbl(s['labels'])} = {s['value']:g}")
    print("== histograms ==")
    for s in snapshot.get("histograms", []):
        mean = s["sum"] / s["count"] if s["count"] else 0.0
        print(f"  {s['name']}{_lbl(s['labels'])}: count={s['count']} "
              f"mean={mean:.3f} sum={s['sum']:.3f}")
    print("== digests ==")
    for s in snapshot.get("digests", []):
        p = s["percentiles"]
        print(f"  {s['name']}{_lbl(s['labels'])}: count={s['count']} "
              f"p50={p['p50']:.3f} p90={p['p90']:.3f} "
              f"p99={p['p99']:.3f} p999={p['p999']:.3f}")
    # forecast + capacity observatory, when the run dumped one
    fdoc = None
    if args.run:
        from attention_tpu import obs as obs_mod

        fdoc = obs_mod.load_forecast(args.run)
    if fdoc is not None:
        from attention_tpu.obs.forecast import PRESSURE_SERIES

        print("== forecast ==")
        cap = fdoc["capacity"]
        print(f"  horizon={fdoc['horizon']} "
              f"ticks={cap['fleet']['ticks']} "
              f"headroom={cap['fleet']['headroom']:g} "
              f"cost_per_token={cap['fleet']['cost_per_token']}")
        for blk in fdoc["series"]:
            st = blk["state"]
            season = (f" season[{len(st['seasonal'])}]"
                      if st["seasonal"] else "")
            print(f"  {blk['name']}: level={st['level']:g} "
                  f"trend={st['trend']:g}{season} "
                  f"mape={blk['backtest']['one_step_mape']:g} "
                  f"coverage={blk['backtest']['coverage']:g}")
            if blk["name"] == PRESSURE_SERIES:
                for row in blk["forecast"]:
                    print(f"    h={row['h']} tick={row['tick']} "
                          f"mean={row['mean']:g} "
                          f"[{row['lo']:g}, {row['hi']:g}]")
        for name, tts in sorted(cap["time_to_saturation"].items()):
            when = (f"tick {tts['tick']} (h={tts['h']}, "
                    f"pressure {tts['pressure']:g})"
                    if tts["tick"] is not None
                    else "beyond horizon")
            print(f"  saturation[{name}] @ {tts['watermark']:g}: {when}")
    # anomaly observatory (obs.anomaly), when the run dumped one
    adoc = None
    if args.run:
        from attention_tpu import obs as obs_mod

        adoc = obs_mod.load_anomaly(args.run)
    if adoc is not None:
        print("== anomalies ==")
        det = adoc["detectors"]
        rb = det["residual_band"]
        print(f"  residual_band: residual={rb['residual']:g} "
              f"band_p90={rb['band_p90']:g} "
              f"ticks={rb['observed_ticks']}")
        for obj, slope in sorted(det["burn_slope"].items()):
            print(f"  burn_slope[{obj}]: slope={slope:g}")
        for rep, score in sorted(det["gray_failure"].items()):
            print(f"  gray_failure[{rep}]: score={score:g}")
        if adoc["firings"]:
            for f in adoc["firings"]:
                print(f"  fired @ tick {f['tick']}: {f['detector']}"
                      f"[{f['key']}] value={f['value']:g} "
                      f"bound={f['bound']:g}")
        else:
            print("  (no firings)")
    # the process's compile log (obs.compiles): always on, so every
    # dump has one
    log = snapshot.get("compiles")
    if log is not None:
        print("== compiles ==")
        print(f"  trace_s={log['trace_s']:.3f} lower_s={log['lower_s']:.3f} "
              f"compile_s={log['compile_s']:.3f} all_s={log['all_s']:.3f} "
              f"traces={log['traces']} programs={log['programs']} "
              f"cache_hits={log['cache_hits']} "
              f"cache_misses={log['cache_misses']} "
              f"cache_retrieval_s={log['cache_retrieval_s']:.3f} "
              f"time_saved_s={log['time_saved_s']:.3f} "
              f"dropped={log['dropped']}")
        for row in log["by_function"]:
            print(f"  {row['function']} [{row['kind']}]: "
                  f"n={row['count']} total_s={row['seconds']:.3f}")
    print("== spans ==")
    agg: dict[str, list[float]] = {}
    for e in events:
        agg.setdefault(e["name"], []).append(e["dur_us"])
    for name in sorted(agg):
        durs = agg[name]
        print(f"  {name}: n={len(durs)} total_ms="
              f"{sum(durs) / 1e3:.3f} mean_us={sum(durs) / len(durs):.1f}")
    if device:
        from attention_tpu.utils.profiling import device_module_seconds

        mods = device_module_seconds(device)
        print("== device modules ==")
        if mods:
            for name, sec in sorted(mods.items(), key=lambda kv: -kv[1]):
                print(f"  {name}: {sec * 1e3:.3f} ms")
        else:
            print("  (no parsable device lane)")
    return 0


def _cmd_obs_export(args: argparse.Namespace) -> int:
    import json

    from attention_tpu import obs

    snapshot, events, _device = _obs_load(args)
    if args.format == "prom":
        text = obs.prom_text(snapshot)
    elif args.format == "jsonl":
        text = "\n".join(obs.jsonl_lines(events, snapshot))
        text += "\n" if text else ""
    else:  # chrome
        text = json.dumps(obs.chrome_trace(events))
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        _logger.info("wrote %s export: %s", args.format, args.out)
    else:
        sys.stdout.write(text)
    return 0


def _cmd_obs_trace(args: argparse.Namespace) -> int:
    """Per-request journey report (obs.trace): ``--request ID`` prints
    one chain event by event; without it, one summary line per chain.
    Reads ``<run>/traces.jsonl`` from a dump, else the live store."""
    from attention_tpu import obs
    from attention_tpu.obs import trace as trace_mod

    chains = (obs.load_traces(args.run) if args.run
              else trace_mod.all_traces())
    if args.request is not None:
        evs = chains.get(args.request)
        if not evs:
            print(f"no trace recorded for request {args.request!r}",
                  file=sys.stderr)
            return 1
        for line in trace_mod.journey_lines(args.request, evs):
            print(line)
        return 0
    for rid in sorted(chains):
        evs = chains[rid]
        term = trace_mod.terminal_of(evs)
        print(f"{rid}: {len(evs)} events, "
              f"terminal={term or 'none (in flight)'}")
    return 0


def _cmd_obs_slo(args: argparse.Namespace) -> int:
    """Print a run's SLO report (obs.slo) in its canonical JSON form —
    byte-identical across same-seed runs, which is the property the
    acceptance test pins."""
    import json

    from attention_tpu import obs

    if not args.run:
        print("obs slo requires --run "
              "(a `serve-sim --obs-out` directory)", file=sys.stderr)
        return 1
    report = obs.load_slo(args.run)
    if report is None:
        print(f"no slo.json under {args.run} (was serve-sim run "
              "with --replicas and --obs-out?)", file=sys.stderr)
        return 1
    print(json.dumps(report, indent=1, sort_keys=True))
    return 0


def _cmd_obs_forecast(args: argparse.Namespace) -> int:
    """Print a run's forecast + capacity report (obs.forecast /
    obs.capacity) in its canonical JSON form.  Without ``--horizon``
    this is byte-identical to the committed forecast.json (same-seed
    determinism, the pinned property); with it, the report is rebuilt
    from the dump's embedded samples at the requested horizon."""
    import json

    from attention_tpu import obs
    from attention_tpu.obs import capacity as capacity_mod

    if not args.run:
        print("obs forecast requires --run "
              "(a `serve-sim --obs-out` directory)", file=sys.stderr)
        return 1
    doc = obs.load_forecast(args.run)
    if doc is None:
        print(f"no forecast.json under {args.run} (was serve-sim run "
              "with --replicas and --forecast and --obs-out?)",
              file=sys.stderr)
        return 1
    if args.horizon is not None:
        doc = capacity_mod.rebuild_report(doc, horizon=args.horizon)
    print(json.dumps(doc, indent=1, sort_keys=True))
    return 0


def _cmd_obs_postmortem(args: argparse.Namespace) -> int:
    """Reconstruct every incident bundle under ``--run`` into a
    cross-replica causal timeline: alarm, correlated trigger events,
    then the ring slice in coordinate order.  Byte-deterministic from
    the bundles alone — same-seed runs print identical reports.  With
    ``--chrome OUT`` also writes a chrome trace whose incident lane
    (pid 4) sits beside the request lanes."""
    import json

    from attention_tpu.obs import postmortem as pm_mod

    if not args.run:
        print("obs postmortem requires --run (an incident directory "
              "written via --incident-dir or a chaos campaign)",
              file=sys.stderr)
        return 1
    bundles = pm_mod.list_incidents(args.run)
    if not bundles:
        print(f"no incident bundles under {args.run}", file=sys.stderr)
        return 1
    print("\n".join(pm_mod.report_lines(args.run)))
    if args.chrome:
        from attention_tpu import obs

        loaded = [pm_mod.load_incident(b) for b in bundles]
        with open(args.chrome, "w") as f:
            json.dump(obs.chrome_trace([], incidents=loaded), f)
        _logger.info("wrote incident chrome trace: %s", args.chrome)
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="attention-tpu", description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="run a testcase and verify (reference main())")
    run.add_argument("testcase")
    run.add_argument("--backend", default="flash")
    run.add_argument("--dtype", choices=["bf16", "f32", "f64"], default="f32")
    run.add_argument("--repeats", type=int, default=1,
                     help="min-over-repeats timing (reference methodology)")
    run.add_argument("--no-verify", action="store_true")
    run.add_argument("--stats", action="store_true",
                     help="append a full-scan statistics line "
                          "(max-abs-error, mismatch count) after the "
                          "frozen verdict lines")
    run.set_defaults(fn=_cmd_run)

    gen = sub.add_parser("generate", help="write a random testcase + oracle output")
    gen.add_argument("out")
    gen.add_argument("--m", type=int, required=True)
    gen.add_argument("--n", type=int, required=True)
    gen.add_argument("--dk", type=int, required=True)
    gen.add_argument("--dv", type=int, required=True)
    gen.add_argument("--seed", type=int, default=0)
    gen.set_defaults(fn=_cmd_generate)

    suite = sub.add_parser("suite", help="write the simple..scale5 ladder")
    suite.add_argument("out_dir")
    suite.add_argument("--seed", type=int, default=0)
    suite.set_defaults(fn=_cmd_suite)

    be = sub.add_parser("backends", help="list available backends")
    be.set_defaults(fn=_cmd_backends)

    ss = sub.add_parser(
        "serve-sim",
        help="continuous-batching engine on a synthetic or JSON request "
             "trace (attention_tpu.engine); prints metrics JSON",
    )
    _add_serve_sim_args(ss)
    ss.set_defaults(fn=_cmd_serve_sim)

    tn = sub.add_parser(
        "tune",
        help="timed on-device kernel tile search; winners persist in "
             "the per-device tuning cache (see attention_tpu.tuning)",
    )
    tn.add_argument("--kernel", default="flash",
                    choices=["flash", "flash-bwd", "flash-bwd-fused",
                             "decode", "paged", "all"])
    tn.add_argument("--seq", type=int, default=32768,
                    help="sequence length (cache capacity for "
                         "decode/paged)")
    tn.add_argument("--dim", type=int, default=128)
    tn.add_argument("--heads", type=int, default=1)
    tn.add_argument("--kv-heads", type=int, default=None,
                    help="GQA KV heads (default: = --heads)")
    tn.add_argument("--batch", type=int, default=8,
                    help="batch size (decode/paged families)")
    tn.add_argument("--dtype", default="bfloat16")
    tn.add_argument("--causal", action="store_true")
    tn.add_argument("--stats", action="store_true",
                    help="tune the partials (stats-emitting) forward")
    tn.add_argument("--window", type=int, default=None)
    tn.add_argument("--sinks", type=int, default=None)
    tn.add_argument("--max-mode", default="bound",
                    choices=["online", "bound", "flashd", "amla", "auto"],
                    help="rescaling-math variant to measure; 'auto' "
                         "races every variant the family can lower and "
                         "records the winner in the cache entry")
    tn.add_argument("--repeats", type=int, default=3,
                    help="median-of-k timing repeats per candidate")
    tn.add_argument("--cache", default=None,
                    help="cache file to write (default: "
                         "$ATTN_TPU_TUNING_CACHE, else "
                         "~/.cache/attention_tpu/tuning_cache.json); "
                         "kernel dispatch reads it back only through "
                         "that variable")
    tn.add_argument("--dry-run", action="store_true",
                    help="search and report but write nothing")
    tn.set_defaults(fn=_cmd_tune)

    ch = sub.add_parser(
        "chaos",
        help="differential fuzzing + fault injection "
             "(attention_tpu.chaos): fuzz kernel configs against the "
             "fp64 oracle, shrink failures to .bin repros, storm the "
             "serving engine with seeded fault plans",
    )
    chsub = ch.add_subparsers(dest="chaos_cmd", required=True)

    cf = chsub.add_parser("fuzz", help="seeded differential fuzz "
                                       "campaign vs the tolerance ledger")
    cf.add_argument("--seed", type=int, default=0)
    cf.add_argument("--cases", type=int, default=16)
    cf.add_argument("--families", default=None,
                    help="comma-separated subset of "
                         "flash,decode,paged,int8,int4 (default: all)")
    cf.add_argument("--max-mode", default="online",
                    choices=["online", "bound", "flashd", "amla"],
                    help="pin the rescaling-math variant for families "
                         "that can lower it (per-variant oracle "
                         "campaigns; others keep online)")
    cf.add_argument("--inject-failure", action="store_true",
                    help="apply the synthetic defect to every kernel "
                         "output (pipeline self-test: forces failures)")
    cf.add_argument("--repro-dir", default=None,
                    help="write each failing config here as "
                         "repro-<i>.json")
    cf.set_defaults(fn=_cmd_chaos_fuzz)

    cr = chsub.add_parser("replay", help="re-run one repro "
                                         "(.json fuzz config or .bin "
                                         "testcase)")
    cr.add_argument("repro")
    cr.add_argument("--backend", default="flash",
                    help=".bin replay backend (any `cli backends` "
                         "name, e.g. chaos-broken)")
    cr.add_argument("--inject-failure", action="store_true")
    cr.set_defaults(fn=_cmd_chaos_replay)

    cs = chsub.add_parser("shrink", help="minimize a failing fuzz "
                                         "config; emit .json/.bin repro")
    cs.add_argument("repro", help="failing-config repro.json")
    cs.add_argument("--out", default=None,
                    help="write the minimal config JSON here")
    cs.add_argument("--bin", default=None,
                    help="write a .bin testcase here when the minimal "
                         "config is plain single-head attention")
    cs.add_argument("--inject-failure", action="store_true")
    cs.set_defaults(fn=_cmd_chaos_shrink)

    cfa = chsub.add_parser("faults", help="seeded fault-injection "
                                          "campaign against the "
                                          "serving engine")
    cfa.add_argument("--seed", type=int, default=0)
    cfa.add_argument("--plans", type=int, default=5)
    cfa.add_argument("--requests", type=int, default=5)
    cfa.add_argument("--events", type=int, default=4)
    cfa.add_argument("--replicas", type=int, default=1,
                     help="storm a --replicas N multi-replica front "
                          "end instead of a single engine (adds "
                          "replica_kill/restart fault kinds and the "
                          "no-request-lost invariant)")
    cfa.add_argument("--temperature", type=float, default=0.0)
    cfa.add_argument("--outputs", action="store_true",
                     help="include per-request token streams in the "
                          "report JSON")
    cfa.set_defaults(fn=_cmd_chaos_faults)

    sn = sub.add_parser(
        "snapshot",
        help="crash-consistency tooling (attention_tpu.engine."
             "snapshot): inspect / verify serve-sim snapshot files",
    )
    snsub = sn.add_subparsers(dest="snapshot_cmd", required=True)
    si = snsub.add_parser("inspect", help="print manifest + metadata "
                                          "JSON per snapshot")
    si.add_argument("path", help=".atpsnap file or a --snapshot-dir")
    si.set_defaults(fn=_cmd_snapshot_inspect)
    sv = snsub.add_parser("verify", help="check integrity (checksums, "
                                         "version, section table); "
                                         "exit 0 iff restorable")
    sv.add_argument("path", help=".atpsnap file or a --snapshot-dir")
    sv.set_defaults(fn=_cmd_snapshot_verify)

    an = sub.add_parser(
        "analyze",
        help="static analysis (attention_tpu.analysis): AST passes "
             "with stable ATP### codes over the whole tree; exit 0 "
             "iff clean modulo analysis/baseline.json",
    )
    an.add_argument("paths", nargs="*",
                    help="specific files to lint (default: the whole "
                         "scanned tree)")
    an.add_argument("--changed", action="store_true",
                    help="lint only files touched since "
                         "`git merge-base HEAD --base` (plus "
                         "staged/unstaged/untracked changes, plus the "
                         "call-graph reverse closure: files whose "
                         "callers changed); an edit under "
                         "attention_tpu/analysis/ escalates to a "
                         "full tree run")
    an.add_argument("--timings", action="store_true",
                    help="print per-pass wall time to stderr (the "
                         "tree-wide budget is <= 5 s)")
    an.add_argument("--base", default="main",
                    help="merge-base ref for --changed (default: main)")
    an.add_argument("--format",
                    choices=["text", "json", "sarif", "github"],
                    default="text",
                    help="report renderer; 'github' emits workflow-"
                         "command annotations (::error file=...)")
    an.add_argument("--baseline", default=None,
                    help="baseline file (default: "
                         "attention_tpu/analysis/baseline.json)")
    an.add_argument("--no-baseline", action="store_true",
                    help="report every finding, accepted or not")
    an.add_argument("--out", default=None,
                    help="write the report here instead of stdout")
    an.add_argument("--list-codes", action="store_true",
                    help="print the ATP### rule table and exit")
    an.set_defaults(fn=_cmd_analyze)

    ob = sub.add_parser(
        "obs",
        help="unified telemetry (attention_tpu.obs): report / export a "
             "run's counters, spans, and host timeline",
    )
    obsub = ob.add_subparsers(dest="obs_cmd", required=True)
    for name, fn in (("report", _cmd_obs_report),
                     ("export", _cmd_obs_export),
                     ("trace", _cmd_obs_trace),
                     ("slo", _cmd_obs_slo),
                     ("forecast", _cmd_obs_forecast),
                     ("postmortem", _cmd_obs_postmortem)):
        sp = obsub.add_parser(name)
        sp.add_argument("--run", default=None,
                        help="telemetry dump directory written by "
                             "`serve-sim --obs-out` (default: the live "
                             "in-process registry); for postmortem, "
                             "the incident directory")
        sp.add_argument("--device-trace", default=None,
                        help="jax.profiler trace dir for the device "
                             "lane (default: <run>/device if present)")
        if name == "export":
            sp.add_argument("--format",
                            choices=["chrome", "prom", "jsonl"],
                            default="chrome")
            sp.add_argument("--out", default=None,
                            help="write here instead of stdout")
        if name == "trace":
            sp.add_argument("--request", default=None,
                            help="print the full journey of one "
                                 "request id (default: list every "
                                 "chain, one line each)")
        if name == "forecast":
            sp.add_argument("--horizon", type=int, default=None,
                            help="rebuild the report from the dump's "
                                 "embedded samples at this horizon "
                                 "(default: print the dump verbatim)")
        if name == "postmortem":
            sp.add_argument("--chrome", default=None,
                            help="also write a chrome trace with the "
                                 "incident lane (pid 4) here")
        sp.set_defaults(fn=fn)

    _setup_logging()
    args = parser.parse_args(argv)
    from attention_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
