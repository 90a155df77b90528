"""The chip's idle time by the engine step's phase.

The program marks the phases of `ServingEngine.step` with profiler
annotations (`attention_tpu.obs.span`): ``engine.step`` around the whole
step and, inside it, ``engine.step.schedule``, ``.pack``, ``.upload``,
``.dispatch``, ``.fetch`` and ``.sample``, all on the thread that runs
the step loop and on the clock of the device's ``XLA Ops`` lane.

A phase's *exposed* time is the part of chip 0's idle intervals that
lies under the spans of that phase: the time the chip stood still
because of it.  A gap that crosses a span's boundary is split there.
It is what moves the end-to-end metric: a phase that asynchronous steps
later hide behind device work keeps its duration and loses its exposed
time.  Every number is per busy step: an ``engine.step`` span of the
slice that holds an ``engine.step.dispatch``.

A program without these spans (one that predates them) gives ``None``
everywhere, so the metrics are left out of the line, never 0.
"""

from __future__ import annotations

import bisect

from benchmark.reduce import trace

STEP = "engine.step"
PHASES = ("schedule", "pack", "upload", "dispatch", "fetch", "sample")
MARK = "bench.traced"      # the slice's own span: names the loop's thread


def overlap(a, b) -> float:
    """Seconds in which two merged, sorted interval lists are both on."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def breakdown(events, plane: str, window) -> dict | None:
    """Chip ``plane``'s idle seconds inside ``window`` by what the step
    loop's thread was in: each phase, the rest of ``engine.step`` (its
    self time) and everything outside a step; beside them each phase's
    summed duration.  ``None`` where the loop's thread has no
    ``engine.step`` span."""
    loop = {e.line for e in events
            if e.plane == trace.HOST_PLANE and e.name == MARK}
    by_name: dict[str, list] = {}
    for e in events:
        if (e.plane == trace.HOST_PLANE and e.line in loop
                and (e.name == STEP or e.name.startswith(STEP + ".")
                     or e.name == "bench.step")):
            by_name.setdefault(e.name, []).append(e)
    steps = sorted(by_name.get(STEP, []), key=lambda e: e.start)
    if not steps:
        return None
    starts = [e.start for e in steps]

    def step_of(e) -> int | None:
        """Index of the ``engine.step`` span that holds ``e``."""
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start + e.dur <= steps[i].start + steps[i].dur:
            return i
        return None

    busy = {step_of(e) for e in by_name.get(STEP + ".dispatch", [])}
    busy.discard(None)
    idle = trace.idle_gaps(events, plane, window)
    in_steps = trace.union(trace.intervals(steps))
    exposed, duration, covered = {}, {}, []
    for phase in PHASES:
        # a phase whose step began before the slice has no parent here
        spans = [e for e in by_name.get(f"{STEP}.{phase}", [])
                 if step_of(e) is not None]
        if not spans:
            continue
        merged = trace.union(trace.intervals(spans))
        covered.extend(merged)
        exposed[phase] = overlap(idle, merged)
        duration[phase] = sum(e.dur for e in spans)
    bench = by_name.get("bench.step", [])
    return {
        "steps": len(steps), "busy_steps": len(busy),
        "step_s": sum(e.dur for e in steps),
        "bench_steps": len(bench), "bench_step_s": sum(e.dur for e in bench),
        "idle_s": trace.total(idle),
        "exposed_s": exposed, "duration_s": duration,
        "self_s": overlap(idle, trace.subtract(in_steps,
                                               trace.union(covered))),
        "outside_s": overlap(idle, trace.subtract([window], in_steps)),
    }


def describe(b: dict) -> str:
    """The one line a traced serving run prints: per busy step, each
    phase's duration and the chip's idle time under it, then the sum
    that has to come out as the chip's idle seconds in the slice."""
    n = max(b["busy_steps"], 1)
    phases = ", ".join(
        f"{p} {1e3 * b['duration_s'][p] / n:.3f} / "
        f"{1e3 * b['exposed_s'][p] / n:.3f}" for p in b["exposed_s"])
    step_ms = 1e3 * b["step_s"] / b["steps"]
    bench_ms = (1e3 * b["bench_step_s"] / b["bench_steps"]
                if b["bench_steps"] else float("nan"))
    self_ms = 1e3 * (b["step_s"] - sum(b["duration_s"].values())) / n
    under = sum(b["exposed_s"].values())
    return (
        f"phases: {b['steps']} engine.step spans of {step_ms:.3f} ms "
        f"({b['busy_steps']} busy; {b['bench_steps']} bench.step of "
        f"{bench_ms:.3f} ms); per busy step, duration / chip idle in ms: "
        f"{phases}, self {self_ms:.3f} / {1e3 * b['self_s'] / n:.3f}; "
        f"chip idle {b['idle_s']:.4f} s = phases {under:.4f} + self "
        f"{b['self_s']:.4f} + outside any step {b['outside_s']:.4f}")


def of_run(ctx) -> dict | None:
    """The traced slice's breakdown, worked out and printed once a run."""
    if "_phases" not in ctx:
        ctx["_phases"] = breakdown(ctx["events"], ctx["planes"][0],
                                   ctx["trace_window"])
        if ctx["_phases"] is not None:
            print(describe(ctx["_phases"]))
    return ctx["_phases"]


def exposed_ms_per_step(ctx, phase: str):
    """Milliseconds a busy step left chip 0 idle under ``phase``."""
    b = of_run(ctx)
    if b is None or not b["busy_steps"] or phase not in b["exposed_s"]:
        return None
    return 1e3 * b["exposed_s"][phase] / b["busy_steps"]


def exposed_schedule_ms_per_step(ctx):
    return exposed_ms_per_step(ctx, "schedule")


def exposed_pack_ms_per_step(ctx):
    return exposed_ms_per_step(ctx, "pack")


def exposed_upload_ms_per_step(ctx):
    return exposed_ms_per_step(ctx, "upload")


def exposed_dispatch_ms_per_step(ctx):
    return exposed_ms_per_step(ctx, "dispatch")


def exposed_fetch_ms_per_step(ctx):
    return exposed_ms_per_step(ctx, "fetch")


def exposed_sample_ms_per_step(ctx):
    return exposed_ms_per_step(ctx, "sample")
