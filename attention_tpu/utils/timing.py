"""Wall-clock benchmarking with the reference's timing discipline.

The reference times the slowest rank (`MPI_Wtime` + `MPI_Reduce(MAX)`,
`attention-mpi.c:519-528`) and reports minimum-over-repeats execution time
(weak_scalability.png).  Under JAX's single-controller model a
``block_until_ready`` fence already waits for the slowest chip, so
"max over ranks" is implicit; we keep the min-over-repeats convention and
also report the median.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax


class Seconds(float):
    """Per-iteration seconds that say which clock produced them:
    ``"device-trace"`` (profiler device lane, `benchmark_traced`) or
    ``"wall-slope"`` (host wall-clock slope, `benchmark_amortized`).
    Arithmetic yields plain floats; reports read ``.clock``."""

    clock: str

    def __new__(cls, value: float, clock: str) -> "Seconds":
        obj = super().__new__(cls, value)
        obj.clock = clock
        return obj


@dataclasses.dataclass
class Timing:
    times_s: list[float]
    clock: str = "wall-fence"  # host clock around block_until_ready

    @property
    def best_s(self) -> float:  # min-over-repeats, the reference's metric
        return min(self.times_s)

    @property
    def median_s(self) -> float:
        s = sorted(self.times_s)
        return s[len(s) // 2]

    @property
    def best_us(self) -> float:
        return self.best_s * 1e6


def benchmark(
    fn: Callable,
    *args,
    repeats: int = 5,
    warmup: int = 2,
    **kwargs,
) -> Timing:
    """Time ``fn(*args)`` with compile warmup and device fencing.

    Warmup runs absorb jit compilation (first TPU compile is tens of
    seconds); each timed run fences with ``block_until_ready`` so the
    measurement covers every chip's work — the `MPI_Reduce(MAX)` analog.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    return Timing(times_s=times)


def _chained_scan(fn):
    """Jitted n-fold application of ``fn`` with a data dependency.

    Shared builder for the two chained clocks (:func:`benchmark_amortized`,
    :func:`benchmark_traced`): each iteration consumes the previous
    output (cast back to the input dtype), and the return value is one
    scalar so fetching it cannot be transfer-dominated.  Big side inputs
    must come through ``ops`` — closure-captured arrays become jaxpr
    constants and make lowering take minutes at hundreds of MB.
    """
    import functools

    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, static_argnums=2)
    def chained(x0, ops, n):
        def body(carry, _):
            return fn(carry, *ops).astype(x0.dtype), None

        out, _ = lax.scan(body, x0, None, length=n)
        return jnp.sum(out.astype(jnp.float32))

    return chained


def benchmark_amortized(
    fn: Callable,
    x,
    *,
    repeats: int = 3,
    n_short: int = 4,
    n_long: int = 20,
    operands: tuple = (),
    _chained=None,
) -> Seconds:
    """Per-iteration seconds of ``fn`` via scan-chained slope timing
    (clock ``"wall-slope"``).

    Chains ``n`` applications of ``fn`` inside one jit with a data
    dependency (each iteration consumes the previous output), fetches
    ONE scalar, and takes the slope (t_long - t_short)/(n_long -
    n_short) — per-call dispatch and fetch latency cancel, which a
    single fenced call cannot do for sub-millisecond ops.

    ``fn`` maps ``(x, *operands)`` to an array of x's shape; its output
    is cast back to ``x.dtype`` between iterations.  Pass big side
    inputs (K/V, caches) via ``operands``, NOT closure: closure-captured
    arrays are flattened into the jaxpr as constants, and at
    hundreds-of-MB that makes lowering/compilation take minutes.
    """
    chained = _chained if _chained is not None else _chained_scan(fn)
    jax.device_get(chained(x, operands, n_short))  # compile both lengths
    jax.device_get(chained(x, operands, n_long))
    slopes, longs = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.device_get(chained(x, operands, n_short))
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.device_get(chained(x, operands, n_long))
        t_long = time.perf_counter() - t0
        # Slope per back-to-back pair: the shared chip's contention
        # varies a lot between windows, and mixing a min(short) from one
        # window with a min(long) from another biases the difference —
        # observed producing impossible >100%-of-peak rates.  Each pair
        # sees similar conditions; the median across pairs is robust.
        slopes.append((t_long - t_short) / (n_long - n_short))
        longs.append(t_long)
    import statistics

    slope = statistics.median(slopes)
    if slope <= 0:
        # Timer noise swamped the slope (per-iteration cost << dispatch
        # jitter).  Fall back to the amortized upper bound — still honest,
        # just conservative: fixed overhead is charged to the iterations.
        slope = statistics.median(longs) / n_long
    return Seconds(slope, "wall-slope")


def benchmark_traced(
    fn: Callable,
    x,
    *,
    n: int = 20,
    operands: tuple = (),
    repeats: int = 3,
    _chained=None,
) -> Seconds | None:
    """Per-iteration seconds from DEVICE-side profiler time (clock
    ``"device-trace"``), or None.

    Chains ``n`` applications of ``fn`` (same contract as
    :func:`benchmark_amortized`), captures a ``jax.profiler`` trace, and
    sums the trace's "XLA Modules" device lane (shared parser:
    `utils.profiling.device_module_seconds`).  Device module time
    excludes host dispatch and fetch latency, where wall-clock sways
    with both — so this is the preferred clock when a device trace is
    available.  Returns the median over ``repeats`` captures, or None
    when the platform's profiler exports no device lane (e.g. CPU).
    """
    import shutil
    import statistics
    import tempfile

    from attention_tpu.utils.profiling import device_module_seconds

    chained = _chained if _chained is not None else _chained_scan(fn)
    jax.device_get(chained(x, operands, n))  # compile + warm

    def one_capture(log_dir) -> float | None:
        shutil.rmtree(log_dir, ignore_errors=True)
        with jax.profiler.trace(log_dir):
            jax.device_get(chained(x, operands, n))
        mods = device_module_seconds(log_dir)
        if not mods:
            return None
        # the chained scan dominates; stray scalar modules (the sum
        # fetch) are orders of magnitude smaller
        return max(mods.values()) / n

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as td:
        samples = []
        for i in range(repeats):
            sec = one_capture(f"{td}/{i}")
            if sec is None:
                return None
            samples.append(sec)
    return Seconds(statistics.median(samples), "device-trace")


def benchmark_candidate(
    fn: Callable,
    x,
    *,
    operands: tuple = (),
    repeats: int = 3,
) -> Seconds:
    """Per-iteration seconds for one AUTOTUNE candidate.

    The tuner's clock (`attention_tpu.tuning.search`): the same
    chained-scan measurement as :func:`benchmark_auto`
    (median-of-``repeats``), with deliberately short chains (2/8 vs the bench default
    4/20): a sweep compiles and times a dozen candidates per shape, so
    per-candidate wall time matters more than squeezing the last few
    percent of clock variance — rank order between tiles is far coarser
    than the short-chain noise floor.
    """
    return benchmark_auto(fn, x, operands=operands, repeats=repeats,
                          n_short=2, n_long=8)


def benchmark_auto(
    fn: Callable,
    x,
    *,
    operands: tuple = (),
    repeats: int = 3,
    n_short: int = 4,
    n_long: int = 20,
) -> Seconds:
    """Per-iteration seconds via the platform's clock; the result's
    ``.clock`` names it.

    Builds the chained-scan program ONCE.  On a TPU the clock is the
    device trace, and a capture with no device lane is an error — it
    means the profiler's output changed, and a quiet drop to the host
    clock would put a different quantity under the same name.  Other
    platforms export no device lane and use the wall-clock slope on the
    same compiled function.
    """
    chained = _chained_scan(fn)
    traced = benchmark_traced(fn, x, n=n_long, operands=operands,
                              repeats=max(1, repeats), _chained=chained)
    if traced is not None:
        return traced
    if jax.default_backend() == "tpu":
        from attention_tpu.utils.profiling import DEVICE_LANE

        raise RuntimeError(
            f"the profiler capture holds no {DEVICE_LANE!r} device lane "
            "on a TPU backend; refusing to substitute the host "
            "wall-clock slope for device time"
        )
    return benchmark_amortized(fn, x, repeats=repeats, n_short=n_short,
                               n_long=n_long, operands=operands,
                               _chained=chained)
