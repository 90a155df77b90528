"""Bound-mode guard cost breakdown on the real chip.

Round 5 shipped the runtime overshoot guard (`_bound_overshoot_estimate`
+ `lax.cond` self-demotion).  The end-of-round ladder shows its cost is
FLAT (~30 us), which is 16% of the small single_chip_8k kernel (0.816
util guarded vs 0.946 unguarded) but only ~1.2% of the 32k headline.
This experiment decomposes that flat cost to decide where (if anywhere)
it can be cut without weakening the guarantee:

  * t(online) / t(bound unguarded) / t(bound guarded) per shape — how
    much the guard costs end-to-end, and whether the online kernel would
    simply be faster than guarded-bound at small shapes (in which case a
    static size-based resolution, like the round-5 windowed one, wins);
  * t(guard expression alone, jitted) — the XLA-fused reduction cost;
  * t(knmax alone) — the part the bound kernel needs as an input anyway.

Interleaved trials, deterministic device clock, medians.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _operands(seq, dim, causal, key=0):
    import jax
    import jax.numpy as jnp

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(kq, (seq, dim), jnp.bfloat16)
    k = jax.random.normal(kk, (seq, dim), jnp.bfloat16)
    v = jax.random.normal(kv, (seq, dim), jnp.bfloat16)
    return q, k, v


def bench_mode(seq, dim, causal, max_mode, repeats, n_long, unsafe=False,
               trivial_pred=False, guard_impl="cond"):
    import attention_tpu.ops.flash as F
    from attention_tpu.utils.timing import benchmark_auto

    import jax

    q, k, v = _operands(seq, dim, causal)
    step = lambda x, kk_, vv_: F.flash_attention(  # noqa: E731
        x, kk_, vv_, causal=causal, max_mode=max_mode)
    if guard_impl != "cond":
        # the in-kernel dynamic-mode implementation was REVERTED after
        # measuring 359 us vs 214 at 8k (see the decision comment at
        # the cond dispatch in ops/flash.py);
        # without it, setting the flag would silently re-measure the
        # cond path under the wrong label.  Probe the SOURCE for the
        # dispatch (a hasattr check is defeated by this script's own
        # earlier arms creating the attribute).
        import inspect

        # match the dispatch CODE, not comment prose mentioning the
        # experiment (a decision comment citing 'inkernel' must not
        # re-enable the arm)
        if '_GUARD_IMPL == "inkernel"' not in inspect.getsource(
                F._flash_call):
            return None
    old = F._UNSAFE_SKIP_GUARD
    old_impl = getattr(F, "_GUARD_IMPL", "cond")
    old_est = F._bound_overshoot_estimate
    old_min = F._BOUND_MIN_SCORE_ELEMS
    # this experiment studies the KERNELS; production's small-shape
    # bound->online resolution would make 2k/4k arms measure the
    # online kernel under the bound label
    F._BOUND_MIN_SCORE_ELEMS = 0
    F._UNSAFE_SKIP_GUARD = unsafe
    F._GUARD_IMPL = guard_impl
    if trivial_pred:
        # isolate the lax.cond structure cost: a data-dependent (not
        # constant-foldable) predicate whose computation is ~free
        F._bound_overshoot_estimate = (
            lambda q_, k_, knmax, *a, **kw: 0.0 * knmax[0])
    # the flag is read at trace time; a cached jit of the same static
    # args would silently reuse the other mode's trace
    jax.clear_caches()
    try:
        return benchmark_auto(step, q, repeats=repeats, n_long=n_long,
                              operands=(k, v))
    finally:
        F._UNSAFE_SKIP_GUARD = old
        F._GUARD_IMPL = old_impl
        F._bound_overshoot_estimate = old_est
        F._BOUND_MIN_SCORE_ELEMS = old_min
        jax.clear_caches()


def bench_guard_expr(seq, dim, causal, repeats):
    """Time the jitted guard expression alone (knmax + estimate)."""
    import jax
    import jax.numpy as jnp

    import attention_tpu.ops.flash as F
    from attention_tpu.utils.timing import benchmark_auto

    q, k, _ = _operands(seq, dim, causal)
    scale = 1.0 / (dim ** 0.5)

    # the chained clock feeds fn's output back as the carry, so return
    # q plus a vanishing data-dependent term (distribution-stationary)
    def guard(qq, kk_):
        q2 = (qq.astype(jnp.float32) * (scale * 1.4426950408889634))[None]
        k2 = kk_[None]
        k32 = k2.astype(jnp.float32)
        knmax = jnp.max(jnp.sqrt(jnp.sum(k32 * k32, axis=-1)), axis=-1)
        offsets = jnp.stack([jnp.int32(0), jnp.int32(0), jnp.int32(seq)])
        est = F._bound_overshoot_estimate(
            q2, k2, knmax, offsets, m=seq, n=seq, group=1, causal=causal,
            window=None, sinks=None, softcap2=None, q_segment_ids=None,
            kv_segment_ids=None, static_diag=causal)
        return qq + 1e-30 * est.astype(qq.dtype)

    def knmax_only(qq, kk_):
        k32 = kk_.astype(jnp.float32)
        knmax = jnp.max(jnp.sqrt(jnp.sum(k32 * k32, axis=-1)))
        return qq + 1e-30 * knmax.astype(qq.dtype)

    t_guard = benchmark_auto(guard, q, repeats=repeats, n_long=64,
                             operands=(k,))
    t_knmax = benchmark_auto(knmax_only, q, repeats=repeats, n_long=64,
                             operands=(k,))
    return t_guard, t_knmax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="+",
                    default=[4096, 8192, 16384, 32768])
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args()

    rows = []
    for seq in args.seqs:
        n_long = max(8, min(64, (32768 // seq) * 8))
        med = {}
        for label, mode, unsafe, trivial, impl in (
            ("online", "online", False, False, "cond"),
            ("bound_guarded", "bound", False, False, "cond"),
            ("bound_unguarded", "bound", True, False, "cond"),
            ("bound_trivial_cond", "bound", False, True, "cond"),
            ("bound_inkernel", "bound", False, False, "inkernel"),
        ):
            ts = [bench_mode(seq, args.dim, args.causal, mode,
                             args.repeats, n_long, unsafe,
                             trivial_pred=trivial, guard_impl=impl)
                  for _ in range(args.trials)]
            if ts[0] is None:
                continue  # arm's implementation not present (see note)
            med[label] = statistics.median(ts)
        tg, tk = bench_guard_expr(seq, args.dim, args.causal, args.repeats)
        row = {
            "seq": seq, "dim": args.dim, "causal": args.causal,
            **{k2: v * 1e6 for k2, v in med.items()},
            "guard_expr_us": tg * 1e6,
            "knmax_only_us": tk * 1e6,
            "guard_overhead_us":
                (med["bound_guarded"] - med["bound_unguarded"]) * 1e6,
        }
        rows.append(row)
        print(json.dumps(row))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
