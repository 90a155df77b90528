"""End-to-end training-step benchmark on the real chip (device clock).

The kernel benches measure attention in isolation; this measures what a
user of the framework actually runs: one full train step (forward loss,
backward through the Pallas flash VJP, adamw update) on a GQA decoder,
timed by device-side profiler module time (`benchmark_traced`'s
methodology).  Reports step time, tokens/s, and model-FLOPs utilization
(6 * params * tokens approximation + exact attention FLOPs).

Run: python scripts/train_bench.py [--dim 1024] [--depth 4] [--seq 8192]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--dim", type=int, default=1024)
    p.add_argument("--depth", type=int, default=4)
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--q-heads", type=int, default=16)
    p.add_argument("--kv-heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--steps-per-trace", type=int, default=4)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--remat", action="store_true",
                   help="rematerialize block activations (jax.checkpoint)"
                   " — the HBM-for-FLOPs trade that fits seq=32768")
    p.add_argument("--loss", choices=("full", "chunked"), default="full",
                   help="'chunked' re-projects the lm head per sequence "
                   "chunk under jax.checkpoint instead of materializing "
                   "the (B, S, vocab) fp32 logits")
    p.add_argument("--ce-chunk", type=int, default=2048)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from attention_tpu.models import TinyDecoder
    from attention_tpu.utils.flops import attention_flops, peak_flops
    from attention_tpu.utils.profiling import device_module_seconds, trace

    model = TinyDecoder(
        vocab=args.vocab, dim=args.dim, depth=args.depth,
        num_q_heads=args.q_heads, num_kv_heads=args.kv_heads,
        impl="flash", dtype=jnp.bfloat16, remat=args.remat,
    )
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, args.vocab,
                                          (args.batch, args.seq + 1)),
        jnp.int32,
    )
    params = model.init(jax.random.PRNGKey(0), toks[:, :8])["params"]
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    # the input embedding is a gather (zero matmul FLOPs) — exclude its
    # table from the 6ND numerator; the output head IS a matmul and
    # stays counted
    n_matmul_params = n_params - args.vocab * args.dim

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, toks):
        def loss_full(p):
            logits = model.apply({"params": p}, toks[:, :-1])
            lp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(
                jnp.take_along_axis(lp, toks[:, 1:, None], -1)
            )

        def loss_chunked(p):
            # (B, S, D) pre-head hidden; per-chunk head matmul + CE
            # under jax.checkpoint so the backward recomputes each
            # chunk's logits instead of saving the full (S, vocab) set
            hid = model.apply({"params": p}, toks[:, :-1],
                              return_hidden=True)
            w = p["Dense_0"]["kernel"]
            tgt = toks[:, 1:]
            b_, s_, d_ = hid.shape
            c = min(args.ce_chunk, s_)
            if s_ % c:
                raise ValueError(f"seq {s_} not divisible by chunk {c}")
            hidc = hid.reshape(b_, s_ // c, c, d_).transpose(1, 0, 2, 3)
            tgtc = tgt.reshape(b_, s_ // c, c).transpose(1, 0, 2)

            @jax.checkpoint
            def one(carry, xs):
                h, t = xs
                logits = jnp.einsum(
                    "bcd,dv->bcv", h.astype(jnp.float32),
                    w.astype(jnp.float32),
                )
                lp = jax.nn.log_softmax(logits)
                tok_lp = jnp.take_along_axis(lp, t[..., None], -1)
                return carry + jnp.sum(tok_lp), None

            tot, _ = jax.lax.scan(one, jnp.float32(0.0), (hidc, tgtc))
            return -tot / (b_ * s_)

        loss = loss_chunked if args.loss == "chunked" else loss_full
        l, g = jax.value_and_grad(loss)(params)
        up, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, up), opt_state, l

    # warm/compile, then N steps per trace capture (amortizes capture
    # edges), median over repeats
    params, opt_state, l = step(params, opt_state, toks)
    jax.block_until_ready(l)
    samples = []
    for r in range(args.repeats):
        log = f"/tmp/train_bench_{r}"
        shutil.rmtree(log, ignore_errors=True)
        with trace(log):
            for _ in range(args.steps_per_trace):
                params, opt_state, l = step(params, opt_state, toks)
            jax.device_get(l)
        mods = device_module_seconds(log)
        if not mods:
            print(json.dumps({"error": "no device trace lane"}))
            return 2
        samples.append(max(mods.values()) / args.steps_per_trace)
    sec = statistics.median(samples)

    tokens = args.batch * args.seq
    # 6ND for the dense weights + exact causal attention FLOPs x3
    # (fwd + ~2x bwd)
    attn_fl = 3 * args.depth * args.q_heads * attention_flops(
        args.seq, args.seq, args.dim // args.q_heads,
        args.dim // args.q_heads, causal=True,
    ) * args.batch
    flops = 6 * n_matmul_params * tokens + attn_fl
    print(json.dumps({
        "config": f"dim{args.dim} x{args.depth}L {args.q_heads}q"
                  f"{args.kv_heads}kv seq{args.seq} b{args.batch} bf16",
        "params_m": round(n_params / 1e6, 1),
        "step_ms": round(sec * 1e3, 2),
        "tokens_per_s": round(tokens / sec, 0),
        "model_flops_util": round(flops / sec / peak_flops(), 3),
        "final_loss": round(float(l), 3),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
