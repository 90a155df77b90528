"""The plain reference of the ``nemotron-3-super-120b`` configuration:
the decoder's forward pass in straightforward `jax.numpy` and float32
at the highest matmul precision, with no kernels, no cache, no
batching, no chunking and no sorting: the state-space recurrence is a
`lax.scan` over tokens, the expert layer a dense loop over the experts
held here.  Imports nothing of the program; it reads the parameter
tree by the names the program serves it under.

``config.json`` of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
(``model_type`` nemotron_h) gives the sizes and the pattern; what it
does not give is the family's convention, listed in the configuration's
file under ``assumed``.  RMSNorm (eps ``norm_eps``) everywhere, no bias
except the convolution's, a final RMSNorm and an untied head.  Every
layer is ONE sublayer under one residual, ``x <- x + f(RMSNorm(x))``,
``f`` by the letter of ``hybrid_override_pattern``:

* ``*`` attention: GQA, ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``, causal, full, NO
  rotary (the family's attention layers carry no positional embedding;
  the state-space layers carry position).
* ``M`` Mamba-2 (arXiv:2405.21060): H = ``mamba_num_heads`` heads of P =
  ``mamba_head_dim``, state N = ``ssm_state_size``, G = ``n_groups``.
  ``[z | xBC | dt] = W_in x`` (H P | H P + 2 G N | H); ``xBC <-
  silu(conv(xBC) + b)``, depthwise and causal over ``conv_kernel``
  taps; ``xBC -> x_h (H x P), B_g, C_g (G x N)``, head ``h`` reads
  group ``h // (H / G)``; ``dt_h = softplus(dt_h + dt_bias_h)``, ``a_h
  = exp(-dt_h exp(A_log_h))``; per head the state ``S`` (P x N) in
  float32: ``S_t = a_t S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t +
  D_h x_t``; ``y <- RMSNorm_groups(y * silu(z))`` over G groups of H P
  / G channels with one scale vector; ``out = W_out y``.
* ``E`` latent sparse experts: ``s = sigmoid(W_r x)`` in float32 over
  all the experts of the deployment; the ``num_experts_per_tok``
  experts with the largest ``s + b`` (``b`` the selection bias); ``g_i
  = routed_scaling_factor s_i / sum_chosen s``; ``u = W_dn x`` (hidden
  -> ``moe_latent_size``); ``r = sum_i g_i W2_i relu(W1_i u)^2``
  (latent -> ``moe_intermediate_size`` -> latent); ``out = W_up r +
  W2s relu(W1s x)^2`` (the shared expert at
  ``moe_shared_expert_intermediate_size``, on the full-width ``x``).
  THE SHARE: this chip holds experts ``[held k, held (k + 1))``
  (``n_routed_experts`` = held, ``expert_share`` = ``{index: k, of:
  n}``); ``s``, the choice and ``g`` are over all ``held n`` experts,
  ``r`` sums only the chosen experts held here, and what the others
  would add is left out.

The vocabulary is the slice the file states (``vocab_size``).
Multi-token prediction is not built (the main head's logits do not
depend on it).  Departures from the source: the configuration's
``reduced`` and ``assumed``, and weights drawn from a seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


#: ``rescale_prenorm_residual`` (true in the source): the projections
#: that write into the residual stream are scaled by 1 / sqrt(the
#: number of residual layers), the published depth whatever the cut
RESIDUAL_LAYERS = 88
RESIDUAL_WRITERS = ("out_proj", "o_proj", "latent_up", "down_proj")


def init_params(shapes, key):
    """Seeded weights for the tree of shapes the program's model
    declares, in the float32 the program stores: norm scales and ``D``
    1; the router's selection bias 0; ``A_log`` the log of a uniform in
    [1, 16) and ``dt_bias`` the inverse softplus of a log-uniform in
    [``time_step_min``, ``time_step_max``) = [1e-3, 1e-1) (the family's
    initialisation; the floor of 1e-4 lies under the range); the
    convolution's bias normal with standard deviation 0.1; the
    embedding normal with standard deviation 1, a residual stream of
    unit scale; every other leaf normal with standard deviation
    1/sqrt(fan_in) (the input axis is the first, the second for the
    experts' stacked kernels; the taps, for a convolution), and the
    projections that WRITE into the residual stream (`RESIDUAL_WRITERS`)
    scaled by 1/sqrt(`RESIDUAL_LAYERS`) besides: the source's
    ``rescale_prenorm_residual``.  So a sublayer adds about a tenth of
    the stream's scale, as in a trained pre-norm decoder, and not all
    of it: with an embedding of 1/sqrt(width) the stream IS the
    sublayers' outputs, and one top-22 choice that flips at the margin
    moves the logits as far as a precision lost everywhere (PERF.md
    section 6).  Made on the device, to be called under one `jax.jit`."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        if name.endswith("['scale']") or name.endswith("['D']"):
            out.append(jnp.ones(leaf.shape, leaf.dtype))
        elif name.endswith("['router_bias']"):
            out.append(jnp.zeros(leaf.shape, leaf.dtype))
        elif name.endswith("['A_log']"):
            out.append(jnp.log(jax.random.uniform(
                k, leaf.shape, leaf.dtype, 1.0, 16.0)))
        elif name.endswith("['dt_bias']"):
            dt = jnp.exp(jax.random.uniform(
                k, leaf.shape, leaf.dtype, np.log(1e-3), np.log(1e-1)))
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        elif name.endswith("['conv_bias']"):
            out.append(0.1 * jax.random.normal(k, leaf.shape, leaf.dtype))
        elif "embedding" in name:
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype))
        else:
            fan_in = leaf.shape[1 if "['experts_" in name else 0]
            std = fan_in ** -0.5
            if any(f"['{w}']" in name for w in RESIDUAL_WRITERS):
                std *= RESIDUAL_LAYERS ** -0.5
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype) * std)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes), out)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _identity(x):
    return x


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _attention(p, x, *, heads, kv_heads, quant):
    """Causal grouped-query attention on ``x`` (S, dim), no rotary."""
    s = x.shape[0]

    def proj(name):
        return jnp.einsum("sd,dhk->hsk", quant(x), quant(p[name]["kernel"]))

    q, k, v = proj("q_proj"), proj("k_proj"), proj("v_proj")
    group = heads // kv_heads
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]

    def one_head(h):  # a head at a time: (S, S) scores fit the chip
        kv = h // group
        scores = jnp.where(causal, (q[h] @ k[kv].T) * (q.shape[-1] ** -0.5),
                           -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[kv]

    out = jax.lax.map(one_head, jnp.arange(heads))
    out = out.transpose(1, 0, 2).reshape(s, -1)
    return quant(out) @ quant(p["o_proj"]["kernel"])


def _state_space(p, x, *, heads, head_dim, state, groups, eps, quant, keep):
    """Mamba-2 on ``x`` (S, dim), from a zero state, token by token.
    ``keep`` is how the state is kept from one token to the next (as it
    is in the reference, rounded to bfloat16 in the second control)."""
    s = x.shape[0]
    inner = heads * head_dim
    proj = quant(x) @ quant(p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * groups * state],
                           axis=-1)
    w = p["conv_weight"]                     # (taps, channels), newest last
    taps = w.shape[0]
    pad = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(pad[i:i + s] * w[i] for i in range(taps))
                      + p["conv_bias"])
    xs, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    xs = xs.reshape(s, heads, head_dim)
    per = heads // groups
    b = jnp.repeat(b.reshape(s, groups, state), per, axis=1)   # (S, H, N)
    c = jnp.repeat(c.reshape(s, groups, state), per, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # (S, H)
    a = jnp.exp(-dt * jnp.exp(p["A_log"]))

    def step(st, t):
        xt, bt, ct, at, dtt = t
        st = keep(st * at[:, None, None]
                  + (xt * dtt[:, None])[:, :, None] * bt[:, None, :])
        return st, jnp.einsum("hpn,hn->hp", st, ct)

    _, y = jax.lax.scan(step, jnp.zeros((heads, head_dim, state)),
                        (xs, b, c, a, dt))
    y = (y + p["D"][:, None] * xs).reshape(s, inner) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(s, groups, inner // groups),
                  p["norm"]["scale"], eps).reshape(s, inner)
    return quant(y) @ quant(p["out_proj"]["kernel"])


def _experts(p, x, *, share, top_k, scale, quant):
    """The latent sparse experts on ``x`` (S, dim): the router over all
    the deployment's experts in float32, a dense loop over the experts
    held here (share ``share``), the shared expert."""
    held = p["experts_up"].shape[0]
    scores = jax.nn.sigmoid(x @ p["router"])                   # (S, all)
    _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
    gate = jnp.take_along_axis(scores, chosen, axis=-1)
    gate = scale * gate / jnp.sum(gate, axis=-1, keepdims=True)
    u = quant(x) @ quant(p["latent_down"]["kernel"])

    def one_expert(r, e):
        mine = jnp.sum(jnp.where(chosen == share * held + e, gate, 0.0),
                       axis=-1)
        w1 = jax.lax.dynamic_index_in_dim(p["experts_up"], e, keepdims=False)
        w2 = jax.lax.dynamic_index_in_dim(p["experts_down"], e,
                                          keepdims=False)
        y = quant(_relu2(quant(u) @ quant(w1))) @ quant(w2)
        return r + mine[:, None] * y, None

    r, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(held))
    out = quant(r) @ quant(p["latent_up"]["kernel"])
    if "shared_expert" in p:
        out = out + _feed_forward(p["shared_expert"], x, quant=quant)
    return out, chosen


def _feed_forward(p, x, *, quant):
    h = _relu2(quant(x) @ quant(p["up_proj"]["kernel"]))
    return quant(h) @ quant(p["down_proj"]["kernel"])


def _block(p, x, *, sizes, quant, keep):
    """One block on ``x`` (S, dim): the sublayer its parameters name.
    ``quant`` rounds the two operands of every weight matmul but the
    float32 router's (identity in the reference, fp8 in the first
    control).  Returns the block's output and, for an expert layer,
    the experts its router chose (S, top_k), else None."""
    y = _rms_norm(x, p["RMSNorm_0"]["scale"], sizes["eps"])
    chosen = None
    if "Mamba2Mixer_0" in p:
        out = _state_space(
            p["Mamba2Mixer_0"], y, heads=sizes["ssm_heads"],
            head_dim=sizes["ssm_head_dim"], state=sizes["ssm_state"],
            groups=sizes["ssm_groups"], eps=sizes["eps"], quant=quant,
            keep=keep)
    elif "LatentExperts_0" in p:
        out, chosen = _experts(
            p["LatentExperts_0"], y, share=sizes["share"],
            top_k=sizes["top_k"], scale=sizes["scale"], quant=quant)
    else:
        out = _attention(p["GQASelfAttention_0"], y, heads=sizes["heads"],
                         kv_heads=sizes["kv_heads"], quant=quant)
    return x + out, chosen


@functools.partial(jax.jit, static_argnames=("sizes", "rows",
                                             "low_precision"))
def _forward(params, tokens, first, *, sizes, rows, low_precision):
    """Logits of ``rows`` positions from ``first``, and the experts
    each expert layer's router chose at every position."""
    quant = {True: fp8_round, "fp8": fp8_round,
             "bf16": bf16_round}.get(low_precision, _identity)
    keep = bf16_round if low_precision == "state_bf16" else _identity
    sizes = dict(sizes)
    x = params["Embed_0"]["embedding"][tokens]
    depth = sum(1 for name in params if name.startswith("SublayerBlock_"))
    routed = []
    for i in range(depth):
        x, chosen = _block(params[f"SublayerBlock_{i}"], x, sizes=sizes,
                           quant=quant, keep=keep)
        if chosen is not None:
            routed.append(chosen)
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    x = _rms_norm(x, params["RMSNorm_0"]["scale"], sizes["eps"])
    return quant(x) @ quant(params["Dense_0"]["kernel"]), tuple(routed)


def _sizes(config: dict) -> tuple:
    share = config.get("expert_share") or {"index": 0, "of": 1}
    return tuple(sorted({
        "eps": float(config["norm_eps"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "ssm_heads": int(config["mamba_num_heads"]),
        "ssm_head_dim": int(config["mamba_head_dim"]),
        "ssm_state": int(config["ssm_state_size"]),
        "ssm_groups": int(config["n_groups"]),
        "share": int(share["index"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
    }.items()))


def served_logits(params, config: dict, prompt, served, *, pad_to: int,
                  rows: int, low_precision: bool | str = False) -> np.ndarray:
    """Float32 logits at the positions that predict the ``served``
    tokens of one request: one pass over prompt + served tokens (causal
    in every layer, so the zero tail up to ``pad_to`` reaches nothing).
    ``rows`` >= len(served) is the static number of positions computed;
    the rows past the served ones are cut off.  ``low_precision`` picks
    a control: True or ``"fp8"`` rounds every weight matmul's operands
    to fp8, the step below the bf16 the configuration states for them
    (the router stays in the float32 it states; ``"bf16"`` rounds them
    to the bf16 it states, for `routing_flips`); ``"state_bf16"``
    rounds the recurrent state to bfloat16 after every token, the step
    below the float32 it states for the state."""
    seq = np.zeros((pad_to,), np.int32)
    real = list(prompt) + list(served[:-1])
    seq[:len(real)] = real
    with jax.default_matmul_precision("highest"):
        logits, _ = _forward(params, jnp.asarray(seq), len(prompt) - 1,
                             sizes=_sizes(config), rows=rows,
                             low_precision=low_precision)
    return np.asarray(logits, np.float64)[:len(served)]


def routing_flips(params, config: dict, tokens, *, pad_to: int) -> dict:
    """How often a router's choice flips at the margin when the weight
    matmuls' operands are rounded to bfloat16 (what the configuration
    states, and about what the program computes) against the float32
    pass, over the real ``tokens`` of one sequence: ``choices`` =
    positions x expert layers, ``flipped`` = those whose chosen SET
    differs, ``experts_swapped`` = experts in the float32 set and not
    in the other, ``held_swapped`` = of the experts that entered or
    left a set, those held here (each moves that expert's weighted
    part of the layer's result; a swap between two absent experts moves
    only the weights' normalisation)."""
    seq = np.zeros((pad_to,), np.int32)
    seq[:len(tokens)] = list(tokens)
    sizes = _sizes(config)
    with jax.default_matmul_precision("highest"):
        exact, low = (_forward(params, jnp.asarray(seq), 0, sizes=sizes,
                               rows=8, low_precision=p)[1]
                      for p in (False, "bf16"))
    held = int(config["n_routed_experts"])
    first = dict(sizes)["share"] * held
    out = {"choices": 0, "flipped": 0, "experts_swapped": 0,
           "held_swapped": 0}
    for a, b in zip(exact, low):
        a, b = np.asarray(a)[:len(tokens)], np.asarray(b)[:len(tokens)]
        gone = ~(a[:, :, None] == b[:, None, :]).any(axis=2)    # in a, not b
        came = ~(b[:, :, None] == a[:, None, :]).any(axis=2)
        local = lambda ids: (ids >= first) & (ids < first + held)  # noqa: E731
        out["choices"] += len(a)
        out["flipped"] += int(gone.any(axis=1).sum())
        out["experts_swapped"] += int(gone.sum())
        out["held_swapped"] += int((gone & local(a)).sum()
                                   + (came & local(b)).sum())
    return out


def token_gaps(logits: np.ndarray, tokens) -> np.ndarray:
    """Per position, how far the token's logit lies below the best."""
    picked = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return logits.max(axis=1) - picked


def widest_gap(logits: np.ndarray, tokens) -> float:
    """What the comparison keeps of ONE request: the MEAN, over its
    served tokens, of the gap by which the token's logit lies below the
    reference's best (`token_gaps`).  The harness takes the largest of
    these over the sampled requests and holds it to the traffic file's
    ``logit_gap_limit``; it asks every reference for this function
    under this name.

    Not the maximum, which the other configurations' references
    return.  This model's router picks 22 of 512 experts with little
    margin at the 22nd place, and a choice that flips under bf16
    activations moves a token's logits by one expert's weighted part:
    the LARGEST gap of a request is one such position whatever the
    precision of everything else, and read 0.08-0.17 for the program
    where the fp8 control read 0.18-0.25 (PERF.md section 6): no limit
    lay between.  A lower precision moves EVERY position a little, so
    it puts an order of magnitude more tokens off the best, and the
    mean over the request holds both how many and how far; the few
    flipped positions add their gap divided by the request's length.  A
    layer left out, or one served token far off (a gap of 2 in 1,000
    tokens), still reads over the limit."""
    return float(np.mean(token_gaps(logits, tokens)))


def bf16_round(x):
    """The second control's precision, for the recurrent state."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def fp8_round(x):
    """The first control's precision: float8 e4m3 under one scale per
    tensor, the step below the bf16 the configuration states."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
