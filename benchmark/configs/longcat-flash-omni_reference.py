"""The plain reference of the ``longcat-flash-omni`` configuration: the
language model's forward pass in straightforward `jax.numpy` and
float32 at the highest matmul precision, with no kernels, no cache, no
batching and no chunking of the sequence: the EXPANDED latent attention
(per-head keys and values from ``W_kvb``, which the program's absorbed
form never makes), a loop over the experts held here.  Imports nothing
of the program; it reads the parameter tree by the names the program
serves it under, whatever dtype the leaves have (bfloat16 here: one
matrix at a time is taken to float32).

``config.json`` of meituan-longcat/LongCat-Flash-Omni gives the sizes;
what it does not give is listed in the configuration's file under
``assumed``.  RMSNorm at ``rms_norm_eps`` everywhere, no biases, a
final RMSNorm and an untied head.  One of the ``num_layers`` layers is
a DOUBLE layer:

    for i in (0, 1):
        x = x + MLA_i(N(x))
        y = N(x)
        if i == 0: m = Experts(y)           # from the FIRST half
        x = x + SwiGLU_i(y)                 # hidden -> ffn_hidden_size -> hidden
    x = x + m                               # after the SECOND half

``MLA`` (H heads; ``s_q`` = sqrt(hidden / q_lora_rank), ``s_kv`` =
sqrt(hidden / kv_lora_rank)):

    c_q = N(x W_qa) s_q ; [q_n | q_r]_h = c_q W_qb
    [c | k_r] = x W_kva ; c = N(c) s_kv          (k_r is not scaled)
    [k_n | v]_h = c W_kvb ; q_r, k_r = rope(.)   (rotate-half pairs, theta ``rope_theta``)
    p_h = causal softmax((q_n,h . k_n,h + q_r,h . k_r) / sqrt(nope + rope))
    out = concat_h(p_h v_h) W_o

``Experts`` (router in float32 over the real experts of the deployment
then ``zero_expert_num`` zero-compute experts):

    s = softmax(y W_r) ; chosen = top ``moe_topk`` of (s + b)
    g_i = routed_scaling_factor s_i                     (not normalised)
    m = sum_{i chosen, real, HELD HERE} g_i W_d,i (silu(W_g,i y) * (W_u,i y))
      + (sum_{i chosen, zero} g_i) y

THE SHARE: this chip holds real experts ``[held k, held (k + 1))``
(``n_routed_experts`` = held, ``expert_share`` = ``{index: k, of:
n}``); the router, the choice and ``g`` are over all ``held n + zero``
columns, ``m`` sums the chosen real experts held here and every chosen
zero expert, and what the real experts held elsewhere would add is
left out.  The vocabulary is the slice the file states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: the projections that write into the residual stream are scaled by
#: 1 / sqrt(their number at the published depth): five in each of 28
#: double layers (two attention outputs, two dense feed-forwards, the
#: experts), whatever the cut
RESIDUAL_WRITERS = ("o_proj", "down_proj", "experts_down")
RESIDUAL_LAYERS = 5 * 28
#: leaves kept in float32: the router computes in float32
FLOAT32_LEAVES = ("router", "router_bias")
#: what `served_logits` can leave out of the mathematics (controls)
LEFT_OUT = ("no_experts", "no_zero", "no_s_kv", "branch_first")


def init_params(shapes, key):
    """Seeded weights for the tree of shapes the program's model
    declares, in BFLOAT16 (the dtype the source is published and served
    in; the router's two leaves stay float32): each leaf is drawn in
    float32 and cast inside the caller's one `jax.jit`, so no float32
    copy of the tree exists.  Norm scales 1, the router's selection
    bias 0, the embedding normal with standard deviation 1 (a residual
    stream of unit scale), every other leaf normal with standard
    deviation 1/sqrt(fan_in) (the input axis is the first, the second
    for the experts' stacked kernels), and the projections that WRITE
    into the residual stream (`RESIDUAL_WRITERS`) scaled by
    1/sqrt(`RESIDUAL_LAYERS`) besides, so that a sublayer adds about a
    tenth of the stream's scale, as in a trained pre-norm decoder."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        dtype = (F32 if any(name.endswith(f"['{n}']")
                            for n in FLOAT32_LEAVES) else jnp.bfloat16)
        if name.endswith("['scale']"):
            value = jnp.ones(leaf.shape, F32)
        elif name.endswith("['router_bias']"):
            value = jnp.zeros(leaf.shape, F32)
        elif "embedding" in name:
            value = jax.random.normal(k, leaf.shape, F32)
        else:
            std = leaf.shape[1 if "['experts_" in name else 0] ** -0.5
            if any(f"['{w}']" in name for w in RESIDUAL_WRITERS):
                std *= RESIDUAL_LAYERS ** -0.5
            value = jax.random.normal(k, leaf.shape, F32) * std
        out.append(value.astype(dtype))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes), out)


def _f(a):
    return a.astype(F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f(scale)


def _identity(x):
    return x


def _rope(x, theta):
    """Rotate ``x`` (S, d) by its row's position: pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _blocks(seq: int, block: int):
    return [(a, min(a + block, seq)) for a in range(0, seq, block)]


def _causal_attention(q, k, v, scale, block):
    """softmax(q k^T scale) v, causal, a block of query rows at a time
    against the keys up to the block's end."""
    out = []
    for a, b in _blocks(q.shape[0], block):
        s = (q[a:b] @ k[:b].T) * scale
        keep = jnp.arange(b)[None, :] <= jnp.arange(a, b)[:, None]
        out.append(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
                   @ v[:b])
    return jnp.concatenate(out)


def _latent_attention(p, x, *, sizes, quant, left_out):
    """MLA on ``x`` (S, dim), expanded: a head at a time, its keys and
    values made from the latent."""
    dim, heads = x.shape[1], sizes["heads"]
    rank, nope, rot = sizes["kv_lora_rank"], sizes["nope"], sizes["rope"]
    eps, theta = sizes["eps"], sizes["theta"]
    c_q = quant(x) @ quant(_f(p["q_a_proj"]["kernel"]))
    c_q = _rms_norm(c_q, p["q_a_norm"]["scale"], eps) * (
        dim / c_q.shape[1]) ** 0.5
    ckv = quant(x) @ quant(_f(p["kv_a_proj"]["kernel"]))
    c = _rms_norm(ckv[:, :rank], p["kv_a_norm"]["scale"], eps)
    if left_out != "no_s_kv":
        c = c * (dim / rank) ** 0.5
    k_r = _rope(ckv[:, rank:], theta)
    w_qb = _f(p["q_b_proj"]["kernel"]).reshape(-1, heads, nope + rot)
    w_kvb = _f(p["kv_b_proj"])
    w_o = _f(p["o_proj"]["kernel"]).reshape(heads, -1, dim)
    scale = (nope + rot) ** -0.5

    def one_head(acc, w):
        w_q, w_kv, w_out = w
        q = quant(c_q) @ quant(w_q)
        kv = quant(c) @ quant(w_kv)
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], theta)], axis=-1)
        k = jnp.concatenate([kv[:, :nope], k_r], axis=-1)
        o = _causal_attention(q, k, kv[:, nope:], scale, sizes["block"])
        return acc + quant(o) @ quant(w_out), None

    out, _ = jax.lax.scan(
        one_head, jnp.zeros_like(x),
        (w_qb.transpose(1, 0, 2), w_kvb.transpose(1, 0, 2), w_o))
    return out


def _swiglu(p, y, *, sizes, quant):
    """``W_d (silu(W_g y) * (W_u y))`` on ``y`` (S, dim), a block of
    rows at a time."""
    w_g, w_u, w_d = (_f(p[n]["kernel"])
                     for n in ("gate_proj", "up_proj", "down_proj"))

    def rows(yb):
        h = jax.nn.silu(quant(yb) @ quant(w_g)) * (quant(yb) @ quant(w_u))
        return quant(h) @ quant(w_d)

    return jnp.concatenate([rows(y[a:b])
                            for a, b in _blocks(y.shape[0], sizes["block"])])


def _experts(p, y, *, sizes, quant, left_out):
    """The expert branch on ``y`` (S, dim).  Returns its result, the
    router's choice (S, top_k) and the most rows one held expert took
    over ``sizes["capacity"]``, the static number of rows an expert's
    product is made for (0: none overflowed)."""
    seq = y.shape[0]
    held = p["experts_gate"].shape[0]
    first, real = sizes["share"] * held, sizes["shares"] * held
    scores = jax.nn.softmax(y @ _f(p["router"]), axis=-1)
    _, chosen = jax.lax.top_k(scores + _f(p["router_bias"]), sizes["top_k"])
    gate = sizes["scale"] * jnp.take_along_axis(scores, chosen, axis=-1)
    cap = min(seq, sizes["capacity"])

    def one_expert(carry, e):
        m, over = carry
        took = chosen == first + e
        mine = jnp.sum(jnp.where(took, gate, 0.0), axis=-1)
        count = jnp.sum(took.any(axis=-1))
        # the rows that took this expert, no more than ``cap`` of them
        at = jnp.nonzero(took.any(axis=-1), size=cap, fill_value=0)[0]
        w_g, w_u, w_d = (_f(jax.lax.dynamic_index_in_dim(
            p[n], e, keepdims=False))
            for n in ("experts_gate", "experts_up", "experts_down"))
        rows = y[at]
        h = (jax.nn.silu(quant(rows) @ quant(w_g))
             * (quant(rows) @ quant(w_u)))
        out = (quant(h) @ quant(w_d)) * jnp.where(
            jnp.arange(cap) < count, mine[at], 0.0)[:, None]
        return (m.at[at].add(out), jnp.maximum(over, count - cap)), None

    (m, over), _ = jax.lax.scan(
        one_expert, (jnp.zeros_like(y), jnp.int32(0)), jnp.arange(held))
    if left_out != "no_zero":
        m = m + jnp.sum(jnp.where(chosen >= real, gate, 0.0), axis=-1,
                        keepdims=True) * y
    return m, chosen, over


def _quant(low_precision):
    return {True: fp8_round, "fp8": fp8_round,
            "bf16": bf16_round}.get(low_precision, _identity)


# One sublayer a compiled program, called from Python: what is in
# float32 at one time is one sublayer's matrices (an expert's, inside
# the loop over the held experts) beside the bfloat16 tree, whatever
# order a compiler would give a whole pass's conversions.
_STATIC = ("sizes", "low_precision")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _attention_sublayer(p, scale, x, *, sizes, low_precision):
    sizes = dict(sizes)
    return x + _latent_attention(
        p, _rms_norm(x, scale, sizes["eps"]), sizes=sizes,
        quant=_quant(low_precision),
        left_out=low_precision if low_precision in LEFT_OUT else None)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _feed_forward_sublayer(p, y, x, *, sizes, low_precision):
    return x + _swiglu(p, y, sizes=dict(sizes), quant=_quant(low_precision))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _expert_branch(p, y, *, sizes, low_precision):
    left_out = low_precision if low_precision in LEFT_OUT else None
    m, chosen, over = _experts(p, y, sizes=dict(sizes),
                               quant=_quant(low_precision),
                               left_out=left_out)
    return (jnp.zeros_like(m) if left_out == "no_experts" else m), chosen, over


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, *, eps):
    return _rms_norm(x, scale, eps)


@functools.partial(jax.jit, static_argnames=("rows", "eps", "low_precision"))
def _head(x, scale, kernel, first, *, rows, eps, low_precision):
    quant = _quant(low_precision)
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    return quant(_rms_norm(x, scale, eps)) @ quant(_f(kernel))


def _double_layer(p, x, *, sizes, low_precision):
    kw = dict(sizes=sizes, low_precision=low_precision)
    eps = dict(sizes)["eps"]
    for i in (0, 1):
        x = _attention_sublayer(p[f"attn_{i}"], p[f"attn_norm_{i}"]["scale"],
                                x, **kw)
        y = _normed(x, p[f"mlp_norm_{i}"]["scale"], eps=eps)
        if i == 0:
            m, chosen, over = _expert_branch(p["experts"], y, **kw)
        x = _feed_forward_sublayer(p[f"mlp_{i}"], y, x, **kw)
        if i == 0 and low_precision == "branch_first":
            x = x + m
    if low_precision != "branch_first":
        x = x + m
    return x, chosen, over


def _forward(params, tokens, first, *, sizes, rows, low_precision):
    """Logits of ``rows`` positions from ``first``, every layer's
    routing (S, top_k) and the experts' overflow (0 where the static
    capacity held every row)."""
    x = _f(params["Embed_0"]["embedding"][tokens])
    depth = sum(1 for n in params if n.startswith("ShortcutExpertsBlock_"))
    routed, over = [], 0
    for i in range(depth):
        x, chosen, o = _double_layer(
            params[f"ShortcutExpertsBlock_{i}"], x, sizes=sizes,
            low_precision=low_precision)
        routed.append(chosen)
        over = max(over, int(o))
    logits = _head(x, params["RMSNorm_0"]["scale"],
                   params["Dense_0"]["kernel"], first, rows=rows,
                   eps=dict(sizes)["eps"], low_precision=low_precision)
    return logits, tuple(routed), over


def _sizes(config: dict, seq: int) -> tuple:
    share = config.get("expert_share") or {"index": 0, "of": 1}
    return tuple(sorted({
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "heads": int(config["num_attention_heads"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "share": int(share["index"]), "shares": int(share["of"]),
        "top_k": int(config["moe_topk"]),
        "scale": float(config["routed_scaling_factor"]),
        # rows a block of the attention and of the feed-forwards takes,
        # and rows an expert's product is made for: eight times what an
        # even router gives one of the held experts
        "block": 2112,
        "capacity": max(256, 8 * seq * int(config["moe_topk"]) // (
            int(config["n_routed_experts"]) * int(share["of"])
            + int(config.get("zero_expert_num", 0)))),
    }.items()))


def served_logits(params, config: dict, prompt, served, *, pad_to: int,
                  rows: int, low_precision: bool | str = False) -> np.ndarray:
    """Float32 logits at the positions that predict the ``served``
    tokens of one request: one pass over prompt + served tokens (causal
    in every layer, so the zero tail up to ``pad_to`` reaches nothing).
    ``rows`` >= len(served) is the static number of positions computed;
    the rows past the served ones are cut off.  ``low_precision`` picks
    a control: True or ``"fp8"`` rounds every weight matmul's operands
    to fp8, the step below the bf16 the configuration states
    (``"bf16"``: to that bf16, for `routing_flips`; the router stays in
    the float32 it states); one of `LEFT_OUT` leaves a piece of the
    mathematics out: the expert branch, the zero experts' part, the
    ``s_kv`` scale, or adds the branch after the FIRST half."""
    seq = np.zeros((pad_to,), np.int32)
    real = list(prompt) + list(served[:-1])
    seq[:len(real)] = real
    with jax.default_matmul_precision("highest"):
        logits, _, over = _forward(
            params, jnp.asarray(seq), len(prompt) - 1,
            sizes=_sizes(config, pad_to), rows=rows,
            low_precision=low_precision)
    if int(over) > 0:
        raise RuntimeError(
            f"an expert took {int(over)} rows more than the reference's "
            "static capacity: raise `capacity` in `_sizes`")
    return np.asarray(logits, np.float64)[:len(served)]


def routing_flips(params, config: dict, tokens, *, pad_to: int) -> dict:
    """How often a router's choice flips at the margin when the weight
    matmuls' operands are rounded to bfloat16 (what the configuration
    states, and about what the program computes) against the float32
    pass, over the real ``tokens`` of one sequence: ``choices`` =
    positions x expert layers, ``flipped`` = those whose chosen SET
    differs, ``held_swapped`` = of the experts that entered or left a
    set, those held here or zero (each moves its weighted part of the
    branch; a swap between two absent experts moves nothing here, the
    weights are not normalised)."""
    seq = np.zeros((pad_to,), np.int32)
    seq[:len(tokens)] = list(tokens)
    sizes = _sizes(config, pad_to)
    with jax.default_matmul_precision("highest"):
        exact, low = (_forward(params, jnp.asarray(seq), 0, sizes=sizes,
                               rows=8, low_precision=p)[1]
                      for p in (False, "bf16"))
    held = int(config["n_routed_experts"])
    first = dict(sizes)["share"] * held
    real = dict(sizes)["shares"] * held
    out = {"choices": 0, "flipped": 0, "held_swapped": 0}
    for a, b in zip(exact, low):
        a, b = np.asarray(a)[:len(tokens)], np.asarray(b)[:len(tokens)]
        gone = ~(a[:, :, None] == b[:, None, :]).any(axis=2)    # in a, not b
        came = ~(b[:, :, None] == a[:, None, :]).any(axis=2)
        here = lambda ids: (((ids >= first) & (ids < first + held))  # noqa: E731
                            | (ids >= real))
        out["choices"] += len(a)
        out["flipped"] += int(gone.any(axis=1).sum())
        out["held_swapped"] += int((gone & here(a)).sum()
                                   + (came & here(b)).sum())
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

    Not the maximum, which the dense configurations' references return
    (`nemotron-3-super-120b_reference.py` made the same choice).  The
    router takes 12 of 768 columns with little margin at the 12th
    place, bf16 activations feed it, and a zero-compute expert that
    enters or leaves a token's set moves that token's stream by ``g y``
    whole, about 6% of its scale: a request's LARGEST gap is one such
    position, and the chip's readings put 3.7 times between the
    program's largest and the fp8 control's smallest maximum where they
    put 19 times between the means (``logit_gap_limit_from`` in
    ``benchmark/traffic/docqa-closed.json``).  A flipped position adds
    its gap over the request's length to the mean; a lower precision,
    or a piece of the mathematics left out, moves every position."""
    return float(np.mean(token_gaps(logits, tokens)))


def bf16_round(x):
    return x.astype(jnp.bfloat16).astype(F32)


def fp8_round(x):
    """The control's precision: float8 e4m3 under one scale per tensor,
    the step below the bf16 the configuration states."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
