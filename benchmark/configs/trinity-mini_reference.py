"""The plain reference of the ``trinity-mini`` configuration: the
forward pass in straightforward `jax.numpy` and float32 at the highest
matmul precision, with no kernels, no cache and no batching: window and
full attention layers side by side, each written as a mask over the
causal pairs, a loop over the experts held here.  Imports nothing of
the program; it reads the parameter tree by the names the program
serves it under, whatever dtype the leaves have (bfloat16 here: one
matrix at a time is taken to float32).  The sequence is computed whole,
a block of query rows at a time, so that 33,792 positions fit.

``config.json`` of arcee-ai/Trinity-Mini (``model_type`` afmoe) gives
the sizes and the router; what it does not give is the published
modelling code's (transformers' ``models/afmoe``) and listed in the
configuration's file under ``assumed``.  RMSNorm at ``rms_norm_eps``
everywhere, no biases, an untied head.  With ``d`` = hidden_size:

    h = E[tok] * sqrt(d)                                (mup_enabled)

a layer (FOUR norms, one before and one after each sublayer):

    a = N1(h)
    q = a W_q (H heads x dh) ; k = a W_k ; v = a W_v (H_kv heads x dh)
    g = a W_g (H x dh)
    q = RMSNorm_dh(q) ; k = RMSNorm_dh(k)      a head, ONE scale of dh each
    sliding_attention layers only: q, k = rope(q), rope(k)
        (theta, the whole dh, rotate-half pairs (j, j + dh/2))
    o = softmax(q k^T dh^-0.5) v  over keys s <= t, and on
        sliding_attention layers also t - s < sliding_window
    h = h + N2((o * sigmoid(g)) W_o)
    m = N3(h)
    h = h + N4(F(m))

``F`` is SwiGLU ``W_d (silu(W_gate m) * (W_up m))`` at
``intermediate_size`` in the first ``num_dense_layers`` layers, after
them ``Experts(m) + SwiGLU_shared(m)`` at ``moe_intermediate_size``:

    s = sigmoid(m W_r)             float32, every ``held x shares`` column
    chosen = top k of s + b        b selects and never weighs
    w_e = route_scale * s_e / (sum of the chosen s + 1e-20)
    Experts(m) = sum_{e chosen, HELD HERE} w_e Expert_e(m)
    Expert_e(m) = W_d,e (silu(W_g,e m) * (W_u,e m))

    logits = N_f(h_L) W_head

THE SHARE: this chip holds experts ``[held k, held (k + 1))``
(``num_experts`` = held, ``expert_share`` = ``{index: k, of: n}``); the
router, the choice and ``w`` are over all ``held n`` columns,
``Experts`` sums the chosen experts held here, the shared expert is
whole, and what the experts held elsewhere would add is left out.  The
vocabulary is the slice the file states.  WHICH layers: the file's
``served_layers`` names the published layers it serves (their kinds
from ``layer_types``, which stays whole).

WEIGHTS (`init_params`).  PR 31 / 34 / 41's scheme where it applies:
kernels normal with standard deviation 1/sqrt(fan_in), the projections
that write into the residual stream scaled by 1/sqrt(2 x 32) besides,
the pre-sublayer norms N1 / N3 and the final norm 1.  Three departures,
each forced by this block.  (1) The EMBEDDING's standard deviation is
1/sqrt(d), not 1: its rows are multiplied by sqrt(d), and at 1 the
stream would start at 45 where a sublayer adds 0.1: no layer would show
in a logit.  (2) N2 / N4 normalise what a sublayer WROTE, so the
writers' 1/sqrt(64) is gone after them (RMSNorm does not see its
input's size) and a branch enters the stream at the size of N2's / N4's
scale alone: those scales carry the 1/sqrt(64), times a value drawn
from the seed between 0.5 and 1.5 a lane.  (3) The per-head scales of q
and k are drawn between 0.75 and 1.75 a lane: a score's standard
deviation is then 1.65, a softmax over 33k keys rests on a few hundred
of them, and which keys a layer may see moves every position.  Scales
away from 1 are what shows a norm in the wrong place or a scale left
out: with every scale 1, N2 in N1's place or a missing q scale changes
no number.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: the projections that write into the residual stream are scaled by
#: 1 / sqrt(their number at the published depth): two sublayers in
#: each of 32 layers, whatever the cut
RESIDUAL_WRITERS = ("o_proj", "down_proj", "experts_down")
RESIDUAL_LAYERS = 2 * 32
#: leaves kept in float32: the router computes in float32
FLOAT32_LEAVES = ("router", "router_bias")
#: the norms AFTER a sublayer, by the names the program's block gives
#: them (a block's four norms in the order they are applied)
POST_NORMS = ("RMSNorm_1", "RMSNorm_3")
#: what `served_logits` can change in the mathematics (controls): the
#: window layers served as full layers; rope on the full layers too; the
#: output gate left out; no shared expert; no routed experts
LEFT_OUT = ("window_as_full", "rope_on_full", "no_gate", "no_shared",
            "no_experts")


def init_params(shapes, key):
    """Seeded weights for the tree of shapes the program's model
    declares, in BFLOAT16 (the router's two leaves stay float32): each
    leaf is drawn in float32 and cast inside the caller's one
    `jax.jit`, so no float32 copy of the tree exists.  The scheme and
    its three departures are the module's docstring's."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        dtype = (F32 if any(name.endswith(f"['{n}']")
                            for n in FLOAT32_LEAVES) else jnp.bfloat16)
        if name.endswith("['scale']"):
            if "_norm']" in name:             # q_norm, k_norm: a head's
                value = jax.random.uniform(k, leaf.shape, F32, 0.75, 1.75)
            elif "Block_" in name and any(f"['{n}']" in name
                                          for n in POST_NORMS):
                value = RESIDUAL_LAYERS ** -0.5 * jax.random.uniform(
                    k, leaf.shape, F32, 0.5, 1.5)
            else:
                value = jnp.ones(leaf.shape, F32)
        elif name.endswith("['router_bias']"):
            value = jnp.zeros(leaf.shape, F32)
        elif "embedding" in name:
            value = jax.random.normal(k, leaf.shape, F32) * (
                leaf.shape[1] ** -0.5)
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


def _identity(x):
    return x


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f(scale)


def _rope(x, theta: float, first=0):
    """Rotate ``x`` (S, heads, d), the rows at positions ``first ..``,
    by its row's position: pairs (j, j + d/2) at theta^(-2j/d)."""
    d = x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=F32) / d)
    ang = (first + jnp.arange(x.shape[0], dtype=F32))[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _divisor(n: int, most: int) -> int:
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _attend(q, k, v, *, window, block: int):
    """softmax(q k^T dh^-0.5) v for every query row: ``q`` (S, H, dh),
    ``k`` / ``v`` (S, H_kv, dh), a group of H / H_kv query heads on a
    key head; keys ``s <= t`` and, with ``window``, ``t - s < window``.
    A block of query rows at a time: against every key (masked) without
    a window, against the ``window + block`` keys that end with the
    block's last row with one."""
    seq, heads, dh = q.shape
    group = heads // k.shape[1]
    block = _divisor(seq, block)
    # with a window: keys from ``window`` rows of zeros ahead of the
    # sequence, so that every block's slice has one length
    ahead = 0 if window is None else window
    span = seq if window is None else window + block
    kp, vp = (jnp.pad(t, ((ahead, 0), (0, 0), (0, 0))) for t in (k, v))

    def one_block(a):
        rows = jax.lax.dynamic_slice_in_dim(q, a, block)
        first = 0 if window is None else a       # of the slice, padded
        ks, vs = (jax.lax.dynamic_slice_in_dim(t, first, span)
                  for t in (kp, vp))
        s_pos = first - ahead + jnp.arange(span)[None, :]
        t_pos = a + jnp.arange(block)[:, None]
        keep = (s_pos <= t_pos) & (s_pos >= 0)
        if window is not None:
            keep &= t_pos - s_pos < window

        def one_key_head(qh, kh, vh):
            # qh (block, group, dh); kh, vh (span, dh)
            s = jnp.einsum("bgd,sd->bgs", qh, kh) * dh ** -0.5
            p = jax.nn.softmax(jnp.where(keep[:, None, :], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("bgs,sd->bgd", p, vh)

        out = jax.lax.map(
            lambda t: one_key_head(*t),
            (rows.reshape(block, -1, group, dh).transpose(1, 0, 2, 3),
             ks.transpose(1, 0, 2), vs.transpose(1, 0, 2)))
        return out.transpose(1, 0, 2, 3).reshape(block, heads * dh)

    out = jax.lax.map(one_block, block * jnp.arange(seq // block))
    return out.reshape(seq, heads * dh)


def _attention(p, a, *, sliding: bool, sizes, quant, left_out):
    """The attention sublayer's branch on ``a`` = N1(h), (S, d), ahead
    of N2."""
    eps, theta = sizes["eps"], sizes["theta"]

    def heads(name):
        w = _f(p[name]["kernel"])                # (d, heads, dh)
        return jnp.einsum("sd,dhk->shk", quant(a), quant(w))

    q, k, v = heads("q_proj"), heads("k_proj"), heads("v_proj")
    if sizes["head_norm"]:
        q = _rms_norm(q, p["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["k_norm"]["scale"], eps)
    rotate = sliding or sizes["full_rope"] or left_out == "rope_on_full"
    if rotate:
        q, k = _rope(q, theta), _rope(k, theta)
    window = sizes["window"] if sliding else None
    if left_out == "window_as_full":
        window = None
    o = _attend(q, k, v, window=window, block=sizes["block"])
    if sizes["gate"] and left_out != "no_gate":
        o = o * jax.nn.sigmoid(heads("gate_proj").reshape(o.shape))
    return quant(o) @ quant(_f(p["o_proj"]["kernel"]))


def _swiglu(p, y, *, sizes, quant):
    """``W_d (silu(W_g y) * (W_u y))`` on ``y`` (S, d), a block of rows
    at a time."""
    w_g, w_u, w_d = (quant(_f(p[n]["kernel"]))
                     for n in ("gate_proj", "up_proj", "down_proj"))

    def rows(yb):
        h = jax.nn.silu(quant(yb) @ w_g) * (quant(yb) @ w_u)
        return quant(h) @ w_d

    block = _divisor(y.shape[0], sizes["block"])
    return jax.lax.map(rows, y.reshape(-1, block, y.shape[1])).reshape(
        y.shape[0], -1)


def route(scores, bias, *, top_k: int, scale: float):
    """The router's choice and weights from ``scores`` (S, E) =
    sigmoid(m W_r): ``(chosen (S, k), weight (S, k))``."""
    chosen = jax.lax.top_k(scores + bias, top_k)[1]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def routed_experts(p, y, *, sizes, quant=_identity):
    """The routed experts held here on ``y`` (S, d): share
    ``sizes["share"]`` of the router's columns.  Returns their result,
    the router's choice (S, top_k) and the most rows one held expert
    took over ``sizes["capacity"]``, the static number of rows an
    expert's product is made for (0: none overflowed)."""
    seq = y.shape[0]
    held = p["experts_gate"].shape[0]
    first = sizes["share"] * held
    chosen, weight = route(
        jax.nn.sigmoid(y @ _f(p["router"])), _f(p["router_bias"]),
        top_k=sizes["top_k"], scale=sizes["scale"])
    cap = min(seq, sizes["capacity"])

    def one_expert(carry, e):
        m, over = carry
        took = chosen == first + e
        mine = jnp.sum(jnp.where(took, weight, 0.0), axis=-1)
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
    return m, chosen, over


def expert_feed_forward(p, shared, y, *, sizes, quant=_identity,
                        left_out=None):
    """``Experts(y) + SwiGLU_shared(y)`` and the experts' overflow."""
    m, _, over = routed_experts(p, y, sizes=sizes, quant=quant)
    out = jnp.zeros_like(y)
    if left_out != "no_experts":
        out = out + m
    if left_out != "no_shared":
        out = out + _swiglu(shared, y, sizes=sizes, quant=quant)
    return out, over


def _quant(low_precision):
    return {True: fp8_round, "fp8": fp8_round,
            "bf16": bf16_round}.get(low_precision, _identity)


def _left_out(low_precision):
    return low_precision if low_precision in LEFT_OUT else None


def _norms(p, sizes):
    """A block's norms around its two sublayers, ``(before, after)``
    each, by the program's names (the order they are applied in):
    four with sandwich norms, else the two pre-sublayer ones."""
    def norm(i):
        scale = p[f"RMSNorm_{i}"]["scale"]
        return lambda x: _rms_norm(x, scale, sizes["eps"])

    if sizes["sandwich"]:
        return (norm(0), norm(1)), (norm(2), norm(3))
    return (norm(0), _identity), (norm(1), _identity)


# One sublayer a compiled program, called from Python: what is in
# float32 at one time is one sublayer's matrices (an expert's, inside
# the loop over the held experts) beside the bfloat16 tree.
_STATIC = ("sliding", "sizes", "low_precision")


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnames=("x",))
def _attention_sublayer(p, x, *, sliding, sizes, low_precision):
    sizes = dict(sizes)
    (before, after), _ = _norms(p, sizes)
    return x + after(_attention(
        p["GQASelfAttention_0"], before(x), sliding=sliding, sizes=sizes,
        quant=_quant(low_precision), left_out=_left_out(low_precision)))


@functools.partial(jax.jit, static_argnames=_STATIC[1:],
                   donate_argnames=("x",))
def _feed_forward_sublayer(p, x, *, sizes, low_precision):
    sizes = dict(sizes)
    _, (before, after) = _norms(p, sizes)
    quant = _quant(low_precision)
    y = before(x)
    if "experts" in p:
        out, over = expert_feed_forward(
            p["experts"], p["shared_expert"], y, sizes=sizes, quant=quant,
            left_out=_left_out(low_precision))
    else:
        out, over = _swiglu(p["GatedMLP_0"], y, sizes=sizes,
                            quant=quant), jnp.int32(0)
    return x + after(out), over


@functools.partial(jax.jit, static_argnames=("rows", "eps", "low_precision"))
def _head(x, scale, kernel, first, *, rows, eps, low_precision):
    quant = _quant(low_precision)
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    return quant(_rms_norm(x, scale, eps)) @ quant(_f(kernel))


def _forward(params, tokens, first, *, sizes, kinds, rows, low_precision):
    """Logits of ``rows`` positions from ``first`` and the experts'
    overflow (0 where the static capacity held every row)."""
    d = dict(sizes)
    x = _f(params["Embed_0"]["embedding"][tokens]) * d["embed_scale"]
    kw = dict(sizes=sizes, low_precision=low_precision)
    over = 0
    for i, kind in enumerate(kinds):
        p = params[f"TransformerBlock_{i}"]
        x = _attention_sublayer(p, x, sliding=kind == "sliding_attention",
                                **kw)
        x, o = _feed_forward_sublayer(p, x, **kw)
        over = max(over, int(o))
    logits = _head(x, params["RMSNorm_0"]["scale"],
                   params["Dense_0"]["kernel"], first, rows=rows,
                   eps=d["eps"], low_precision=low_precision)
    return logits, over


def layer_kinds(config: dict) -> tuple[str, ...]:
    """The kind of each served layer: ``layer_types`` at
    ``served_layers`` (absent: the first ``num_hidden_layers``)."""
    served = config.get("served_layers",
                        range(int(config["num_hidden_layers"])))
    return tuple(config["layer_types"][i] for i in served)


def layer_sizes(config: dict, seq: int) -> tuple:
    """What the sublayers read of the configuration, as a sorted tuple
    of pairs (static under `jax.jit`)."""
    share = config.get("expert_share") or {"index": 0, "of": 1}
    width = int(config["num_experts"]) * int(share["of"])
    dim = int(config["hidden_size"])
    return tuple(sorted({
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "window": int(config["sliding_window"]),
        "full_rope": bool(config.get("full_attention_rotary", False)),
        "head_norm": bool(config.get("qk_head_norm", False)),
        "gate": bool(config.get("attention_gate", False)),
        "sandwich": bool(config.get("sandwich_norm", False)),
        "embed_scale": dim ** 0.5 if config.get("mup_enabled") else 1.0,
        "share": int(share["index"]), "shares": int(share["of"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config.get("route_scale", 1.0)),
        # rows a block of the attention and of the feed-forwards takes,
        # and rows an expert's product is made for: four times what an
        # even router gives one of the held experts
        "block": 256,
        "capacity": max(256, 4 * seq * int(
            config["num_experts_per_tok"]) // width),
    }.items()))


def served_logits(params, config: dict, prompt, served, *, pad_to: int,
                  rows: int, low_precision: bool | str = False
                  ) -> np.ndarray:
    """Float32 logits at the positions that predict the ``served``
    tokens of one request: one pass over prompt + served tokens (causal
    in every layer, so the zero tail up to ``pad_to`` reaches nothing).
    ``rows`` >= len(served) is the static number of positions computed;
    the rows past the served ones are cut off.  ``low_precision`` picks
    a control: True or ``"fp8"`` rounds every weight matmul's operands
    to fp8, the step below the bf16 the configuration states; one of
    `LEFT_OUT` changes the mathematics."""
    seq = np.zeros((pad_to,), np.int32)
    real = list(prompt) + list(served[:-1])
    seq[:len(real)] = real
    with jax.default_matmul_precision("highest"):
        logits, over = _forward(
            params, jnp.asarray(seq), len(prompt) - 1,
            sizes=layer_sizes(config, pad_to), kinds=layer_kinds(config),
            rows=rows, low_precision=low_precision)
    if int(over) > 0:
        raise RuntimeError(
            f"an expert took {int(over)} rows more than the reference's "
            "static capacity: raise `capacity` in `layer_sizes`")
    return np.asarray(logits, np.float64)[:len(served)]


def token_gaps(logits: np.ndarray, tokens) -> np.ndarray:
    """Per position, how far the token's logit lies below the best."""
    picked = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return logits.max(axis=1) - picked


def widest_gap(logits: np.ndarray, tokens) -> float:
    """What the comparison keeps of ONE request: the MEAN, over its
    served tokens, of the gap by which the token's logit lies below the
    reference's best (`token_gaps`), as the other three expert
    configurations' references return it and for their reason: a
    router with little margin at its 8th of 128 places moves ONE
    position's logits when bf16 operands flip it; a lower precision, or
    a piece of the mathematics changed, moves every position.  The
    harness takes the largest of these over the sampled requests and
    holds it to the traffic file's ``logit_gap_limit``."""
    return float(np.mean(token_gaps(logits, tokens)))


def bf16_round(x):
    return x.astype(jnp.bfloat16).astype(F32)


def fp8_round(x):
    """The control's precision: float8 e4m3 under one scale per tensor,
    the step below the bf16 the configuration states."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
