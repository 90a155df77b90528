"""The plain reference of the ``deepseek-v3.2-exp`` configuration: the
forward pass in straightforward `jax.numpy` and float32 at the highest
matmul precision, with no kernels, no cache, no batching and no
chunking of the sequence: latent attention over the keys the lightning
indexer chooses, a loop over the experts held here.  Imports nothing
of the program; it reads the parameter tree by the names the program
serves it under, whatever dtype the leaves have (bfloat16 here: one
matrix at a time is taken to float32).

The attention is written TWICE (``form``, `served_logits`), and the
CPU tests hold the two to each other.  ``"expanded"``: per-head keys
and values from ``W_kvb``, which the program's absorbed form never
makes, every causal pair scored and the pairs not chosen masked: the
equations below as they stand, 0.1 PFLOP a layer at 50k positions (62 s
a request on the chip).  ``"gathered"`` (the benchmark's): a row's
chosen latents are gathered, ``index_topk`` of them, and the row's
product with a head's ``W_kvb`` is made on the query's side, ``(q_n
W_k,h^T) . c`` for ``q_n . (c W_k,h)``: the same sums in another
order, 0.03 PFLOP a layer.  The indexer's scores, the rule and
everything else are one code for both.

``config.json`` of deepseek-ai/DeepSeek-V3.2-Exp gives the sizes; what
it does not give is listed in the configuration's file under
``assumed``, what is left out under ``omitted``.  RMSNorm at
``rms_norm_eps`` everywhere, no biases but the index key's LayerNorm, a
final RMSNorm and an untied head.  A layer (pre-norm):

    x = x + MLA(N(x))
    x = x + F(N(x))       F = SwiGLU hidden -> intermediate_size -> hidden
                          in the first ``first_k_dense_replace`` layers,
                          else Experts(.) + SwiGLU_shared(.)

``MLA`` (H heads; the normalised latents are NOT scaled):

    c_q = N(x W_qa) ; [q_n | q_r]_h = c_q W_qb
    [c | k_r] = x W_kva ; c = N(c)
    [k_n | v]_h = c W_kvb ; q_r, k_r = rope(.)   (rotate-half pairs, YaRN)
    p_h = softmax over S_t of ((q_n,h . k_n,h + q_r,h . k_r) * s)
    s = (nope + rope)^-0.5 * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    out = concat_h(p_h v_h) W_o

YaRN: pair ``i`` of the rope lanes turns at ``f_i = theta^(-2i/d)``,
blended to ``f_i / factor`` by the linear ramp between the pairs that
turn ``beta_fast`` and ``beta_slow`` times over the original context
(`_yarn_frequencies`); cos and sin are not scaled.

The LIGHTNING INDEXER chooses ``S_t`` (H_i heads of d_i):

    q^I_j = (c_q W^I_q)_j ; k^I = LayerNorm(x W^I_k) ; w = x W^I_w
    rope on the first ``rope`` lanes of q^I and k^I (MLA's frequencies)
    I[t, s] = sum_j w_{t,j} H_i^-0.5 d_i^-0.5 relu(q^I_{t,j} . k^I_s)   s <= t
    S_t = the min(index_topk, t + 1) keys of largest I[t, .],
          ties to the lower position

``Experts`` (router in float32 over all ``held x shares`` experts):

    s = sigmoid(y W_r) ; choice by s + b (b never weighs)
    groups of equal size, a group's mark the sum of its two largest
    s + b, the ``topk_group`` best groups kept; top ``k`` of s + b in them
    g_i = routed_scaling_factor s_i / sum_chosen s
    m = sum_{i chosen, HELD HERE} g_i W_d,i (silu(W_g,i y) * (W_u,i y))

THE SHARE: this chip holds experts ``[held k, held (k + 1))``
(``n_routed_experts`` = held, ``expert_share`` = ``{index: k, of:
n}``); the router, the groups, the choice and ``g`` are over all
``held n`` columns, ``m`` sums the chosen experts held here, the shared
expert is whole, and what the experts held elsewhere would add is left
out.  The vocabulary is the slice the file states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: the projections that write into the residual stream are scaled by
#: 1 / sqrt(their number at the published depth): two sublayers in
#: each of 61 layers, whatever the cut
RESIDUAL_WRITERS = ("o_proj", "down_proj", "experts_down")
RESIDUAL_LAYERS = 2 * 61
#: leaves kept in float32: the router computes in float32
FLOAT32_LEAVES = ("router", "router_bias")
#: what `served_logits` can leave out of the mathematics or change in
#: it (controls): attend the NEWEST ``index_topk`` keys in place of the
#: chosen ones; attend every key; no shared expert; no routed experts;
#: plain rope frequencies and softmax scale
LEFT_OUT = ("newest", "all_keys", "no_shared", "no_experts", "no_yarn")


def init_params(shapes, key):
    """Seeded weights for the tree of shapes the program's model
    declares, in BFLOAT16 (the router's two leaves stay float32): each
    leaf is drawn in float32 and cast inside the caller's one
    `jax.jit`, so no float32 copy of the tree exists.  Norm scales 1,
    biases 0 (the router's and the index key's LayerNorm's), the
    embedding normal with standard deviation 1, every other leaf normal
    with standard deviation 1/sqrt(fan_in) (the input axis is the
    first, the second for the experts' stacked kernels), and the
    projections that WRITE into the residual stream
    (`RESIDUAL_WRITERS`) scaled by 1/sqrt(`RESIDUAL_LAYERS`) besides."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        dtype = (F32 if any(name.endswith(f"['{n}']")
                            for n in FLOAT32_LEAVES) else jnp.bfloat16)
        if name.endswith("['scale']"):
            value = jnp.ones(leaf.shape, F32)
        elif name.endswith(("['router_bias']", "['bias']")):
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


def _layer_norm(x, scale, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * _f(scale) + _f(bias))


def _identity(x):
    return x


def _yarn_frequencies(width: int, theta: float, yarn) -> jnp.ndarray:
    """The ``width // 2`` pair frequencies; ``yarn`` = (factor,
    original context, beta_fast, beta_slow) or None."""
    i = jnp.arange(width // 2, dtype=F32)
    freq = theta ** (-2.0 * i / width)
    if yarn is None:
        return freq
    factor, original, fast, slow = yarn

    def pair(turns):
        return (width * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(fast)), 0)
    high = min(math.ceil(pair(slow)), width - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def _rope(x, freq, first: int = 0):
    """Rotate ``x`` (S, d), the rows at positions ``first ..``, by its
    row's position: pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    ang = (first + jnp.arange(x.shape[0], dtype=F32))[:, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _bands(seq: int, block: int, per: int):
    """``(first, blocks, rows)``: ``blocks`` blocks of ``rows`` query
    rows from ``first``, at most ``per`` to a band, whole blocks and the
    shorter last one apart.  The blocks of one band are ONE compiled
    body run over each of them (`_by_block`) against the keys up to the
    band's end, where a body for each of 49 blocks of 50,176 rows, each
    with its own number of keys, took 200 s to compile; the price is
    the keys between a block's end and its band's, masked."""
    whole = seq // block
    out = [(a * block, min(per, whole - a), block)
           for a in range(0, whole, per)]
    if seq % block:
        out.append((whole * block, 1, seq % block))
    return out


def _by_block(one, first: int, blocks: int, rows: int):
    """``one(start)`` for each block of a band, the results' rows
    joined."""
    starts = first + rows * jnp.arange(blocks)
    out = jax.lax.map(one, starts)
    return out.reshape(blocks * rows, *out.shape[2:])


def _rows(x, start, rows: int):
    return jax.lax.dynamic_slice_in_dim(x, start, rows)


def _pack(mask):
    """Booleans (R, N) as bits (R, N / 32)."""
    words = mask.reshape(mask.shape[0], -1, 32).astype(jnp.uint32)
    return jnp.sum(words << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def _unpack(bits):
    return ((bits[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
            ).reshape(bits.shape[0], -1).astype(bool)


def _kept(scores, first, top_k):
    """The rule on a block of query rows: ``scores`` (R, N) of the
    rows at positions ``first ..``, every key's; True where the row
    attends the key."""
    rows, keys = scores.shape
    t = first + jnp.arange(rows)[:, None]
    causal = jnp.arange(keys)[None, :] <= t
    if keys <= top_k:
        return causal
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, top_k)[0][:, -1:]
    above, at = scores > kth, scores == kth
    need = top_k - jnp.sum(above, axis=-1, keepdims=True)
    # of the keys AT the k-th value, the first ``need`` by position
    few = above | (at & (jnp.cumsum(at, axis=-1) <= need))
    return jnp.where(t + 1 <= top_k, causal, few & causal)


def _chosen(scores, first, top_k):
    """`_kept` as positions: (R, min(top_k, N)) int32, the keys the row
    at ``first + i`` attends, -1 where it sees fewer.  `jax.lax.top_k`
    puts the lower index first among equals: ties to the lower
    position."""
    rows, keys = scores.shape
    t = first + jnp.arange(rows)[:, None]
    causal = jnp.arange(keys)[None, :] <= t
    value, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                              min(top_k, keys))
    return jnp.where(value > -jnp.inf, at, -1).astype(jnp.int32)


def _selection(p, x, c_q, *, sizes, quant, left_out, positions=False):
    """Which keys each position attends: bit-packed (S, S / 32), or
    with ``positions`` (S, min(index_topk, S)) int32 (`_chosen`)."""
    seq = x.shape[0]
    heads, width, rot = sizes["index_heads"], sizes["index_dim"], sizes["rope"]
    top_k = sizes["index_topk"]
    if left_out in ("newest", "all_keys"):
        t = jnp.arange(seq)[:, None]
        if positions:
            at = t - jnp.arange(seq if left_out == "all_keys"
                                else min(top_k, seq))[None, :]
            return jnp.where(at >= 0, at, -1).astype(jnp.int32)
        s = jnp.arange(seq)[None, :]
        keep = s <= t
        if left_out == "newest":
            keep &= s > t - top_k
        return _pack(keep)
    freq = _yarn_frequencies(rot, sizes["theta"], sizes["yarn"])
    k = _layer_norm(quant(x) @ quant(_f(p["index_k_proj"]["kernel"])),
                    p["index_k_norm"]["scale"], p["index_k_norm"]["bias"],
                    sizes["eps"])
    k = jnp.concatenate([_rope(k[:, :rot], freq), k[:, rot:]], axis=-1)
    w = (quant(x) @ quant(_f(p["index_w_proj"]["kernel"]))) * (
        heads * width) ** -0.5
    w_q = _f(p["index_q_proj"]["kernel"]).reshape(-1, heads, width)
    out = []
    for first, blocks, rows in _bands(seq, sizes["index_block"],
                                      sizes["band"]):
        # the keys up to the band's end: its rows see no later one
        end = first + blocks * rows
        k_seen = quant(k[:end]).T

        def one_block(a):
            def one_head(acc, hw):
                w_head, weight = hw
                q = quant(_rows(c_q, a, rows)) @ quant(w_head)
                q = jnp.concatenate(
                    [_rope(q[:, :rot], freq, a), q[:, rot:]], axis=-1)
                return acc + weight[:, None] * jax.nn.relu(
                    quant(q) @ k_seen), None

            scores, _ = jax.lax.scan(
                one_head, jnp.zeros((rows, end), F32),
                (w_q.transpose(1, 0, 2), _rows(w, a, rows).T))
            if positions:
                at = _chosen(scores, a, top_k)
                return jnp.pad(
                    at, ((0, 0), (0, min(top_k, seq) - at.shape[1])),
                    constant_values=-1)
            return _pack(jnp.pad(_kept(scores, a, top_k),
                                 ((0, 0), (0, seq - end))))

        out.append(_by_block(one_block, first, blocks, rows))
    return jnp.concatenate(out)


def _attend(q, k, v, bits, scale, block, band):
    """softmax(q k^T scale) v over the keys ``bits`` keeps (no key
    after the row itself), a block of query rows at a time against the
    keys up to its band's end."""
    out = []
    for first, blocks, rows in _bands(q.shape[0], block, band):
        end = first + blocks * rows
        k_seen, v_seen = k[:end].T, v[:end]

        def one_block(a):
            s = (_rows(q, a, rows) @ k_seen) * scale
            keep = _unpack(_rows(bits, a, rows))[:, :end]
            return jax.nn.softmax(jnp.where(keep, s, -jnp.inf),
                                  axis=-1) @ v_seen

        out.append(_by_block(one_block, first, blocks, rows))
    return jnp.concatenate(out)


def _per_head(quant, w):
    """``quant`` on each head's slice (axis 1) of ``w``: one scale a
    head where ``quant`` rounds under one scale a tensor."""
    return jax.vmap(quant, in_axes=1, out_axes=1)(w)


def _divisor(n: int, most: int) -> int:
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _attend_chosen(c_q, at, latents, w, *, sizes, quant, freq, scale):
    """The GATHERED form: position i's softmax over the keys ``at[i]``
    (-1: none), whose latents ``latents`` (S, rank + rope) = [c | k_r]
    are gathered; a head's ``q_n . (c W_k)`` is made as ``(q_n W_k^T)
    . c`` and its ``p (c W_v)`` as ``(p c) W_v``.  ``w`` = (W_qb, W_k,
    W_v, W_o), a head on axis 1 (W_o: 0), rounded by ``quant`` a head
    at a time already.  A block of rows at a time."""
    w_qb, w_k, w_v, w_o = w
    rank, nope = sizes["kv_lora_rank"], sizes["nope"]
    block = _divisor(c_q.shape[0], sizes["gather_block"])

    def one_block(a):
        q = jnp.einsum("bq,qhn->bhn", quant(_rows(c_q, a, block)), w_qb)
        q_r = jax.vmap(lambda z: _rope(z, freq, a), 1, 1)(q[..., nope:])
        q_c = jnp.einsum("bhn,rhn->bhr", q[..., :nope], w_k)
        picked = _rows(at, a, block)
        chosen = latents[jnp.maximum(picked, 0)]          # (B, K, rank + rope)
        s = jnp.einsum("bhr,bkr->bhk", jnp.concatenate([q_c, q_r], axis=-1),
                       chosen) * scale
        prob = jax.nn.softmax(
            jnp.where(picked[:, None, :] >= 0, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhk,bkr->bhr", prob, chosen[..., :rank])
        o = _per_head(quant, jnp.einsum("bhr,rhv->bhv", o, w_v))
        return jnp.einsum("bhv,hvd->bd", o, w_o)

    out = jax.lax.map(one_block, block * jnp.arange(c_q.shape[0] // block))
    return out.reshape(c_q.shape[0], -1)


def _latent_attention(p, x, *, sizes, quant, left_out):
    """MLA on ``x`` (S, dim) over the keys the indexer chose, in the
    form ``sizes["form"]`` names."""
    dim, heads = x.shape[1], sizes["heads"]
    rank, nope, rot = sizes["kv_lora_rank"], sizes["nope"], sizes["rope"]
    eps = sizes["eps"]
    gathered = sizes["form"] == "gathered"
    yarn = None if left_out == "no_yarn" else sizes["yarn"]
    freq = _yarn_frequencies(rot, sizes["theta"], yarn)
    c_q = _rms_norm(quant(x) @ quant(_f(p["q_a_proj"]["kernel"])),
                    p["q_a_norm"]["scale"], eps)
    chosen = _selection(p, x, c_q, sizes=sizes, quant=quant,
                        left_out=left_out, positions=gathered)
    ckv = quant(x) @ quant(_f(p["kv_a_proj"]["kernel"]))
    c = _rms_norm(ckv[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_r = _rope(ckv[:, rank:], freq)
    w_qb = _f(p["q_b_proj"]["kernel"]).reshape(-1, heads, nope + rot)
    w_kvb = _f(p["kv_b_proj"])
    w_o = _f(p["o_proj"]["kernel"]).reshape(heads, -1, dim)
    m = 1.0 if yarn is None else sizes["mscale"]
    scale = (nope + rot) ** -0.5 * m * m
    if gathered:
        w_kvb = _per_head(quant, w_kvb)
        return _attend_chosen(
            c_q, chosen, jnp.concatenate([quant(c), k_r], axis=-1),
            (_per_head(quant, w_qb), w_kvb[..., :nope], w_kvb[..., nope:],
             jax.vmap(quant)(w_o)),
            sizes=sizes, quant=quant, freq=freq, scale=scale)

    def one_head(acc, w):
        w_q, w_kv, w_out = w
        q = quant(c_q) @ quant(w_q)
        kv = quant(c) @ quant(w_kv)
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], freq)], axis=-1)
        k = jnp.concatenate([kv[:, :nope], k_r], axis=-1)
        o = _attend(q, k, kv[:, nope:], chosen, scale, sizes["block"],
                    sizes["band"])
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

    w_g, w_u, w_d = quant(w_g), quant(w_u), quant(w_d)

    def rows(yb):
        h = jax.nn.silu(quant(yb) @ w_g) * (quant(yb) @ w_u)
        return quant(h) @ w_d

    # one compiled body for every block (49 bodies of 1,024 rows were
    # 121 MiB of program and 140 s to compile)
    block = _divisor(y.shape[0], sizes["block"])
    return jax.lax.map(rows, y.reshape(-1, block, y.shape[1])).reshape(
        y.shape[0], -1)


def route(scores, bias, *, top_k, groups, top_groups, scale):
    """The router's choice and weights from ``scores`` (S, E) =
    sigmoid(y W_r): ``(chosen (S, k), gate (S, k))``."""
    choice = scores + bias
    by_group = choice.reshape(choice.shape[0], groups, -1)
    mark = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    best = jax.lax.top_k(mark, top_groups)[1]
    kept = jnp.zeros(mark.shape, bool).at[
        jnp.arange(mark.shape[0])[:, None], best].set(True)
    choice = jnp.where(jnp.repeat(kept, by_group.shape[-1], axis=1), choice,
                       -jnp.inf)
    chosen = jax.lax.top_k(choice, top_k)[1]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def _experts(p, y, *, sizes, quant):
    """The routed experts held here on ``y`` (S, dim).  Returns their
    result, the router's choice (S, top_k) and the most rows one held
    expert took over ``sizes["capacity"]``, the static number of rows
    an expert's product is made for (0: none overflowed)."""
    seq = y.shape[0]
    held = p["experts_gate"].shape[0]
    first = sizes["share"] * held
    chosen, gate = route(
        jax.nn.sigmoid(y @ _f(p["router"])), _f(p["router_bias"]),
        top_k=sizes["top_k"], groups=sizes["groups"],
        top_groups=sizes["top_groups"], scale=sizes["scale"])
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
    return m, chosen, over


def _quant(low_precision):
    return {True: fp8_round, "fp8": fp8_round,
            "bf16": bf16_round}.get(low_precision, _identity)


def _left_out(low_precision):
    return low_precision if low_precision in LEFT_OUT else None


# One sublayer a compiled program, called from Python: what is in
# float32 at one time is one sublayer's matrices (an expert's, inside
# the loop over the held experts) beside the bfloat16 tree.
_STATIC = ("sizes", "low_precision")


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnames=("x",))
def _attention_sublayer(p, scale, x, *, sizes, low_precision):
    sizes = dict(sizes)
    return x + _latent_attention(
        p, _rms_norm(x, scale, sizes["eps"]), sizes=sizes,
        quant=_quant(low_precision), left_out=_left_out(low_precision))


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnames=("x",))
def _dense_sublayer(p, scale, x, *, sizes, low_precision):
    sizes = dict(sizes)
    return x + _swiglu(p, _rms_norm(x, scale, sizes["eps"]), sizes=sizes,
                       quant=_quant(low_precision))


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnames=("x",))
def _expert_sublayer(p, shared, scale, x, *, sizes, low_precision):
    sizes = dict(sizes)
    quant, left_out = _quant(low_precision), _left_out(low_precision)
    y = _rms_norm(x, scale, sizes["eps"])
    m, chosen, over = _experts(p, y, sizes=sizes, quant=quant)
    if left_out != "no_experts":
        x = x + m
    if left_out != "no_shared":
        x = x + _swiglu(shared, y, sizes=sizes, quant=quant)
    return x, chosen, over


@functools.partial(jax.jit, static_argnames=("rows", "eps", "low_precision"))
def _head(x, scale, kernel, first, *, rows, eps, low_precision):
    quant = _quant(low_precision)
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    return quant(_rms_norm(x, scale, eps)) @ quant(_f(kernel))


def _forward(params, tokens, first, *, sizes, rows, low_precision):
    """Logits of ``rows`` positions from ``first`` and the experts'
    overflow (0 where the static capacity held every row)."""
    x = _f(params["Embed_0"]["embedding"][tokens])
    depth = sum(1 for n in params if n.startswith("LatentBlock_"))
    kw = dict(sizes=sizes, low_precision=low_precision)
    over = 0
    for i in range(depth):
        p = params[f"LatentBlock_{i}"]
        x = _attention_sublayer(p["attn"], p["attn_norm"]["scale"], x, **kw)
        if "experts" in p:
            x, _, o = _expert_sublayer(
                p["experts"], p["shared_expert"], p["mlp_norm"]["scale"], x,
                **kw)
            over = max(over, int(o))
        else:
            x = _dense_sublayer(p["mlp"], p["mlp_norm"]["scale"], x, **kw)
    logits = _head(x, params["RMSNorm_0"]["scale"],
                   params["Dense_0"]["kernel"], first, rows=rows,
                   eps=dict(sizes)["eps"], low_precision=low_precision)
    return logits, over


def _sizes(config: dict, seq: int, form: str = "gathered") -> tuple:
    share = config.get("expert_share") or {"index": 0, "of": 1}
    yarn = config.get("rope_scaling")
    width = int(config["n_routed_experts"]) * int(share["of"])
    return tuple(sorted({
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "yarn": None if yarn is None else (
            float(yarn["factor"]),
            int(yarn["original_max_position_embeddings"]),
            float(yarn["beta_fast"]), float(yarn["beta_slow"])),
        "mscale": 1.0 if yarn is None else (
            0.1 * float(yarn["mscale_all_dim"])
            * math.log(float(yarn["factor"])) + 1.0),
        "heads": int(config["num_attention_heads"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "index_heads": int(config["index_n_heads"]),
        "index_dim": int(config["index_head_dim"]),
        "index_topk": int(config["index_topk"]),
        "share": int(share["index"]), "shares": int(share["of"]),
        "top_k": int(config["num_experts_per_tok"]),
        "groups": int(config["n_group"]),
        "top_groups": int(config["topk_group"]),
        "scale": float(config["routed_scaling_factor"]),
        # rows a block of the attention and of the feed-forwards takes,
        # rows a block of the indexer's scores takes, blocks to a band
        # (`_bands`: 49 blocks of 50,176 positions in 4 bands; compiling
        # for the v5e, 7 bands of 7 took 26 s, 4 take 12 s and score an
        # eighth more pairs, 1 takes 7 s and scores three quarters
        # more), and rows an expert's product is made for: four times
        # what an even router gives one of the held experts
        "block": 1024, "index_block": 1024, "band": 13,
        # which of the two writings of the attention, and the rows a
        # block of the gathered one takes (64 rows gather 302 MB of
        # latents at 2,048 keys of 576)
        "form": form, "gather_block": 64,
        "capacity": max(256, 4 * seq * int(
            config["num_experts_per_tok"]) // width),
    }.items()))


def served_logits(params, config: dict, prompt, served, *, pad_to: int,
                  rows: int, low_precision: bool | str = False,
                  form: str = "gathered") -> np.ndarray:
    """Float32 logits at the positions that predict the ``served``
    tokens of one request: one pass over prompt + served tokens (causal
    in every layer, so the zero tail up to ``pad_to`` reaches nothing).
    ``rows`` >= len(served) is the static number of positions computed;
    the rows past the served ones are cut off.  ``low_precision`` picks
    a control: True or ``"fp8"`` rounds every weight matmul's operands
    (the indexer's too) to fp8, the step below the bf16 the
    configuration states; one of `LEFT_OUT` changes the mathematics:
    ``"newest"`` attends the newest ``index_topk`` keys in place of the
    chosen ones (the SELECTION control).  ``form``: which writing of
    the attention (the module's docstring); the gathered one rounds a
    control's operands under one scale a block of rows where the
    expanded one has one a sequence."""
    if form not in ("gathered", "expanded"):
        raise ValueError(f"form {form!r}: 'gathered' or 'expanded'")
    seq = np.zeros((pad_to,), np.int32)
    real = list(prompt) + list(served[:-1])
    seq[:len(real)] = real
    with jax.default_matmul_precision("highest"):
        logits, over = _forward(
            params, jnp.asarray(seq), len(prompt) - 1,
            sizes=_sizes(config, pad_to, form), rows=rows,
            low_precision=low_precision)
    if int(over) > 0:
        raise RuntimeError(
            f"an expert took {int(over)} rows more than the reference's "
            "static capacity: raise `capacity` in `_sizes`")
    return np.asarray(logits, np.float64)[:len(served)]


def token_gaps(logits: np.ndarray, tokens) -> np.ndarray:
    """Per position, how far the token's logit lies below the best."""
    picked = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return logits.max(axis=1) - picked


def widest_gap(logits: np.ndarray, tokens) -> float:
    """What the comparison keeps of ONE request: the MEAN, over its
    served tokens, of the gap by which the token's logit lies below the
    reference's best (`token_gaps`), as `longcat-flash-omni_reference`
    and `nemotron-3-super-120b_reference` return it and for their
    reason: a router with little margin at the last chosen place (top 8
    of 256 behind a group limit) and, here, a selector with little
    margin at its 2,048th place move ONE position's logits when bf16
    operands flip them; a lower precision, or a piece of the
    mathematics changed, moves every position.  The harness takes the
    largest of these over the sampled requests and holds it to the
    traffic file's ``logit_gap_limit``."""
    return float(np.mean(token_gaps(logits, tokens)))


def bf16_round(x):
    return x.astype(jnp.bfloat16).astype(F32)


def fp8_round(x):
    """The control's precision: float8 e4m3 under one scale per tensor,
    the step below the bf16 the configuration states."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
