"""The plain reference of the ``olmo-hybrid-7b`` configuration: the
decoder's forward pass in straightforward `jax.numpy` and float32 at
the highest matmul precision, with no kernels, no cache and no
batching; the linear layers' recurrence is a `lax.scan` over tokens.
Imports nothing of the program; it reads the parameter tree by the
names the program serves it under.

The layers (``config.json`` of allenai/Olmo-Hybrid-7B gives the sizes
and ``layer_types``; what it does not give is the family's convention,
listed in the configuration's file under ``assumed``):

* linear layer, Gated DeltaNet (arXiv:2412.06464, as in
  flash-linear-attention's ``GatedDeltaNet``).  H heads, keys of dk,
  values of dv.  q~ = W_q x, k~ = W_k x, v~ = W_v x; each through its
  own depthwise causal convolution of width 4 (no bias) and SiLU.  Per
  head q <- q / |q| * dk^-1/2, k <- k / |k| (|.| with 1e-6 under the
  root, as the family's l2 norm has it).  b_t = 2 sigmoid(W_b x_t)
  (the 2 is ``linear_allow_neg_eigval``), a_t = exp(-exp(A_log)
  softplus(W_a x_t + dt_bias)).  State S (dk, dv) per head:
  S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T,
  o_t = S_t^T q_t.  y_t = W_o [RMSNorm_dv(o_t) * SiLU(W_g x_t)].
* full layer: causal multi-head attention, RMSNorm over the whole q
  and k projections, NO rotary (``rope_parameters.rope_theta`` is null
  in the source; the alternative would be OLMo 3's theta of 5e5).
* block, OLMo 2's reordered norm: h = x + RMSNorm(mixer(x)),
  y = h + RMSNorm(W_down(SiLU(W_gate h) * W_up h)); a final RMSNorm
  and an untied float32 head.

Departures from the source: depth (the configuration's ``reduced``),
and weights drawn from a seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def init_params(shapes, key):
    """Seeded weights for the tree of shapes the program's model
    declares, in the float32 the program stores: norm scales 1;
    ``A_log`` the log of a uniform in [1, 16) and ``dt_bias`` the
    inverse softplus of a log-uniform in [1e-3, 1e-1) (the family's
    initialisation); every other leaf normal with standard deviation
    1/sqrt(fan_in) (the input axis is the first: the taps, for a
    convolution; for the embedding, the model width).  Made on the
    device, to be called under one `jax.jit`."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        if name.endswith("['scale']"):
            out.append(jnp.ones(leaf.shape, leaf.dtype))
        elif name.endswith("['A_log']"):
            out.append(jnp.log(jax.random.uniform(
                k, leaf.shape, leaf.dtype, 1.0, 16.0)))
        elif name.endswith("['dt_bias']"):
            dt = jnp.exp(jax.random.uniform(
                k, leaf.shape, leaf.dtype, np.log(1e-3), np.log(1e-1)))
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        else:
            fan_in = leaf.shape[-1] if "embedding" in name else leaf.shape[0]
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype)
                       * (fan_in ** -0.5))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes), out)


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * scale


def _identity(x):
    return x


def _linear_mixer(p, x, *, heads, dk, dv, neg_eigval, quant, keep):
    """Gated DeltaNet on ``x`` (S, dim), from a zero state.  ``keep``
    is how the state is kept from one token to the next (as it is in
    the reference, rounded to bfloat16 in the second control)."""
    s = x.shape[0]

    def proj(name):
        return quant(x) @ quant(p[name]["kernel"])

    def conv(name, u):
        w = p[name]                          # (taps, channels), newest last
        taps = w.shape[0]
        pad = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1])), u])
        return jax.nn.silu(sum(pad[i:i + s] * w[i] for i in range(taps)))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = unit(conv("q_conv", proj("q_proj")).reshape(s, heads, dk)) * dk ** -0.5
    k = unit(conv("k_conv", proj("k_proj")).reshape(s, heads, dk))
    v = conv("v_conv", proj("v_proj")).reshape(s, heads, dv)
    beta = jax.nn.sigmoid(proj("b_proj")) * (2.0 if neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(proj("a_proj") + p["dt_bias"]))

    def step(state, t):
        qt, kt, vt, at, bt = t
        state = state * at[:, None, None]
        u = vt - jnp.einsum("hkv,hk->hv", state, kt)
        state = keep(state + jnp.einsum("hk,hv->hkv", kt * bt[:, None], u))
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv)),
                        (q, k, v, alpha, beta))
    o = _rms_norm(o, p["o_norm"]["scale"]).reshape(s, heads * dv)
    return quant(o * jax.nn.silu(proj("g_proj"))) @ quant(
        p["o_proj"]["kernel"])


def _full_mixer(p, x, *, quant):
    """Causal multi-head attention with QK-norm on ``x`` (S, dim)."""
    s = x.shape[0]

    def proj(name):
        return jnp.einsum("sd,dhk->shk", quant(x), quant(p[name]["kernel"]))

    def whole(t, name):
        return _rms_norm(t.reshape(s, -1), p[name]["scale"]).reshape(t.shape)

    q, k, v = whole(proj("q_proj"), "q_norm"), whole(proj("k_proj"),
                                                     "k_norm"), proj("v_proj")
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]

    def one_head(qkv):  # a head at a time: (S, S) scores fit the chip
        qh, kh, vh = qkv
        scores = jnp.where(causal, (qh @ kh.T) * (qh.shape[-1] ** -0.5),
                           -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    out = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2)
                                      for t in (q, k, v)))
    out = out.transpose(1, 0, 2).reshape(s, -1)
    return quant(out) @ quant(p["o_proj"]["kernel"])


def _block(p, x, *, linear, quant, keep):
    """One decoder block on ``x`` (S, dim).  ``quant`` rounds the two
    operands of every weight matmul (identity in the reference, fp8 in
    the first control)."""
    if "GatedDeltaNet_0" in p:
        mixed = _linear_mixer(p["GatedDeltaNet_0"], x, quant=quant,
                              keep=keep, **linear)
    else:
        mixed = _full_mixer(p["GQASelfAttention_0"], x, quant=quant)
    x = x + _rms_norm(mixed, p["RMSNorm_0"]["scale"])
    mlp = p["GatedMLP_0"]
    h = (jax.nn.silu(quant(x) @ quant(mlp["gate_proj"]["kernel"]))
         * (quant(x) @ quant(mlp["up_proj"]["kernel"])))
    return x + _rms_norm(quant(h) @ quant(mlp["down_proj"]["kernel"]),
                         p["RMSNorm_1"]["scale"])


@functools.partial(jax.jit, static_argnames=(
    "heads", "dk", "dv", "neg_eigval", "rows", "low_precision"))
def _forward(params, tokens, first, *, heads, dk, dv, neg_eigval, rows,
             low_precision):
    quant = fp8_round if low_precision in (True, "fp8") else _identity
    keep = bf16_round if low_precision == "state_bf16" else _identity
    linear = dict(heads=heads, dk=dk, dv=dv, neg_eigval=neg_eigval)
    x = params["Embed_0"]["embedding"][tokens]
    depth = sum(1 for name in params if name.startswith("TransformerBlock_"))
    for i in range(depth):
        x = _block(params[f"TransformerBlock_{i}"], x, linear=linear,
                   quant=quant, keep=keep)
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    x = _rms_norm(x, params["RMSNorm_0"]["scale"])
    return quant(x) @ quant(params["Dense_0"]["kernel"])


def served_logits(params, config: dict, prompt, served, *, pad_to: int,
                  rows: int, low_precision: bool | str = False) -> np.ndarray:
    """Float32 logits at the positions that predict the ``served``
    tokens of one request: one pass over prompt + served tokens (causal
    in every layer, so the zero tail up to ``pad_to`` reaches nothing).
    ``rows`` >= len(served) is the static number of positions computed;
    the rows past the served ones are cut off.  ``low_precision`` picks
    a control: True or ``"fp8"`` rounds every weight matmul's operands
    to fp8, the step below the bf16 the configuration states for them;
    ``"state_bf16"`` rounds the recurrent state to bfloat16 after every
    token, the step below the float32 it states for the state."""
    seq = np.zeros((pad_to,), np.int32)
    real = list(prompt) + list(served[:-1])
    seq[:len(real)] = real
    with jax.default_matmul_precision("highest"):
        logits = _forward(
            params, jnp.asarray(seq), len(prompt) - 1,
            heads=int(config["linear_num_value_heads"]),
            dk=int(config["linear_key_head_dim"]),
            dv=int(config["linear_value_head_dim"]),
            neg_eigval=bool(config["linear_allow_neg_eigval"]), rows=rows,
            low_precision=low_precision)
    return np.asarray(logits, np.float64)[:len(served)]


def widest_gap(logits: np.ndarray, tokens) -> float:
    """The widest gap by which a token's logit lies below the best."""
    picked = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(logits.max(axis=1) - picked))


def bf16_round(x):
    """The second control's precision, for the recurrent state."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def fp8_round(x):
    """The first control's precision: float8 e4m3 under one scale per
    tensor, the step below the bf16 the configuration states."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
